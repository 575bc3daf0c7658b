"""Configuration files of the dense family at a size the CPU runs in seconds."""


def small_config(arch: str) -> dict:
    """A configuration file's keys at a size the CPU runs in seconds."""
    config = {"arch": arch, "hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_hidden_layers": 2, "vocab_size": 500,
              "tie_word_embeddings": True, "n_slots": 4, "smax": 48}
    if arch == "qwen1.5-0.5b":
        config.update(hidden_act="silu", num_key_value_heads=4, rms_norm_eps=1e-6,
                      rope_theta=1e6)
    else:
        config.update(hidden_act="gelu_pytorch_tanh", num_key_value_heads=2,
                      norm_epsilon=1e-5, rope_theta=999999.4420358813)
    return config
