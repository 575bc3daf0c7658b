"""The dense decoder family (qwen1.5, starcoder2): GQA attention with RoPE,
an MLP per layer, RMSNorm or LayerNorm.

Program config: the registry's ``LMConfig`` for the file's ``arch`` with
every size taken from the file.  Reference: ``bench/reference/dense.py``.
Counts: ``bench/work.py``.
"""
from __future__ import annotations

import dataclasses
import inspect

from bench import work
from bench.reference import dense as reference

# keys the mapping reads, and keys that do not change what is computed
READ = {"family", "arch", "hidden_act", "hidden_size", "intermediate_size",
        "num_attention_heads", "num_hidden_layers", "num_key_value_heads", "vocab_size",
        "rope_theta", "tie_word_embeddings", "norm_type"}
INERT = {"max_position_embeddings", "torch_dtype", "source", "reduced", "assumed",
         "deployment", "n_slots", "smax"}
# per norm: the key of its eps, the program's norm function and ``norm_type``
NORMS = {"ln": ("norm_epsilon", "layernorm", "layer_norm"),
         "rms": ("rms_norm_eps", "rmsnorm", "rms_norm")}


def _refuse_unhonoured(config: dict, norm: str) -> None:
    """Raise, naming the key, on any key the dense program would drop: MoE,
    MLA, rope scaling or anything else unread (a key listed under
    ``assumed``, or set to null, passes), and on a norm eps other than the
    program's fixed one."""
    from repro.models import layers

    eps_key, fn, norm_type = NORMS[norm]
    for key, value in config.items():
        if (key not in READ | INERT | {eps_key} and key not in config.get("assumed", {})
                and value is not None):
            raise ValueError(f"the dense family cannot honour key {key!r} = {value!r}")
    eps = inspect.signature(getattr(layers, fn)).parameters["eps"].default
    if eps_key in config and float(config[eps_key]) != eps:
        raise ValueError(f"the program's {fn} eps is fixed at {eps}; {eps_key!r} = {config[eps_key]!r}")
    if config.get("norm_type", norm_type) != norm_type:
        raise ValueError(f"'norm_type' = {config['norm_type']!r} does not match {eps_key!r}")


def lm_config(config: dict):
    """The program's ``LMConfig`` for a configuration file: the registry's
    entry for its architecture with every size taken from the file."""
    from repro.configs import get_config

    norm = "ln" if "norm_epsilon" in config else "rms"
    _refuse_unhonoured(config, norm)
    base = get_config(config["arch"])
    if (base.family, base.attn_kind) != ("dense", "gqa"):
        raise ValueError(f"arch {config['arch']!r} is a {base.family}/{base.attn_kind} model, "
                         "not a dense GQA decoder")
    if not config["tie_word_embeddings"]:
        raise ValueError("'tie_word_embeddings' = false: the dense reference's head is the embedding")
    act = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}[config["hidden_act"]]
    return dataclasses.replace(
        base,
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_kv=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        head_dim=None, rope_theta=float(config["rope_theta"]),
        norm=norm, gated_ffn=act == "silu", act=act,
        tie_embeddings=bool(config["tie_word_embeddings"]),
    )


reference_spec = reference.DenseSpec.from_config
logits_at = reference.logits_at
decode_calls = work.decode_calls
step_model_flops = work.step_model_flops
