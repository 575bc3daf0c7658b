"""Test fixture: a second model family, brought as new files alone.

Fine-grained MoE decoders of the registry (``deepseek-moe-16b``) from a
configuration file with DeepSeekMoE's Hugging Face keys.  Reference: the
fixture's ``bench/reference/moe_smoke.py``, found beside this file in the
checkout it was loaded from.
"""
from __future__ import annotations

import dataclasses
import pathlib

from bench import families
from bench.work import Call

reference = families.load_file(pathlib.Path(__file__).resolve().parents[1] / "reference" / "moe_smoke.py")
reference_spec = reference.spec_from_config
logits_at = reference.logits_at


def lm_config(config: dict):
    from repro.configs import get_config
    from repro.models.moe import MoEConfig

    if not config["norm_topk_prob"]:
        raise ValueError("the program renormalises the top-k gates: 'norm_topk_prob' must be true")
    f, n_shared = config["moe_intermediate_size"], config["n_shared_experts"]
    return dataclasses.replace(
        get_config(config["arch"]),
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_kv=config["num_key_value_heads"],
        d_ff=f, vocab=config["vocab_size"], rope_theta=float(config["rope_theta"]),
        first_k_dense=config["first_k_dense_replace"], dense_d_ff=config["intermediate_size"],
        moe=MoEConfig(d_model=config["hidden_size"], n_experts=config["n_routed_experts"],
                      top_k=config["num_experts_per_tok"], d_expert=f, n_shared=n_shared,
                      d_shared=n_shared * f),
        tie_embeddings=bool(config["tie_word_embeddings"]), remat=False,
    )


def _sizes(config: dict) -> dict:
    s = reference.spec_from_config(config)
    b = s.base
    return {"d": b.d_model, "q": b.n_heads * b.head_dim, "kv": b.n_kv * b.head_dim,
            "L": b.n_layers, "dense": s.first_dense, "moe": b.n_layers - s.first_dense,
            "ff": b.d_ff, "e": s.n_experts, "k": s.top_k, "f": s.d_expert, "sh": s.d_shared,
            "vocab": b.vocab, "vocab_rows": b.padded_vocab, "attn_pos": 4 * b.n_heads * b.head_dim}


def decode_calls(config: dict, n_slots: int) -> list[Call]:
    """The ``ft_matmul`` kernel's calls of one decode step; the experts run
    ``ft_matmul_batched``, another kernel, and are not among them."""
    s, m = _sizes(config), n_slots
    d, L = s["d"], s["L"]
    qkv = ([Call("attn.qkv", m, d, s["q"], 3 * L)] if s["kv"] == s["q"] else
           [Call("attn.qkv", m, d, s["q"], L), Call("attn.qkv", m, d, s["kv"], 2 * L)])
    return qkv + [
        Call("attn.out", m, s["q"], d, L),
        Call("ffn", m, d, s["ff"], 2 * s["dense"]),
        Call("ffn", m, s["ff"], d, s["dense"]),
        Call("moe.router", m, d, s["e"], s["moe"]),
        Call("ffn", m, d, s["sh"], 2 * s["moe"]),
        Call("ffn", m, s["sh"], d, s["moe"]),
        Call("head", m, d, s["vocab_rows"], 1),
    ]


def step_model_flops(config: dict, active: int, attended: int) -> float:
    """Two FLOPs per weight a token multiplies through (the router, its
    ``top_k`` experts and the shared ones in an MoE layer; the head at the
    true vocabulary) for each active slot, and four per (layer, head,
    head-dim, attended position)."""
    s = _sizes(config)
    d = s["d"]
    attn = d * s["q"] + 2 * d * s["kv"] + s["q"] * d
    per_token = (s["L"] * attn + s["dense"] * 3 * d * s["ff"]
                 + s["moe"] * (d * s["e"] + 3 * d * (s["k"] * s["f"] + s["sh"]))
                 + d * s["vocab"])
    return 2.0 * per_token * active + s["L"] * s["attn_pos"] * attended
