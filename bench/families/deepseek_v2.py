"""The DeepSeek-V2 family (deepseek-v2-lite): latent attention (MLA) without a
query LoRA and YaRN rope, a dense first layer, then MoE layers of routed and
shared experts; untied head.

A configuration file carries config.json's keys.  Where it holds one chip's
share of an expert-parallel deployment, ``n_routed_experts`` is the number of
experts held here (experts ``[0, n)``) and ``reduced`` gives the published
count, which is the router's width.

Program config: the registry's ``deepseek-v2-lite`` entry with every size
taken from the file.  Reference: ``bench/reference/deepseek_v2.py``.  Counts:
here, for the MLA absorbed decode (``decode_calls``, ``step_model_flops``)
and for the held experts' ``ft_matmul_batched`` work (``expert_calls``).
"""
from __future__ import annotations

import dataclasses
import inspect

from bench.reference import deepseek_v2 as reference
from bench.work import Call

# keys the mapping reads, and keys that do not change what is computed
READ = {"family", "arch", "hidden_act", "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "num_hidden_layers", "vocab_size", "rope_theta", "rope_scaling",
        "tie_word_embeddings", "rms_norm_eps", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "attention_bias", "first_k_dense_replace",
        "moe_layer_freq", "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "scoring_func",
        "topk_method", "n_group", "topk_group"}
INERT = {"max_position_embeddings", "model_type", "torch_dtype", "source", "reduced", "assumed",
         "deployment", "n_slots", "smax"}
# the value the program gives each of these keys: anything else is refused
FIXED = {"hidden_act": "silu", "q_lora_rank": None, "attention_bias": False,
         "moe_layer_freq": 1, "scoring_func": "softmax", "topk_method": "greedy",
         "n_group": 1, "topk_group": 1, "routed_scaling_factor": 1,
         "tie_word_embeddings": False}
YARN_KEYS = {"type", "factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
             "mscale", "mscale_all_dim"}


def _refuse_unhonoured(config: dict) -> None:
    """Raise, naming the key, on any key the program would drop or compute
    otherwise (a key listed under ``assumed``, or set to null, passes)."""
    from repro.models import layers

    for key, value in config.items():
        if (key not in READ | INERT and key not in config.get("assumed", {})
                and value is not None):
            raise ValueError(f"the deepseek_v2 family cannot honour key {key!r} = {value!r}")
    for key, want in FIXED.items():
        if config.get(key, want) != want:
            raise ValueError(f"the program computes {key!r} = {want!r}; the file has {config[key]!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("'num_key_value_heads' differs from 'num_attention_heads': MLA has one kv per head")
    rs = config.get("rope_scaling")
    if rs is not None and (rs.get("type") != "yarn" or set(rs) != YARN_KEYS):
        raise ValueError(f"'rope_scaling' = {rs!r}: the program has YaRN only, with keys {sorted(YARN_KEYS)}")
    eps = inspect.signature(layers.rmsnorm).parameters["eps"].default
    if float(config["rms_norm_eps"]) != eps:
        raise ValueError(f"the program's rmsnorm eps is fixed at {eps}; 'rms_norm_eps' = {config['rms_norm_eps']!r}")


def lm_config(config: dict):
    """The program's ``LMConfig`` for a configuration file: the registry's
    entry for its architecture with every size taken from the file."""
    from repro.configs import get_config
    from repro.models.attention import MLAConfig
    from repro.models.layers import YaRN
    from repro.models.moe import MoEConfig

    _refuse_unhonoured(config)
    base = get_config(config["arch"])
    if (base.family, base.attn_kind) != ("moe", "mla"):
        raise ValueError(f"arch {config['arch']!r} is a {base.family}/{base.attn_kind} model, "
                         "not an MoE model with latent attention")
    s = reference.Spec.from_config(config)
    y = s.yarn
    d, f = s.d_model, s.d_expert
    return dataclasses.replace(
        base,
        n_layers=s.n_layers, d_model=d, n_heads=s.n_heads, n_kv=s.n_heads, d_ff=f,
        vocab=s.vocab, rope_theta=s.rope_theta,
        mla=MLAConfig(
            d_model=d, n_heads=s.n_heads, q_lora=None, kv_lora=s.kv_lora, d_nope=s.d_nope,
            d_rope=s.d_rope, d_v=s.d_v, rope_theta=s.rope_theta,
            rope_scaling=None if y is None else YaRN(
                factor=y.factor, original_max_position=y.original_max_position,
                beta_fast=y.beta_fast, beta_slow=y.beta_slow, mscale=y.mscale,
                mscale_all_dim=y.mscale_all_dim)),
        first_k_dense=s.first_dense, dense_d_ff=s.d_ff,
        moe=MoEConfig(
            d_model=d, n_experts=s.router_width, top_k=s.top_k, d_expert=f,
            n_shared=config["n_shared_experts"], d_shared=s.d_shared, norm_topk=s.norm_topk,
            held=None if s.held == (0, s.router_width) else s.held),
        tie_embeddings=False,
    )


reference_spec = reference.Spec.from_config
logits_at = reference.logits_at


# --------------------------------------------------------------------------- #
# counts of one decode step
# --------------------------------------------------------------------------- #
def decode_calls(config: dict, n_slots: int) -> list[Call]:
    """Every ``ft_matmul`` call of one decode step over ``n_slots`` slots:
    per layer the query, latent-down and output projections (the absorbed
    latent einsums are not protected matmuls), the dense layer's FFN, each
    MoE layer's router and shared experts, and the head.  The routed
    experts run ``ft_matmul_batched`` (:func:`expert_calls`)."""
    s, m = reference.Spec.from_config(config), n_slots
    d, L, h = s.d_model, s.n_layers, s.n_heads
    n_moe = L - s.first_dense
    return [
        Call("attn.qkv", m, d, h * (s.d_nope + s.d_rope), L),
        Call("attn.qkv", m, d, s.kv_lora + s.d_rope, L),
        Call("attn.out", m, h * s.d_v, d, L),
        Call("ffn", m, d, s.d_ff, 2 * s.first_dense),
        Call("ffn", m, s.d_ff, d, s.first_dense),
        Call("moe.router", m, d, s.router_width, n_moe),
        Call("ffn", m, d, s.d_shared, 2 * n_moe),
        Call("ffn", m, s.d_shared, d, n_moe),
        Call("head", m, d, s.padded_vocab, 1),
    ]


def expert_calls(config: dict, active: float) -> list[Call]:
    """The held experts' matmuls of one decode step in which ``active``
    slots each routed one token: ``active * top_k * held / router_width``
    expected (token, held expert) pairs a layer, each held expert a call of
    ``m = active * top_k / router_width`` rows.  So FLOPs are ``2 pairs k n``
    and bytes the held experts' bfloat16 weights plus the pairs' ``x`` and
    output, whatever implements the dispatch."""
    s = reference.Spec.from_config(config)
    lo, hi = s.held
    n_moe, held = s.n_layers - s.first_dense, hi - lo
    m = active * s.top_k / s.router_width
    return [Call("moe.expert", m, s.d_model, s.d_expert, 2 * held * n_moe),
            Call("moe.expert", m, s.d_expert, s.d_model, held * n_moe)]


def step_model_flops(config: dict, active: int, attended: int) -> float:
    """Model FLOPs of a step in which ``active`` slots each fed one token and
    attended to ``attended`` positions between them: two per weight a token
    multiplies through (the projections, the absorbed ``W_uk``/``W_uv``
    products, the dense FFN, the router, its routed experts held here and
    the shared ones, the head), and per (layer, attended position) two per
    head and latent-plus-rope dimension for the scores and two per head and
    latent dimension for the context."""
    s = reference.Spec.from_config(config)
    d, h, L = s.d_model, s.n_heads, s.n_layers
    lo, hi = s.held
    n_moe = L - s.first_dense
    attn = (d * h * (s.d_nope + s.d_rope) + d * (s.kv_lora + s.d_rope) + h * s.d_v * d
            + h * s.d_nope * s.kv_lora + h * s.kv_lora * s.d_v)
    routed = s.top_k * (hi - lo) / s.router_width
    per_token = (L * attn + s.first_dense * 3 * d * s.d_ff
                 + n_moe * (d * s.router_width + 3 * d * (routed * s.d_expert + s.d_shared))
                 + d * s.vocab)
    per_position = 2 * h * (s.kv_lora + s.d_rope) + 2 * h * s.kv_lora
    return 2.0 * per_token * active + L * per_position * attended
