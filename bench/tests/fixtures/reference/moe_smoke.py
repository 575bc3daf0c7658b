"""Plain float32 reference of a fine-grained mixture-of-experts decoder,
DeepSeekMoE's layout (arXiv:2401.06066): a test fixture that a second
model family brings as a new file of ``bench/reference/``.

Imports nothing of the program.  Attention, norms, the dense FFN of the
first layers, the embedding and the head are the dense reference's
(``bench.reference.dense``); the MoE FFN of the other layers is written
out here: a softmax router over the routed experts, the ``top_k`` largest
gates renormalised to sum to 1, each chosen expert a SwiGLU FFN weighted by
its gate, plus the shared experts as one SwiGLU FFN.  Every expert is
computed for every token and the unchosen ones weighted 0: no capacity, no
dropped token.  Untied head.  Weights from the seed by the recipe the
program's MoE configurations are initialised with (router std 0.006, the
rest std 0.02).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense

ROUTER_STD = 0.006


@dataclasses.dataclass(frozen=True)
class MoESpec:
    base: dense.DenseSpec    # attention, norms, vocabulary; ``d_ff`` of the dense layers
    first_dense: int         # layers 0 .. first_dense-1 have a dense FFN
    n_experts: int
    top_k: int
    d_expert: int
    d_shared: int            # hidden size of the shared experts together


def spec_from_config(cfg: dict) -> MoESpec:
    """From a configuration file's Hugging Face keys (DeepSeekMoE names)."""
    return MoESpec(
        base=dense.DenseSpec(
            n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]), norm="rms",
            norm_eps=float(cfg["rms_norm_eps"]), gated=True, qkv_bias=False),
        first_dense=cfg["first_k_dense_replace"], n_experts=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"], d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
    )


# --------------------------------------------------------------------------- #
# weights from the seed
# --------------------------------------------------------------------------- #
def _ffn(key, d: int, f: int) -> dict:
    k = jax.random.split(key, 3)
    return {"up": dense._normal(k[0], (d, f)), "down": dense._normal(k[1], (f, d)),
            "gate": dense._normal(k[2], (d, f))}


@functools.partial(jax.jit, static_argnums=(0, 2))
def layer_weights(spec: MoESpec, seed_key, i: int) -> dict:
    """Weights of layer ``i``: the dense stack draws from the seed's third
    key, the MoE stack from its second."""
    ks = jax.random.split(seed_key, 8)
    n_moe = spec.base.n_layers - spec.first_dense
    if i < spec.first_dense:
        key = jax.random.split(ks[2], spec.first_dense)[i]
    else:
        key = jax.random.split(ks[1], n_moe)[i - spec.first_dense]
    k_attn, k_ffn = jax.random.split(key)
    a = jax.random.split(k_attn, 4)
    d, hd, b = spec.base.d_model, spec.base.head_dim, spec.base
    w = {"wq": dense._normal(a[0], (d, b.n_heads * hd)), "wk": dense._normal(a[1], (d, b.n_kv * hd)),
         "wv": dense._normal(a[2], (d, b.n_kv * hd)), "wo": dense._normal(a[3], (b.n_heads * hd, d)),
         "ln1": dense._norm_params(b), "ln2": dense._norm_params(b)}
    if i < spec.first_dense:
        return w | _ffn(k_ffn, d, b.d_ff)
    m = jax.random.split(k_ffn, 5)
    e, f = spec.n_experts, spec.d_expert
    return w | {
        "router": jax.random.normal(m[0], (d, e), jnp.float32) * ROUTER_STD,
        "e_gate": dense._normal(m[1], (e, d, f)), "e_up": dense._normal(m[2], (e, d, f)),
        "e_down": dense._normal(m[3], (e, f, d)), "shared": _ffn(m[4], d, spec.d_shared),
    }


@functools.partial(jax.jit, static_argnums=0)
def lm_head(spec: MoESpec, seed_key) -> jax.Array:
    ks = jax.random.split(seed_key, 8)
    return dense._normal(ks[7], (spec.base.padded_vocab, spec.base.d_model))[: spec.base.vocab]


# --------------------------------------------------------------------------- #
# arithmetic
# --------------------------------------------------------------------------- #
def _swiglu(h, w, mode: str):
    up = dense._mm("bsd,df->bsf", h, w["up"], mode)
    act = jax.nn.silu(dense._mm("bsd,df->bsf", h, w["gate"], mode)) * up
    return dense._mm("bsf,fd->bsd", act, w["down"], mode)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _moe_layer(spec: MoESpec, x, w, mode: str):
    """One residual block with the MoE FFN over whole sequences x: (B, S, d)."""
    b = spec.base

    def attn_one(xb):
        return dense._mm("sd,de->se", dense._attention(b, dense._norm(b, xb, w["ln1"]), w, mode),
                         w["wo"], mode)

    x = x + jax.lax.map(attn_one, x)
    h = dense._norm(b, x, w["ln2"])
    gates = jax.nn.softmax(dense._mm("bsd,de->bse", h, w["router"], mode), axis=-1)
    top, idx = jax.lax.top_k(gates, spec.top_k)
    top = top / top.sum(-1, keepdims=True)
    weight = jnp.einsum("bsk,bske->bse", top, jax.nn.one_hot(idx, spec.n_experts))
    act = (jax.nn.silu(dense._mm("bsd,edf->bsef", h, w["e_gate"], mode))
           * dense._mm("bsd,edf->bsef", h, w["e_up"], mode))
    experts = dense._mm("bsef,efd->bsed", act, w["e_down"], mode)
    return x + jnp.einsum("bse,bsed->bsd", weight, experts) + _swiglu(h, w["shared"], mode)


def logits_at(spec: MoESpec, seed: int, tokens: np.ndarray, pos: np.ndarray,
              mode: str = "f32") -> jax.Array:
    """Reference logits (B, P, vocab) at positions ``pos`` (B, P) of the
    token sequences ``tokens`` (B, S), as ``bench.reference.dense.logits_at``."""
    if mode not in ("f32", "fp8"):
        raise ValueError(f"unknown reference mode {mode!r}")
    key = jax.random.key(seed)
    x = dense.embedding(spec.base, key)[jnp.asarray(tokens, jnp.int32)]
    for i in range(spec.base.n_layers):
        layer = (functools.partial(dense._layer, spec.base) if i < spec.first_dense
                 else functools.partial(_moe_layer, spec))
        x = layer(x, layer_weights(spec, key, i), mode)
    return dense._head(spec.base, x, jnp.asarray(pos, jnp.int32), lm_head(spec, key),
                       dense._norm_params(spec.base), mode)
