"""Plain float32 reference of DeepSeek-V2's decoder (arXiv:2405.04434), as
DeepSeek-V2-Lite's config.json configures it, cut to one chip's share.

Straight ``jax.numpy`` in float32 with every matmul at ``Precision.HIGHEST``:
no kernels, no cache, no latent-space absorption.  A causal forward over
whole sequences, layer by layer.  Imports nothing of the served program.

Layer equations, from the paper and the published modelling code:

* embedding lookup; pre-norm residual blocks ``x += attn(rms1(x))``,
  ``x += ffn(rms2(x))``; RMSNorm with eps ``rms_norm_eps``; a final RMSNorm
  and an untied LM head over the vocabulary;
* attention (MLA without a query LoRA): ``q = h Wq`` split per head into
  ``d_nope`` and ``d_rope`` parts; ``[c, k_pe] = h Wkv_a``, the latent ``c``
  (``kv_lora``) RMS-normed; ``[k_nope, v] = c Wkv_b`` per head; ``q_pe`` and
  the head-shared ``k_pe`` rotated; scores ``q_nope.k_nope + q_pe.k_pe``
  times the softmax scale, causal softmax, ``v`` per head, output ``Wo``;
* rotary embedding with YaRN (``rope_scaling``): per pair ``i`` of
  ``d_rope``, ``inv_freq = f_extra m + f_extra / factor (1 - m)`` with
  ``f_extra = theta^(-2i/d_rope)`` and ``m = 1 - clip((i - low)/(high -
  low), 0, 1)``, ``low``/``high`` the floor/ceil of ``d_rope ln(L0 / (2 pi
  beta)) / (2 ln theta)`` at ``beta_fast``/``beta_slow``, clamped to
  ``[0, d_rope - 1]``; cos and sin times ``mscale(mscale) /
  mscale(mscale_all_dim)``; the softmax scale ``1/sqrt(d_nope + d_rope)``
  times ``mscale(mscale_all_dim)^2``, ``mscale(s) = 0.1 s ln(factor) + 1``;
* FFN: SwiGLU ``down(silu(gate(h)) * up(h))``; layers below
  ``first_k_dense_replace`` a dense one of ``intermediate_size``;
* MoE layers: softmax router over all ``router_width`` experts, greedy top-k,
  each chosen expert (a SwiGLU of ``moe_intermediate_size``) weighted by its
  gate probability, unnormalised unless ``norm_topk_prob``, times
  ``routed_scaling_factor``; plus the shared experts as one SwiGLU.

The chip's share: the router keeps its published width, and only experts
``[lo, hi)`` (the ones the chip holds) give their part; what the others
would add is left out, as in the served program.  Each held expert is
computed for every token and weighted 0 where not chosen: no capacity, no
dropped token.

Departures from the published model, also in the served program:

* the rotary embedding rotates the two halves of the rope part
  (rotate-half form); DeepSeek-V2 rotates interleaved pairs.  With random
  weights that is a fixed permutation of the rope columns of ``Wq`` and
  ``Wkv_a``;
* random weights by the program's recipe (normal, std 0.02; the router std
  0.006; norm gains 1), generated here from the seed.  A held expert ``e``
  is row ``e`` of the whole layer's draw of every expert.

``mode="fp8"`` is the control: every matmul operand rounded to float8 e4m3
with one amax scale per tensor before a float32 product.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense
from bench.reference.dense import _mm, _normal

ROUTER_STD = 0.006


@dataclasses.dataclass(frozen=True)
class YarnSpec:
    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


@dataclasses.dataclass(frozen=True)
class Spec:
    n_layers: int
    d_model: int
    n_heads: int
    kv_lora: int
    d_nope: int
    d_rope: int
    d_v: int
    vocab: int
    rope_theta: float
    yarn: YarnSpec | None
    eps: float
    first_dense: int      # layers 0 .. first_dense-1 have a dense FFN
    d_ff: int             # the dense FFN's hidden size
    router_width: int     # experts the router scores
    held: tuple           # (lo, hi): the experts whose part this share gives
    top_k: int
    d_expert: int
    d_shared: int         # hidden size of the shared experts together
    norm_topk: bool
    routed_scale: float

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256

    @classmethod
    def from_config(cls, cfg: dict) -> "Spec":
        """From a configuration file's DeepSeek-V2 keys; the router width is
        the published ``n_routed_experts`` under ``reduced`` where the file
        holds a share, and the share is experts ``[0, n_routed_experts)``."""
        rs = cfg.get("rope_scaling")
        held = cfg["n_routed_experts"]
        return cls(
            n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"], kv_lora=cfg["kv_lora_rank"],
            d_nope=cfg["qk_nope_head_dim"], d_rope=cfg["qk_rope_head_dim"],
            d_v=cfg["v_head_dim"], vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
            yarn=None if rs is None else YarnSpec(
                factor=float(rs["factor"]),
                original_max_position=int(rs["original_max_position_embeddings"]),
                beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
                mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"])),
            eps=float(cfg["rms_norm_eps"]), first_dense=cfg["first_k_dense_replace"],
            d_ff=cfg["intermediate_size"],
            router_width=cfg.get("reduced", {}).get("n_routed_experts", held),
            held=(0, held), top_k=cfg["num_experts_per_tok"],
            d_expert=cfg["moe_intermediate_size"],
            d_shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            norm_topk=bool(cfg["norm_topk_prob"]),
            routed_scale=float(cfg["routed_scaling_factor"]),
        )


# --------------------------------------------------------------------------- #
# rotary embedding and softmax scale
# --------------------------------------------------------------------------- #
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(spec: Spec) -> np.ndarray:
    """(d_rope / 2,) float64 rotary frequencies."""
    d, theta = spec.d_rope, spec.rope_theta
    f_extra = 1.0 / theta ** (np.arange(0, d, 2) / d)
    y = spec.yarn
    if y is None:
        return f_extra

    def dim_of(turns):
        return d * math.log(y.original_max_position / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(y.beta_fast)), 0)
    high = min(math.ceil(dim_of(y.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low if high != low else 1e-3), 0, 1)
    m = 1.0 - ramp
    return f_extra / y.factor * (1 - m) + f_extra * m


def rope_mscale(spec: Spec) -> float:
    """The factor on cos and sin."""
    y = spec.yarn
    return 1.0 if y is None else _yarn_mscale(y.factor, y.mscale) / _yarn_mscale(y.factor, y.mscale_all_dim)


def softmax_scale(spec: Spec) -> float:
    scale = 1.0 / math.sqrt(spec.d_nope + spec.d_rope)
    y = spec.yarn
    if y is not None and y.mscale_all_dim:
        scale *= _yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _rope(x, spec: Spec):
    """x: (S, H, d_rope) at positions 0..S-1, rotate-half form."""
    s, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * jnp.asarray(inv_freq(spec), jnp.float32)
    cos, sin = jnp.cos(ang) * rope_mscale(spec), jnp.sin(ang) * rope_mscale(spec)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# --------------------------------------------------------------------------- #
# weights from the seed
# --------------------------------------------------------------------------- #
def _swiglu_weights(key, d: int, f: int) -> dict:
    k = jax.random.split(key, 3)
    return {"up": _normal(k[0], (d, f)), "down": _normal(k[1], (f, d)), "gate": _normal(k[2], (d, f))}


@functools.partial(jax.jit, static_argnums=(0, 2))
def layer_weights(spec: Spec, seed_key, i: int) -> dict:
    """Weights of layer ``i``: the dense layers draw from the seed's third
    key, the MoE layers from its second."""
    ks = jax.random.split(seed_key, 8)
    n_moe = spec.n_layers - spec.first_dense
    if i < spec.first_dense:
        key = jax.random.split(ks[2], spec.first_dense)[i]
    else:
        key = jax.random.split(ks[1], n_moe)[i - spec.first_dense]
    k_attn, k_ffn = jax.random.split(key)
    a = jax.random.split(k_attn, 6)
    d, h = spec.d_model, spec.n_heads
    ones = jnp.ones((d,), jnp.float32)
    w = {"wq": _normal(a[0], (d, h * (spec.d_nope + spec.d_rope))),
         "wkv_a": _normal(a[2], (d, spec.kv_lora + spec.d_rope)),
         "kv_norm": jnp.ones((spec.kv_lora,), jnp.float32),
         "wkv_b": _normal(a[3], (spec.kv_lora, h * (spec.d_nope + spec.d_v))),
         "wo": _normal(a[4], (h * spec.d_v, d)), "ln1": ones, "ln2": ones}
    if i < spec.first_dense:
        return w | _swiglu_weights(k_ffn, d, spec.d_ff)
    m = jax.random.split(k_ffn, 5)
    e, f = spec.router_width, spec.d_expert
    lo, hi = spec.held
    return w | {
        "router": jax.random.normal(m[0], (d, e), jnp.float32) * ROUTER_STD,
        "e_gate": _normal(m[1], (e, d, f))[lo:hi], "e_up": _normal(m[2], (e, d, f))[lo:hi],
        "e_down": _normal(m[3], (e, f, d))[lo:hi], "shared": _swiglu_weights(m[4], d, spec.d_shared),
    }


@functools.partial(jax.jit, static_argnums=0)
def embedding(spec: Spec, seed_key) -> jax.Array:
    ks = jax.random.split(seed_key, 8)
    return _normal(ks[0], (spec.padded_vocab, spec.d_model))[: spec.vocab]


@functools.partial(jax.jit, static_argnums=0)
def lm_head(spec: Spec, seed_key) -> jax.Array:
    ks = jax.random.split(seed_key, 8)
    return _normal(ks[7], (spec.padded_vocab, spec.d_model))[: spec.vocab]


# --------------------------------------------------------------------------- #
# arithmetic
# --------------------------------------------------------------------------- #
def _rms(spec: Spec, x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + spec.eps) * g


def _attention(spec: Spec, h, w, mode: str):
    """h: (S, d) one normed sequence -> (S, d) after ``Wo``."""
    s = h.shape[0]
    nh, dn, dr, dv = spec.n_heads, spec.d_nope, spec.d_rope, spec.d_v
    q = _mm("sd,de->se", h, w["wq"], mode).reshape(s, nh, dn + dr)
    kv_a = _mm("sd,de->se", h, w["wkv_a"], mode)
    c = _rms(spec, kv_a[:, : spec.kv_lora], w["kv_norm"])
    k_pe = _rope(kv_a[:, spec.kv_lora:][:, None, :], spec)          # (S, 1, dr)
    q_pe = _rope(q[..., dn:], spec)
    kv = _mm("sl,le->se", c, w["wkv_b"], mode).reshape(s, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (s, nh, dr))], -1)
    qk = jnp.concatenate([q[..., :dn], q_pe], -1)
    sc = _mm("qhd,khd->hqk", qk, k, mode) * softmax_scale(spec)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    out = _mm("hqk,khd->qhd", p, v, mode).reshape(s, nh * dv)
    return _mm("se,ed->sd", out, w["wo"], mode)


def _swiglu(h, w, mode: str):
    up = _mm("sd,df->sf", h, w["up"], mode)
    act = jax.nn.silu(_mm("sd,df->sf", h, w["gate"], mode)) * up
    return _mm("sf,fd->sd", act, w["down"], mode)


def route(spec: Spec, h, router, mode: str = "f32"):
    """(S, E_held) combine weights of the held experts for h: (S, d)."""
    gates = jax.nn.softmax(_mm("sd,de->se", h, router, mode), axis=-1)
    top, idx = jax.lax.top_k(gates, spec.top_k)
    if spec.norm_topk:
        top = top / top.sum(-1, keepdims=True)
    # elementwise, so exact in float32 on any backend (an einsum at default
    # precision may round the gates to bfloat16 on a TPU)
    weight = (jax.nn.one_hot(idx, spec.router_width) * top[..., None]).sum(-2) * spec.routed_scale
    lo, hi = spec.held
    return weight[:, lo:hi]


def moe_ffn(spec: Spec, h, w, mode: str = "f32"):
    """The held experts' part plus the shared experts, for h: (S, d)."""
    weight = route(spec, h, w["router"], mode)
    act = (jax.nn.silu(_mm("sd,edf->sef", h, w["e_gate"], mode))
           * _mm("sd,edf->sef", h, w["e_up"], mode))
    experts = _mm("sef,efd->sed", act, w["e_down"], mode)
    return jnp.einsum("se,sed->sd", weight, experts, precision=dense.HIGHEST) + _swiglu(h, w["shared"], mode)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer(spec: Spec, x, w, moe: bool, mode: str):
    """One residual block over a batch of whole sequences x: (B, S, d),
    one sequence at a time."""
    def one(xb):
        xb = xb + _attention(spec, _rms(spec, xb, w["ln1"]), w, mode)
        h = _rms(spec, xb, w["ln2"])
        return xb + (moe_ffn(spec, h, w, mode) if moe else _swiglu(h, w, mode))

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(spec: Spec, x, pos, table, mode: str):
    h = jnp.take_along_axis(x, pos[..., None], axis=1)
    return _mm("bpd,vd->bpv", _rms(spec, h, 1.0), table, mode)


def logits_at(spec: Spec, seed: int, tokens: np.ndarray, pos: np.ndarray,
              mode: str = "f32") -> jax.Array:
    """Reference logits (B, P, vocab) at positions ``pos`` (B, P) of the
    token sequences ``tokens`` (B, S); the logits at position ``t`` predict
    token ``t + 1``."""
    if mode not in ("f32", "fp8"):
        raise ValueError(f"unknown reference mode {mode!r}")
    key = jax.random.key(seed)
    x = embedding(spec, key)[jnp.asarray(tokens, jnp.int32)]
    for i in range(spec.n_layers):
        x = _layer(spec, x, layer_weights(spec, key, i), i >= spec.first_dense, mode)
    return _head(spec, x, jnp.asarray(pos, jnp.int32), lm_head(spec, key), mode)
