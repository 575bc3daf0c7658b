"""Shared building blocks: norms, embeddings, RoPE, FFNs, init helpers.

Models are pure pytrees (nested dicts of jax.Arrays) + pure apply functions.
Stacked-layer parameters carry a leading layer axis and are consumed with
``jax.lax.scan`` so the lowered HLO stays small enough to compile 62-layer
models on one host CPU and to keep dry-run compiles fast.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.ftcontext import site_matmul
from repro.dist.sharding import shard as _shard

Params = dict

DEFAULT_INIT_SCALE = 0.02


def scan_or_unroll(f, init, xs, unroll: bool = False):
    """lax.scan, or a python loop when ``unroll`` — the roofline probes unroll
    every sequence-mix loop so cost_analysis counts each iteration (XLA
    tallies a while body once regardless of trip count)."""
    if not unroll:
        return jax.lax.scan(f, init, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    carry = init
    for i in range(n):
        carry, y = f(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    stacked = (
        None if all(y is None for y in ys)
        else jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    )
    return carry, stacked


def dense_init(key, d_in: int, d_out: int, scale: float | None = None) -> jax.Array:
    s = DEFAULT_INIT_SCALE if scale is None else scale
    return jax.random.normal(key, (d_in, d_out), jnp.float32) * s


def embed_init(key, vocab: int, d: int) -> jax.Array:
    return jax.random.normal(key, (vocab, d), jnp.float32) * DEFAULT_INIT_SCALE


def rmsnorm_init(d: int) -> jax.Array:
    return jnp.ones((d,), jnp.float32)


def rmsnorm(x: jax.Array, g: jax.Array, eps: float = 1e-6) -> jax.Array:
    # variance in f32 (inside the reduce fusion), scale applied in x.dtype —
    # a full f32 copy of x is never demanded, so GSPMD's tensor-parallel
    # all-reduces stay in bf16 (§Perf: halves per-layer wire bytes)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * g.astype(x.dtype)


def layernorm_init(d: int) -> Params:
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def layernorm(x: jax.Array, p: Params, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True).astype(x.dtype)
    var = ((x32 - mu.astype(jnp.float32)) ** 2).mean(-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return (x - mu) * inv * p["g"].astype(x.dtype) + p["b"].astype(x.dtype)


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #
def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rope scaling (Peng et al., arXiv:2309.00071) in DeepSeek-V2's
    form: the ``rope_scaling`` group of its config.json."""
    factor: float
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature for a context stretched ``factor`` times."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(head_dim: int, theta: float, s: YaRN) -> jax.Array:
    """Rotary frequencies of each pair ``i`` of ``head_dim``: the plain
    frequency for the fast-rotating pairs (more than ``beta_fast`` turns over
    the original context), the plain one divided by ``factor`` for the slow
    ones (fewer than ``beta_slow`` turns), a linear ramp between them."""
    def turns_dim(turns: float) -> float:
        return head_dim * math.log(s.original_max_position / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(s.beta_fast)), 0)
    high = min(math.ceil(turns_dim(s.beta_slow)), head_dim - 1)
    extra = rope_freqs(head_dim, theta)
    ramp = (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3)
    m = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return extra / s.factor * (1.0 - m) + extra * m


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
               freqs: jax.Array | None = None) -> jax.Array:
    """x: (..., S, H, D); positions: (..., S).  ``freqs`` (D/2,) replaces the
    plain frequencies of ``theta`` (rope scaling)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d, theta)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((n, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# --------------------------------------------------------------------------- #
# FFNs
# --------------------------------------------------------------------------- #
def ffn_init(key, d: int, d_ff: int, gated: bool = True) -> Params:
    ks = jax.random.split(key, 3)
    p = {"up": dense_init(ks[0], d, d_ff), "down": dense_init(ks[1], d_ff, d)}
    if gated:
        p["gate"] = dense_init(ks[2], d, d_ff)
    return p


def ffn(
    x: jax.Array, p: Params, act: Callable = jax.nn.silu, ftc=None, site: str = "ffn"
) -> jax.Array:
    """``ftc`` (core.ftcontext.FTContext) routes the up/gate/down matmuls
    through the HyCA-protected virtual array — the framework's
    fault-tolerance hook.  ``ftc=None`` lowers plain matmuls."""
    mm = site_matmul(ftc, site)
    h = mm(x, p["up"])
    if "gate" in p:
        h = act(mm(x, p["gate"])) * h
    else:
        h = act(h)
    out = mm(h, p["down"])
    if out.ndim == 3:
        # pin the row-parallel reshard HERE, on the bf16 dot output, before
        # any f32 consumer can pull the convert above the all-reduce (§Perf)
        out = _shard(out, "batch", "seq", "embed")
    return out


def stack_layer_params(init_one: Callable[[jax.Array], Params], key, n: int) -> Params:
    """Initialise ``n`` layers and stack every leaf on a leading layer axis."""
    keys = jax.random.split(key, n)
    layers = [init_one(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token NLL; labels < 0 are masked out."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - ll) * mask) / jnp.maximum(mask.sum(), 1.0)


def streamed_cross_entropy(
    x: jax.Array, table: jax.Array, labels: jax.Array, n_chunks: int, true_vocab: int,
    unroll: bool = False, ftc=None,
) -> jax.Array:
    """NLL of ``x @ table.T`` computed in vocab chunks — the (B, S, V) logit
    tensor is never materialised (§Perf: the dense loss head costs ~10 layers
    of HBM traffic at 150k vocab).  The chunk loop is a rematerialised scan,
    so backward recomputes chunk logits instead of storing them.

    table: (V, d) with V % n_chunks == 0; rows >= true_vocab are padding.
    """
    b, s, d = x.shape
    v = table.shape[0]
    assert v % n_chunks == 0, (v, n_chunks)
    tc = v // n_chunks
    xf = x.reshape(b * s, d)
    lab = jnp.maximum(labels.reshape(-1), 0)
    head_mm = site_matmul(ftc, "head")
    # With a fault-aware context the label logit must come from the SAME
    # (possibly corrupted) chunk logits as the normalizer — a separate clean
    # gather would mix a faulty logsumexp with a fault-free numerator and
    # misreport the fault's impact on the loss.  The plain path keeps the
    # cheap row-gather.
    fault_path = ftc is not None and ftc.protects("head")
    if not fault_path:
        # label logit via row gather (tiny): (N, d) . (N, d) -> (N,)
        ll = jnp.sum(xf * table[lab].astype(x.dtype), axis=-1).astype(jnp.float32)

    def chunk(carry, ci):
        m, acc, llc = carry  # running max / sum-exp / label logit (N,)
        rows = jax.lax.dynamic_slice(table, (ci * tc, 0), (tc, d)).astype(x.dtype)
        lg = head_mm(xf, rows.T).astype(jnp.float32)  # (N, tc)
        pad = ci * tc + jnp.arange(tc) >= true_vocab
        lg = jnp.where(pad, -1e30, lg)
        m2 = jnp.maximum(m, lg.max(-1))
        acc = acc * jnp.exp(m - m2) + jnp.exp(lg - m2[:, None]).sum(-1)
        if fault_path:  # pick the label's logit out of this chunk's panel
            inchunk = (lab >= ci * tc) & (lab < (ci + 1) * tc)
            col = jnp.clip(lab - ci * tc, 0, tc - 1)
            got = jnp.take_along_axis(lg, col[:, None], axis=1)[:, 0]
            llc = jnp.where(inchunk, got, llc)
        return (m2, acc, llc), None

    init = (
        jnp.full((b * s,), -1e30, jnp.float32),
        jnp.zeros((b * s,), jnp.float32),
        jnp.zeros((b * s,), jnp.float32),
    )
    f = jax.checkpoint(chunk)
    if unroll:  # roofline probes: count every chunk
        carry = init
        for ci in range(n_chunks):
            carry, _ = f(carry, jnp.asarray(ci))
        m, acc, llf = carry
    else:
        (m, acc, llf), _ = jax.lax.scan(f, init, jnp.arange(n_chunks))
    if fault_path:
        ll = llf
    lse = m + jnp.log(acc)
    mask = (labels.reshape(-1) >= 0).astype(jnp.float32)
    return jnp.sum((lse - ll) * mask) / jnp.maximum(mask.sum(), 1.0)
