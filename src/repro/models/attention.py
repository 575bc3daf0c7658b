"""Attention variants: GQA (covers MHA), MLA (MiniCPM3/DeepSeek style), with
blockwise (flash-style) training attention and KV-cache decode steps.

Blockwise attention scans over query blocks so the (S × S) score matrix is
never materialised — required for the prefill_32k shape cells to fit HBM.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.ftcontext import site_matmul
from repro.dist.sharding import shard as _shard
from repro.models.layers import (
    Params,
    YaRN,
    apply_rope,
    dense_init,
    rmsnorm,
    rmsnorm_init,
    scan_or_unroll,
    yarn_freqs,
    yarn_mscale,
)


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int | None = None
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    q_block: int = 512

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads


# --------------------------------------------------------------------------- #
# GQA
# --------------------------------------------------------------------------- #
def gqa_init(key, cfg: AttnConfig) -> Params:
    ks = jax.random.split(key, 4)
    hd = cfg.hd
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv * hd),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv * hd),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.n_kv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.n_kv * hd,), jnp.float32)
    return p


def _qkv(x, p, cfg: AttnConfig, positions, ftc=None):
    b, s, _ = x.shape
    hd = cfg.hd
    mm = site_matmul(ftc, "attn.qkv")
    q = mm(x, p["wq"])
    k = mm(x, p["wk"])
    v = mm(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv, hd)
    v = v.reshape(b, s, cfg.n_kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _grouped_scores(qb, k, scale):
    """qb: (B,qb,Hk,G,D), k: (B,S,Hk,D) -> (B,qb,Hk,G,S) fp32."""
    return jnp.einsum(
        "bqhgd,bshd->bqhgs", qb.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale


def blockwise_causal_attention(q, k, v, n_kv: int, q_block: int, unroll: bool = False) -> jax.Array:
    """q: (B,S,Hq,D); k, v: (B,S,Hk,D); returns (B,S,Hq,D).

    Scans query blocks; each block sees the full K/V panel with a causal mask
    (peak score memory B·qb·Hq·S instead of B·S·Hq·S).
    """
    b, s, hq, d = q.shape
    g = hq // n_kv
    scale = 1.0 / (d ** 0.5)
    qb = min(q_block, s)
    assert s % qb == 0, (s, qb)
    nblk = s // qb
    qr = q.reshape(b, nblk, qb, n_kv, g, d)
    kpos = jnp.arange(s)

    def body(carry, inp):
        blk_idx, qblk = inp
        qpos = blk_idx * qb + jnp.arange(qb)
        sc = _grouped_scores(qblk, k, scale)  # (B,qb,Hk,G,S)
        mask = kpos[None, :] <= qpos[:, None]  # (qb, S)
        sc = jnp.where(mask[None, :, None, None, :], sc, -1e30)
        wts = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("bqhgs,bshd->bqhgd", wts, v.astype(jnp.float32))
        return carry, out.astype(q.dtype)

    _, outs = scan_or_unroll(body, None, (jnp.arange(nblk), qr.swapaxes(0, 1)), unroll)
    return outs.swapaxes(0, 1).reshape(b, s, hq, d)


def gqa_forward(x, p, cfg: AttnConfig, positions=None, unroll: bool = False, ftc=None) -> jax.Array:
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _qkv(x, p, cfg, positions, ftc)
    out = blockwise_causal_attention(q, k, v, cfg.n_kv, cfg.q_block, unroll)
    out = site_matmul(ftc, "attn.out")(out.reshape(b, s, cfg.n_heads * cfg.hd), p["wo"])
    return _shard(out, "batch", "seq", "embed")  # bf16 reshard point (§Perf)


def _step_rows(idx: jax.Array, n_rows: int, advance=None, chunk=None):
    """Cache slot, position and write flag of each row of a decode step, and
    the cache lengths after it.

    The first ``B = idx.shape[0]`` rows are the slots' own tokens: row ``b``
    sits at position ``idx[b]`` of slot ``b`` and writes there where
    ``advance[b]`` (every row by default).  With ``chunk`` (``{"slot": (),
    "len": ()}`` int32) the remaining ``n_rows - B`` rows are a prompt chunk
    of slot ``chunk["slot"]`` at positions ``idx[slot], idx[slot] + 1, …``;
    the first ``chunk["len"]`` of them write, the padding past it writes
    nothing.  A row that does not write leaves every cache leaf as it was.
    """
    b = idx.shape[0]
    slot = jnp.arange(b)
    pos = idx
    write = jnp.ones((b,), bool) if advance is None else advance
    new_idx = idx + write.astype(idx.dtype)
    if chunk is not None:
        i = jnp.arange(n_rows - b)
        slot = jnp.concatenate([slot, jnp.full(i.shape, chunk["slot"], slot.dtype)])
        pos = jnp.concatenate([pos, idx[chunk["slot"]] + i])
        write = jnp.concatenate([write, i < chunk["len"]])
        new_idx = new_idx.at[chunk["slot"]].add(chunk["len"])
    return slot, pos, write, new_idx


def _write_rows(leaf, slot, pos, write, new):
    """``leaf[slot[r], pos[r]] = new[r]`` for the rows that write."""
    drop = jnp.where(write, pos, leaf.shape[1])      # out of range: dropped
    return leaf.at[slot, drop].set(new.astype(leaf.dtype), mode="drop")


def _chunk_view(a, b: int):
    """Rows ``b:`` of a step's ``(rows, 1, …)`` array as one sequence ``(1, C, …)``."""
    return a[b:].swapaxes(0, 1)


def _cached_attention(q, k, v, qpos, scale):
    """q: (N,Q,Hk,G,D) at positions qpos (N,Q) over k, v: (N,S,Hk,D); a
    query sees the positions up to its own.  Returns (N,Q,Hk,G,D) fp32."""
    sc = _grouped_scores(q, k, scale)  # (N,Q,Hk,G,S)
    valid = jnp.arange(k.shape[1]) <= qpos[..., None]  # (N,Q,S)
    sc = jnp.where(valid[:, :, None, None, :], sc, -1e30)
    wts = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bqhgs,bshd->bqhgd", wts, v.astype(jnp.float32))


def gqa_decode(x, p, cfg: AttnConfig, cache: Params, ftc=None, *, advance=None,
               chunk=None) -> tuple[jax.Array, Params]:
    """One decode step. x: (B,1,d), one token a slot; cache: {k,v:
    (B,Smax,Hk,D), idx: (B,)}.  With ``chunk`` (see :func:`_step_rows`), x is
    (B + C, 1, d): the slots' tokens, then C prompt tokens of one slot, which
    attend causally to that slot's cache and to each other.  The projections
    run over all rows at once; ``advance`` masks which slot rows write."""
    b = cache["idx"].shape[0]
    idx = cache["idx"]  # (B,) current length
    slot, pos, write, new_idx = _step_rows(idx, x.shape[0], advance, chunk)
    q, k_new, v_new = _qkv(x, p, cfg, pos[:, None], ftc)
    k_cache = _write_rows(cache["k"], slot, pos, write, k_new[:, 0])
    v_cache = _write_rows(cache["v"], slot, pos, write, v_new[:, 0])
    g = cfg.n_heads // cfg.n_kv
    scale = 1.0 / (cfg.hd ** 0.5)
    qh = q.reshape(-1, 1, cfg.n_kv, g, cfg.hd)
    out = _cached_attention(qh[:b], k_cache, v_cache, idx[:, None], scale)
    if chunk is not None:
        s = chunk["slot"]
        out_c = _cached_attention(_chunk_view(qh, b), k_cache[s][None], v_cache[s][None],
                                  pos[None, b:], scale)
        out = jnp.concatenate([out, out_c.swapaxes(0, 1)])
    out = out.reshape(-1, 1, cfg.n_heads * cfg.hd).astype(x.dtype)
    new_cache = {"k": k_cache, "v": v_cache, "idx": new_idx}
    return site_matmul(ftc, "attn.out")(out, p["wo"]), new_cache


def gqa_cache_init(cfg: AttnConfig, batch: int, smax: int, dtype=jnp.bfloat16) -> Params:
    return {
        "k": jnp.zeros((batch, smax, cfg.n_kv, cfg.hd), dtype),
        "v": jnp.zeros((batch, smax, cfg.n_kv, cfg.hd), dtype),
        "idx": jnp.zeros((batch,), jnp.int32),
    }


# --------------------------------------------------------------------------- #
# MLA — multi-head latent attention (MiniCPM3 / DeepSeek-V2)
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora: int | None = 768     # None: one query projection, no low-rank path
    kv_lora: int = 256
    d_nope: int = 64
    d_rope: int = 32
    d_v: int = 64
    rope_theta: float = 10000.0
    q_block: int = 512
    rope_scaling: YaRN | None = None

    @property
    def softmax_scale(self) -> float:
        """1/sqrt(d_nope + d_rope), times YaRN's temperature squared."""
        scale = 1.0 / ((self.d_nope + self.d_rope) ** 0.5)
        s = self.rope_scaling
        if s is not None and s.mscale_all_dim:
            scale *= yarn_mscale(s.factor, s.mscale_all_dim) ** 2
        return scale

    def rope(self, x, positions):
        """Rotary embedding of the rope part, with YaRN's frequencies and
        its cos/sin attenuation where the config scales the rope."""
        s = self.rope_scaling
        if s is None:
            return apply_rope(x, positions, self.rope_theta)
        x = apply_rope(x, positions, freqs=yarn_freqs(x.shape[-1], self.rope_theta, s))
        atten = yarn_mscale(s.factor, s.mscale) / yarn_mscale(s.factor, s.mscale_all_dim)
        return x if atten == 1.0 else (x * atten).astype(x.dtype)


def mla_init(key, cfg: MLAConfig) -> Params:
    ks = jax.random.split(key, 6)
    h, dn, dr, dv = cfg.n_heads, cfg.d_nope, cfg.d_rope, cfg.d_v
    if cfg.q_lora is None:
        q = {"wq": dense_init(ks[0], cfg.d_model, h * (dn + dr))}
    else:
        q = {
            "wq_a": dense_init(ks[0], cfg.d_model, cfg.q_lora),
            "q_norm": rmsnorm_init(cfg.q_lora),
            "wq_b": dense_init(ks[1], cfg.q_lora, h * (dn + dr)),
        }
    return q | {
        "wkv_a": dense_init(ks[2], cfg.d_model, cfg.kv_lora + dr),
        "kv_norm": rmsnorm_init(cfg.kv_lora),
        "wkv_b": dense_init(ks[3], cfg.kv_lora, h * (dn + dv)),
        "wo": dense_init(ks[4], h * dv, cfg.d_model),
    }


def _mla_qkr(x, p, cfg: MLAConfig, positions, ftc=None):
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.d_nope, cfg.d_rope
    mm = site_matmul(ftc, "attn.qkv")
    if cfg.q_lora is None:
        q = mm(x, p["wq"])
    else:
        q = mm(rmsnorm(mm(x, p["wq_a"]), p["q_norm"]), p["wq_b"])
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = cfg.rope(q_rope, positions)
    kv_a = mm(x, p["wkv_a"])
    c_kv = rmsnorm(kv_a[..., : cfg.kv_lora], p["kv_norm"])  # (B,S,kv_lora)
    k_rope = cfg.rope(kv_a[..., cfg.kv_lora :][:, :, None, :], positions)[
        :, :, 0
    ]  # (B,S,dr) shared across heads
    return q_nope, q_rope, c_kv, k_rope


def mla_forward(x, p, cfg: MLAConfig, positions=None, unroll: bool = False, ftc=None) -> jax.Array:
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    h, dn, dr, dv = cfg.n_heads, cfg.d_nope, cfg.d_rope, cfg.d_v
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(x, p, cfg, positions, ftc)
    kv = site_matmul(ftc, "attn.qkv")(c_kv, p["wkv_b"]).reshape(b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = cfg.softmax_scale
    qb = min(cfg.q_block, s)
    assert s % qb == 0
    nblk = s // qb
    kpos = jnp.arange(s)

    def body(carry, inp):
        i, qn, qr = inp
        qpos = i * qb + jnp.arange(qb)
        sc = (
            jnp.einsum("bqhd,bshd->bqhs", qn.astype(jnp.float32), k_nope.astype(jnp.float32))
            + jnp.einsum("bqhd,bsd->bqhs", qr.astype(jnp.float32), k_rope.astype(jnp.float32))
        ) * scale
        mask = kpos[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, :, None, :], sc, -1e30)
        wts = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("bqhs,bshd->bqhd", wts, v.astype(jnp.float32))
        return carry, out.astype(x.dtype)

    _, outs = scan_or_unroll(
        body,
        None,
        (
            jnp.arange(nblk),
            q_nope.reshape(b, nblk, qb, h, dn).swapaxes(0, 1),
            q_rope.reshape(b, nblk, qb, h, dr).swapaxes(0, 1),
        ),
        unroll,
    )
    out = outs.swapaxes(0, 1).reshape(b, s, h * dv)
    return site_matmul(ftc, "attn.out")(out, p["wo"])


def mla_cache_init(cfg: MLAConfig, batch: int, smax: int, dtype=jnp.bfloat16) -> Params:
    return {
        "c_kv": jnp.zeros((batch, smax, cfg.kv_lora), dtype),
        "k_rope": jnp.zeros((batch, smax, cfg.d_rope), dtype),
        "idx": jnp.zeros((batch,), jnp.int32),
    }


def _latent_attention(q_nope, q_rope, c, r, qpos, w_uk, w_uv, scale):
    """Absorbed attention of q_nope (N,Q,H,dn), q_rope (N,Q,H,dr) at
    positions qpos (N,Q) over a latent cache c (N,S,L) and its rope keys r
    (N,S,dr); a query sees the positions up to its own.  Returns (N,Q,H,dv)
    fp32."""
    q_abs = jnp.einsum("bqhd,lhd->bqhl", q_nope.astype(jnp.float32), w_uk)
    sc = (
        jnp.einsum("bqhl,bsl->bqhs", q_abs, c.astype(jnp.float32))
        + jnp.einsum("bqhd,bsd->bqhs", q_rope.astype(jnp.float32), r.astype(jnp.float32))
    ) * scale
    valid = jnp.arange(c.shape[1]) <= qpos[..., None]  # (N,Q,S)
    sc = jnp.where(valid[:, :, None, :], sc, -1e30)
    wts = jax.nn.softmax(sc, axis=-1)
    ctx = jnp.einsum("bqhs,bsl->bqhl", wts, c.astype(jnp.float32))
    return jnp.einsum("bqhl,lhd->bqhd", ctx, w_uv)


def mla_decode(x, p, cfg: MLAConfig, cache: Params, ftc=None, *, advance=None,
               chunk=None) -> tuple[jax.Array, Params]:
    """Absorbed-matmul decode: attention runs in the compressed latent space so
    the cache stays (kv_lora + d_rope) per token — MLA's whole point.  Rows,
    ``advance`` and ``chunk`` as in :func:`gqa_decode`: a prompt chunk's
    rows share the projections with the slots' rows and attend causally to
    their slot's latent cache.

    The absorbed latent einsums (w_uk / w_uv contractions) run off the
    protected array: they are reshaped views of ``wkv_b``, which *is*
    protected on the prefill path; coverage here is the q-side projections
    plus the output projection (see docs/ftcontext.md).  They, the scores
    over the latent cache and the softmax run under the device scope
    ``attn.latent``.
    """
    b = cache["idx"].shape[0]
    idx = cache["idx"]
    h, dn, dv = cfg.n_heads, cfg.d_nope, cfg.d_v
    slot, pos, write, new_idx = _step_rows(idx, x.shape[0], advance, chunk)
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkr(x, p, cfg, pos[:, None], ftc)
    c_cache = _write_rows(cache["c_kv"], slot, pos, write, c_kv_new[:, 0])
    r_cache = _write_rows(cache["k_rope"], slot, pos, write, k_rope_new[:, 0])
    with jax.named_scope("attn.latent"):
        w_uk = p["wkv_b"].reshape(cfg.kv_lora, h, dn + dv)[..., :dn]  # (L,H,dn)
        w_uv = p["wkv_b"].reshape(cfg.kv_lora, h, dn + dv)[..., dn:]  # (L,H,dv)
        out = _latent_attention(q_nope[:b], q_rope[:b], c_cache, r_cache, idx[:, None],
                                w_uk, w_uv, cfg.softmax_scale)
        if chunk is not None:
            s = chunk["slot"]
            out_c = _latent_attention(_chunk_view(q_nope, b), _chunk_view(q_rope, b),
                                      c_cache[s][None], r_cache[s][None], pos[None, b:],
                                      w_uk, w_uv, cfg.softmax_scale)
            out = jnp.concatenate([out, out_c.swapaxes(0, 1)])
        out = out.reshape(-1, 1, h * dv).astype(x.dtype)
    out = site_matmul(ftc, "attn.out")(out, p["wo"])
    return out, {"c_kv": c_cache, "k_rope": r_cache, "idx": new_idx}
