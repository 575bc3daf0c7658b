"""Unified LM composer — one config schema + init/forward/loss/decode for all
ten assigned architectures (dense / MoE / SSM / hybrid / enc-dec / VLM).

Design rules:
  * pure pytrees + pure functions; params stored fp32 (optimizer master),
    cast to ``cfg.dtype`` (bf16) at stage entry for MXU-rate compute;
  * homogeneous layer stacks scan over stacked params (small HLO, fast
    dry-run compiles for 62-layer models);
  * activations carry logical-axis sharding constraints (repro.dist.shard)
    so GSPMD lowers the Megatron TP layout + DP batch split on any mesh;
  * every family exposes the same three entry points used by launch/:
      forward(params, cfg, batch)            -> logits           (train/prefill)
      init_cache(cfg, batch, smax)           -> cache pytree     (serve)
      decode_step(params, cfg, cache, batch) -> (logits, cache)  (serve)
  * an optional :class:`~repro.core.ftcontext.FTContext` threads the
    HyCA-protected matmul through **every** weight matmul — attention
    projections, FFNs, MoE routers + experts, SSM/RWKV projections, the
    multimodal projector, and the LM head — with per-site policy and a
    static protected-layer prefix (unprotected layers lower plain matmuls,
    zero fault-machinery overhead).  See docs/ftcontext.md.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.ftcontext import FTContext, site_matmul
from repro.dist.sharding import shard
from repro.models import encdec as ed
from repro.models.attention import (
    AttnConfig,
    MLAConfig,
    gqa_cache_init,
    gqa_decode,
    gqa_forward,
    gqa_init,
    mla_cache_init,
    mla_decode,
    mla_forward,
    mla_init,
)
from repro.models.frontends import audio_frontend, mm_project, mm_projector_init, splice_patches
from repro.models.layers import (
    Params,
    cross_entropy,
    streamed_cross_entropy,
    embed_init,
    ffn,
    ffn_init,
    layernorm,
    layernorm_init,
    rmsnorm,
    rmsnorm_init,
    stack_layer_params,
)
from repro.models.mamba2 import Mamba2Config, mamba2_cache_init, mamba2_decode, mamba2_forward, mamba2_init
from repro.models.moe import MoEConfig, moe_decode, moe_forward, moe_init
from repro.models.rwkv6 import RWKV6Config, rwkv6_cache_init, rwkv6_decode, rwkv6_forward, rwkv6_init

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    family: str              # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    attn_kind: str = "gqa"   # gqa | mla
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm: str = "rms"        # rms | ln
    gated_ffn: bool = True
    act: str = "silu"
    tie_embeddings: bool = True
    q_block: int = 512
    # MoE
    moe: MoEConfig | None = None
    first_k_dense: int = 0
    dense_d_ff: int = 0
    # MLA
    mla: MLAConfig | None = None
    # SSM / hybrid
    ssm: Mamba2Config | None = None
    rwkv: RWKV6Config | None = None
    attn_every: int = 0      # hybrid: shared attn block every k SSM layers
    # enc-dec
    n_enc_layers: int = 0
    enc_len: int = 1500
    # vlm
    n_patches: int = 0
    d_vision: int = 1024
    subquadratic: bool = False
    remat: bool = True
    # remat policy: "full" recomputes everything (min memory, max recompute
    # FLOPs); "dots" saves matmul outputs and recomputes only elementwise ops
    # (§Perf lever: trades activation memory for the dominant compute term)
    remat_policy: str = "full"
    # §Perf: compute the training loss in vocab chunks — the (B,S,V) logit
    # tensor is never materialised (0 = dense head)
    loss_chunks: int = 0
    # unroll layer loops into straight-line HLO.  Production keeps scans (small
    # HLO, fast compiles); the roofline probes unroll so cost_analysis counts
    # every layer (XLA tallies a while body ONCE regardless of trip count).
    unroll: bool = False
    dtype: Any = jnp.bfloat16

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so embedding/logit tables always
        shard over a 16-way model axis (MaxText-style; padded logit rows are
        masked to -inf in the head).  GSPMD's gather partitioner rejects
        replicated-table + sharded-consumer programs for non-divisible
        vocabs — padding is both the fix and a memory/throughput win."""
        return -(-self.vocab // 256) * 256

    @property
    def attn_cfg(self) -> AttnConfig:
        return AttnConfig(
            self.d_model, self.n_heads, self.n_kv, head_dim=self.head_dim,
            qkv_bias=self.qkv_bias, rope_theta=self.rope_theta, q_block=self.q_block,
        )

    def n_params(self) -> int:
        """Total parameter count (host-side, from shapes)."""
        import math
        shapes = jax.eval_shape(lambda k: init_params(k, self), jax.random.key(0))
        return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))

    def n_active_params(self) -> int:
        """Active params per token (MoE: only routed top-k + shared experts)."""
        total = self.n_params()
        if self.moe is None:
            return total
        m = self.moe
        per_expert = 3 * self.d_model * m.d_expert
        # a share holds n_held experts, of which top_k * n_held / n_padded are routed to
        routed = m.top_k * m.n_held / m.n_padded
        inactive = (m.n_held - routed) * per_expert * (self.n_layers - self.first_k_dense)
        return int(total - inactive)


# --------------------------------------------------------------------------- #
# norm / cast helpers
# --------------------------------------------------------------------------- #
def _norm_init(cfg: LMConfig, d: int):
    return rmsnorm_init(d) if cfg.norm == "rms" else layernorm_init(d)


def _norm(x, p, cfg: LMConfig):
    return rmsnorm(x, p) if cfg.norm == "rms" else layernorm(x, p)


def _cast(tree, dtype):
    # named so that the device trace can tell the per-step weight cast apart
    with jax.named_scope("weights.cast"):
        return jax.tree.map(
            lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a, tree
        )


def _remat(f, cfg: LMConfig):
    if not cfg.remat:
        return f
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(f)


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #
def _dense_block_init(key, cfg: LMConfig, d_ff: int | None = None) -> Params:
    k1, k2 = jax.random.split(key)
    attn = mla_init(k1, cfg.mla) if cfg.attn_kind == "mla" else gqa_init(k1, cfg.attn_cfg)
    return {
        "ln1": _norm_init(cfg, cfg.d_model),
        "attn": attn,
        "ln2": _norm_init(cfg, cfg.d_model),
        "ffn": ffn_init(k2, cfg.d_model, d_ff or cfg.d_ff, gated=cfg.gated_ffn),
    }


def _moe_block_init(key, cfg: LMConfig) -> Params:
    k1, k2 = jax.random.split(key)
    attn = mla_init(k1, cfg.mla) if cfg.attn_kind == "mla" else gqa_init(k1, cfg.attn_cfg)
    return {
        "ln1": _norm_init(cfg, cfg.d_model),
        "attn": attn,
        "ln2": _norm_init(cfg, cfg.d_model),
        "moe": moe_init(k2, cfg.moe),
    }


def init_params(key, cfg: LMConfig) -> Params:
    ks = jax.random.split(key, 8)
    p: Params = {"embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model)}
    if not cfg.tie_embeddings:
        p["lm_head"] = embed_init(ks[7], cfg.padded_vocab, cfg.d_model)
    p["final_norm"] = _norm_init(cfg, cfg.d_model)

    if cfg.family in ("dense", "vlm"):
        p["blocks"] = stack_layer_params(lambda k: _dense_block_init(k, cfg), ks[1], cfg.n_layers)
        if cfg.family == "vlm":
            p["mm_proj"] = mm_projector_init(ks[2], cfg.d_vision, cfg.d_model)
    elif cfg.family == "moe":
        n_moe = cfg.n_layers - cfg.first_k_dense
        p["blocks"] = stack_layer_params(lambda k: _moe_block_init(k, cfg), ks[1], n_moe)
        if cfg.first_k_dense:
            p["dense_blocks"] = stack_layer_params(
                lambda k: _dense_block_init(k, cfg, d_ff=cfg.dense_d_ff or cfg.d_ff),
                ks[2], cfg.first_k_dense,
            )
    elif cfg.family == "ssm":
        p["blocks"] = stack_layer_params(lambda k: rwkv6_init(k, cfg.rwkv), ks[1], cfg.n_layers)
    elif cfg.family == "hybrid":
        def mamba_block(k):
            return {"ln": _norm_init(cfg, cfg.d_model), "mamba": mamba2_init(k, cfg.ssm)}
        p["blocks"] = stack_layer_params(mamba_block, ks[1], cfg.n_layers)
        p["shared"] = _dense_block_init(ks[2], cfg)  # one shared attn+ffn block
    elif cfg.family == "encdec":
        p["encoder"] = ed.encoder_init(ks[1], cfg.n_enc_layers, cfg.d_model, cfg.n_heads, cfg.d_ff)
        p["blocks"] = stack_layer_params(
            lambda k: ed.decoder_layer_init(k, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_ff),
            ks[2], cfg.n_layers,
        )
    else:
        raise ValueError(f"unknown family {cfg.family!r}")
    return p


# --------------------------------------------------------------------------- #
# forward (train / prefill)
# --------------------------------------------------------------------------- #
def _attn_fwd(x, p, cfg: LMConfig, positions, ftc: FTContext | None = None):
    if cfg.attn_kind == "mla":
        return mla_forward(x, p, cfg.mla, positions, unroll=cfg.unroll, ftc=ftc)
    return gqa_forward(x, p, cfg.attn_cfg, positions, unroll=cfg.unroll, ftc=ftc)


def _embed(params, cfg: LMConfig, batch, ftc: FTContext | None = None) -> jax.Array:
    tokens = batch["tokens"]
    emb = params["embed"].astype(cfg.dtype)
    x = emb[tokens]
    if cfg.family == "vlm" and "patches" in batch:
        proj = mm_project(
            batch["patches"].astype(cfg.dtype), _cast(params["mm_proj"], cfg.dtype), ftc
        )
        x = splice_patches(x, proj)
    return shard(x, "batch", "seq", "embed")


def _logits(x, params, cfg: LMConfig, ftc: FTContext | None = None):
    x = _norm(x, params["final_norm"], cfg)
    table = params.get("lm_head", params["embed"]).astype(cfg.dtype)
    logits = site_matmul(ftc, "head")(x, table.T)
    if cfg.padded_vocab != cfg.vocab:  # mask padded rows out of the softmax
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad, jnp.asarray(-1e30, logits.dtype), logits)
    return shard(logits, "batch", "seq", "vocab")


def _scan_blocks(x, blocks, body, cfg: LMConfig, carry_aux=False):
    """Scan a stacked block stage; body(x, layer_params) -> x or (x, aux)."""
    blocks = _cast(blocks, cfg.dtype)

    def f(carry, lp):
        if carry_aux:
            x, aux = carry
            x, a = body(x, lp)
            return (shard(x, "batch", "seq", "embed"), aux + a), None
        x = body(carry, lp)
        return shard(x, "batch", "seq", "embed"), None

    f = _remat(f, cfg)
    init = (x, jnp.zeros((), jnp.float32)) if carry_aux else x
    if cfg.unroll:
        carry = init
        for i in range(jax.tree.leaves(blocks)[0].shape[0]):
            carry, _ = f(carry, jax.tree.map(lambda a: a[i], blocks))
        return carry
    out, _ = jax.lax.scan(f, init, blocks)
    return out


def _layer_splits(n: int, ftc: FTContext | None) -> list[tuple[int, int, FTContext | None]]:
    """Static protected-prefix split of an ``n``-layer stack.

    The ProtectPolicy's layer fraction becomes a compile-time split: layers
    [0, k) scan with the fault-aware context, layers [k, n) scan with plain
    matmuls.  Unprotected layers therefore pay zero overhead — unlike the old
    traced ``protect_mask`` gate, which evaluated both the protected and the
    plain matmul and selected between them.
    """
    if ftc is None or not ftc.active or n == 0:
        return [(0, n, ftc if (ftc is not None and ftc.active) else None)]
    k = ftc.n_protected_layers(n)
    if k == 0:
        return [(0, n, None)]
    if k >= n:
        return [(0, n, ftc)]
    return [(0, k, ftc), (k, n, None)]


def _slice_layers(tree, lo: int, hi: int):
    return jax.tree.map(lambda a: a[lo:hi], tree)


def forward(
    params: Params,
    cfg: LMConfig,
    batch: dict,
    *,
    ftc: FTContext | None = None,
    last_only: bool = False,
    return_hidden: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (logits, aux_loss).  batch: tokens (B,S) [+ frames / patches].

    ``ftc``: fault-aware execution context; every weight matmul in the
    protected layer prefix (and the frontends / LM head) routes through it.
    ``last_only``: production prefill — project logits for the final position
    only (the (B,S,V) tensor is never built)."""
    x = _embed(params, cfg, batch, ftc)
    b, s = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    aux = jnp.zeros((), jnp.float32)
    act = _ACTS[cfg.act]

    if cfg.family in ("dense", "vlm"):
        def make_body(fc):
            def body(x, lp):
                x = x + _attn_fwd(_norm(x, lp["ln1"], cfg), lp["attn"], cfg, positions, fc)
                return x + ffn(_norm(x, lp["ln2"], cfg), lp["ffn"], act=act, ftc=fc)
            return body
        for lo, hi, fc in _layer_splits(cfg.n_layers, ftc):
            x = _scan_blocks(x, _slice_layers(params["blocks"], lo, hi), make_body(fc), cfg)

    elif cfg.family == "moe":
        if cfg.first_k_dense:
            # the first-k dense blocks sit below the gated main stack and are
            # always protected when a context is threaded
            def dense_body(x, lp):
                x = x + _attn_fwd(_norm(x, lp["ln1"], cfg), lp["attn"], cfg, positions, ftc)
                return x + ffn(_norm(x, lp["ln2"], cfg), lp["ffn"], act=act, ftc=ftc)
            x = _scan_blocks(x, params["dense_blocks"], dense_body, cfg)
        n_moe = cfg.n_layers - cfg.first_k_dense
        for lo, hi, fc in _layer_splits(n_moe, ftc):
            blocks = _cast(_slice_layers(params["blocks"], lo, hi), cfg.dtype)
            def f(carry, lp, fc=fc):
                x, a = carry
                x2 = x + _attn_fwd(_norm(x, lp["ln1"], cfg), lp["attn"], cfg, positions, fc)
                y, ai = moe_forward(
                    _norm(x2, lp["ln2"], cfg), lp["moe"], cfg.moe, unroll=cfg.unroll, ftc=fc
                )
                return (shard(x2 + y, "batch", "seq", "embed"), a + ai), None
            f = _remat(f, cfg)
            if cfg.unroll:
                carry = (x, aux)
                for i in range(jax.tree.leaves(blocks)[0].shape[0]):
                    carry, _ = f(carry, jax.tree.map(lambda a: a[i], blocks))
                x, aux = carry
            else:
                (x, aux), _ = jax.lax.scan(f, (x, aux), blocks)
        aux = aux / max(n_moe, 1)

    elif cfg.family == "ssm":
        def make_body(fc):
            def body(x, lp):
                return rwkv6_forward(x, lp, cfg.rwkv, unroll=cfg.unroll, ftc=fc)
            return body
        for lo, hi, fc in _layer_splits(cfg.n_layers, ftc):
            x = _scan_blocks(x, _slice_layers(params["blocks"], lo, hi), make_body(fc), cfg)

    elif cfg.family == "hybrid":
        x = _hybrid_forward(x, params, cfg, positions, act, ftc)

    elif cfg.family == "encdec":
        enc = ed.encoder_forward(
            audio_frontend(batch["frames"].astype(cfg.dtype)),
            _cast(params["encoder"], cfg.dtype), cfg.d_model, cfg.n_heads,
            unroll=cfg.unroll, ftc=ftc,
        )
        enc = shard(enc, "batch", "seq", "embed")
        xcfg = ed.CrossAttnConfig(cfg.d_model, cfg.n_heads)
        def make_body(fc):
            def body(x, lp):
                x = x + gqa_forward(layernorm(x, lp["ln1"]), lp["attn"], cfg.attn_cfg, positions, unroll=cfg.unroll, ftc=fc)
                x = x + ed.cross_attn(layernorm(x, lp["ln_x"]), enc, lp["xattn"], xcfg, fc)
                return x + ffn(layernorm(x, lp["ln2"]), lp["ffn"], act=jax.nn.gelu, ftc=fc)
            return body
        for lo, hi, fc in _layer_splits(cfg.n_layers, ftc):
            x = _scan_blocks(x, _slice_layers(params["blocks"], lo, hi), make_body(fc), cfg)
    else:
        raise ValueError(cfg.family)

    if last_only:
        x = x[:, -1:]
    if return_hidden:
        return _norm(x, params["final_norm"], cfg), aux
    return _logits(x, params, cfg, ftc), aux


def _hybrid_groups(cfg: LMConfig) -> list[tuple[int, int]]:
    """[(start, length)] mamba-layer groups; shared attn runs after each."""
    ae = cfg.attn_every or cfg.n_layers
    groups = []
    i = 0
    while i < cfg.n_layers:
        groups.append((i, min(ae, cfg.n_layers - i)))
        i += ae
    return groups


def _hybrid_forward(x, params, cfg: LMConfig, positions, act, ftc: FTContext | None = None):
    """Hybrid stacks are all-or-nothing: the shared attention block runs after
    every mamba group, so a layer-fraction split has no clean prefix — the
    whole stack follows the context (see docs/ftcontext.md)."""
    shared = _cast(params["shared"], cfg.dtype)

    def mamba_body(x, lp):
        return x + mamba2_forward(_norm(x, lp["ln"], cfg), lp["mamba"], cfg.ssm, unroll=cfg.unroll, ftc=ftc)

    for start, length in _hybrid_groups(cfg):
        blocks = jax.tree.map(lambda a: a[start : start + length], params["blocks"])
        x = _scan_blocks(x, blocks, mamba_body, cfg)
        x = x + _attn_fwd(_norm(x, shared["ln1"], cfg), shared["attn"], cfg, positions, ftc)
        x = x + ffn(_norm(x, shared["ln2"], cfg), shared["ffn"], act=act, ftc=ftc)
        x = shard(x, "batch", "seq", "embed")
    return x


# --------------------------------------------------------------------------- #
# loss
# --------------------------------------------------------------------------- #
def loss_fn(params, cfg: LMConfig, batch, *, aux_weight: float = 0.01, ftc: FTContext | None = None):
    if cfg.loss_chunks:
        x, aux = forward(params, cfg, batch, ftc=ftc, return_hidden=True)
        table = params.get("lm_head", params["embed"]).astype(cfg.dtype)
        nll = streamed_cross_entropy(
            x, table, batch["labels"], cfg.loss_chunks, cfg.vocab, unroll=cfg.unroll,
            ftc=ftc,
        )
    else:
        logits, aux = forward(params, cfg, batch, ftc=ftc)
        nll = cross_entropy(logits, batch["labels"])
    loss = nll + aux_weight * aux
    return loss, {"loss": nll, "aux": aux}


# --------------------------------------------------------------------------- #
# serve: cache init + single-token decode
# --------------------------------------------------------------------------- #
def _stackN(tree, n):
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)).copy(), tree)


def init_cache(cfg: LMConfig, batch: int, smax: int, dtype=jnp.bfloat16) -> Params:
    if cfg.family in ("dense", "vlm", "moe"):
        if cfg.attn_kind == "mla":
            one = mla_cache_init(cfg.mla, batch, smax, dtype)
        else:
            one = gqa_cache_init(cfg.attn_cfg, batch, smax, dtype)
        cache: Params = {"attn": _stackN(one, cfg.n_layers - cfg.first_k_dense)}
        if cfg.first_k_dense:
            cache["attn_dense"] = _stackN(one, cfg.first_k_dense)
        if cfg.family == "moe":   # the last step's routing, written by decode_step
            cache["moe_load"] = jnp.zeros(
                (cfg.n_layers - cfg.first_k_dense, batch, cfg.moe.n_held), jnp.int32)
        return cache
    if cfg.family == "ssm":
        return {"rwkv": _stackN(rwkv6_cache_init(cfg.rwkv, batch), cfg.n_layers)}
    if cfg.family == "hybrid":
        n_groups = len(_hybrid_groups(cfg))
        return {
            "mamba": _stackN(mamba2_cache_init(cfg.ssm, batch), cfg.n_layers),
            "shared_attn": _stackN(gqa_cache_init(cfg.attn_cfg, batch, smax, dtype), n_groups),
        }
    if cfg.family == "encdec":
        return {
            "attn": _stackN(gqa_cache_init(cfg.attn_cfg, batch, smax, dtype), cfg.n_layers),
            "enc": jnp.zeros((batch, cfg.enc_len, cfg.d_model), dtype),
        }
    raise ValueError(cfg.family)


# families whose decode step takes a prompt chunk beside the slots' tokens
# (attention caches only: a recurrent state has no positions to write into)
CHUNK_FAMILIES = ("dense", "vlm", "moe")


def _attn_decode(x, p, cfg: LMConfig, cache, ftc: FTContext | None = None, **rows):
    if cfg.attn_kind == "mla":
        return mla_decode(x, p, cfg.mla, cache, ftc, **rows)
    return gqa_decode(x, p, cfg.attn_cfg, cache, ftc, **rows)


def _fold_chunk_load(load, b: int, chunk):
    """(B + C, E) routing of a step's rows -> (B, E): the chunk's real rows
    summed into its slot's row, whose own token fed nothing."""
    real = (jnp.arange(load.shape[0] - b) < chunk["len"])[:, None]
    mine = jnp.where(real, load[b:], 0).sum(0)
    s = chunk["slot"]
    return load[:b].at[s].set(jnp.where(chunk["len"] > 0, mine, load[s]))


def _decode_scan(f, x, xs, cfg: LMConfig):
    """scan(f, x, xs) with the roofline-probe unroll option (see LMConfig)."""
    if not cfg.unroll:
        return jax.lax.scan(f, x, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x, y = f(x, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    return x, jax.tree.map(lambda *zs: jnp.stack(zs), *ys)


def _concat_cache_parts(parts: list) -> Params:
    """Re-join per-split cache slices along the leading layer axis."""
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *parts)


def decode_step(
    params: Params,
    cfg: LMConfig,
    cache: Params,
    batch: dict,
    *,
    ftc: FTContext | None = None,
) -> tuple[jax.Array, Params]:
    """batch: {"token": (B, 1) int32}.  Returns (logits (B,1,V), new cache).

    A family of :data:`CHUNK_FAMILIES` also takes a prompt chunk in the
    same step: ``batch["chunk"]`` = {"tokens": (C,) int32, "slot": (),
    "len": ()} — the next ``len`` prompt tokens of slot ``slot``, padded to
    C — and ``batch["advance"]`` (B,) bool, the slots whose token this step
    feeds.  The chunk's rows are appended to the slots' rows for every
    weight matmul (one call per site for both), attend causally to their
    slot's cache, and write their K/V (or latent) rows there; padding and
    the slots that do not advance write nothing.  The slot's row of the
    logits then holds the logits of the chunk's last real token (unchanged
    when ``len`` is 0).

    An MoE model's new cache also holds the step's routing under
    ``"moe_load"``: int32 (MoE layers, B, E_held), 1 where the row's token
    was dispatched to the held expert in that layer, a chunk slot's row
    counting its chunk's tokens (the server reads it with the sampled
    tokens while a trace records, docs/observability.md).

    ``ftc`` mirrors :func:`forward`'s execution context: every weight matmul
    of the protected layer prefix — attention projections, FFN, MoE router +
    experts, SSM/RWKV projections — plus the LM head routes through the
    fault-aware dispatcher.  The ProtectPolicy's layer fraction splits the
    main-stack scan statically, so unprotected layers lower plain matmuls.
    """
    tok = batch["token"]
    b = tok.shape[0]
    chunk = batch.get("chunk")
    rows = {}
    if chunk is not None:
        if cfg.family not in CHUNK_FAMILIES:
            raise ValueError(f"the {cfg.family} family takes no prompt chunk")
        tok = jnp.concatenate([tok, chunk["tokens"][:, None]])
        rows = {"advance": batch["advance"], "chunk": chunk}
    x = params["embed"].astype(cfg.dtype)[tok]
    x = shard(x, "batch", None, "embed")
    act = _ACTS[cfg.act]

    if cfg.family in CHUNK_FAMILIES:
        is_moe = cfg.family == "moe"
        new_cache = dict(cache)
        if cfg.first_k_dense:
            blocks = _cast(params["dense_blocks"], cfg.dtype)
            def fd(x, inp):
                lp, c = inp
                h, c2 = _attn_decode(_norm(x, lp["ln1"], cfg), lp["attn"], cfg, c, ftc, **rows)
                x = x + h
                x = x + ffn(_norm(x, lp["ln2"], cfg), lp["ffn"], act=act, ftc=ftc)
                return x, c2
            x, cd = _decode_scan(fd, x, (blocks, cache["attn_dense"]), cfg)
            new_cache["attn_dense"] = cd
        n_main = cfg.n_layers - cfg.first_k_dense
        cache_parts, loads = [], []
        for lo, hi, fc in _layer_splits(n_main, ftc):
            blocks = _cast(_slice_layers(params["blocks"], lo, hi), cfg.dtype)
            def f(x, inp, fc=fc):
                lp, c = inp
                h, c2 = _attn_decode(_norm(x, lp["ln1"], cfg), lp["attn"], cfg, c, fc, **rows)
                x = x + h
                if is_moe:
                    y, load = moe_decode(_norm(x, lp["ln2"], cfg), lp["moe"], cfg.moe, ftc=fc)
                    if chunk is not None:
                        load = _fold_chunk_load(load, b, chunk)
                    c2 = (c2, load)
                else:
                    y = ffn(_norm(x, lp["ln2"], cfg), lp["ffn"], act=act, ftc=fc)
                return shard(x + y, "batch", None, "embed"), c2
            x, ca = _decode_scan(f, x, (blocks, _slice_layers(cache["attn"], lo, hi)), cfg)
            if is_moe:
                ca, load = ca
                loads.append(load)
            cache_parts.append(ca)
        new_cache["attn"] = _concat_cache_parts(cache_parts)
        if is_moe:
            new_cache["moe_load"] = jnp.concatenate(loads)
        if chunk is not None:   # the chunk's last real row stands in its slot's row
            s, last = chunk["slot"], b + jnp.maximum(chunk["len"] - 1, 0)
            x = x[:b].at[s].set(jnp.where(chunk["len"] > 0, x[last], x[s]))

    elif cfg.family == "ssm":
        cache_parts = []
        for lo, hi, fc in _layer_splits(cfg.n_layers, ftc):
            blocks = _cast(_slice_layers(params["blocks"], lo, hi), cfg.dtype)
            def f(x, inp, fc=fc):
                lp, c = inp
                return rwkv6_decode(x, lp, cfg.rwkv, c, fc)
            x, cr = _decode_scan(f, x, (blocks, _slice_layers(cache["rwkv"], lo, hi)), cfg)
            cache_parts.append(cr)
        new_cache = {"rwkv": _concat_cache_parts(cache_parts)}

    elif cfg.family == "hybrid":
        shared = _cast(params["shared"], cfg.dtype)
        mamba_caches = []
        attn_caches = []
        def fm(x, inp):
            lp, c = inp
            y, c2 = mamba2_decode(_norm(x, lp["ln"], cfg), lp["mamba"], cfg.ssm, c, ftc)
            return x + y, c2
        for gi, (start, length) in enumerate(_hybrid_groups(cfg)):
            blocks = _cast(jax.tree.map(lambda a: a[start : start + length], params["blocks"]), cfg.dtype)
            gcache = jax.tree.map(lambda a: a[start : start + length], cache["mamba"])
            x, c2 = _decode_scan(fm, x, (blocks, gcache), cfg)
            mamba_caches.append(c2)
            acache = jax.tree.map(lambda a: a[gi], cache["shared_attn"])
            h, ac2 = _attn_decode(_norm(x, shared["ln1"], cfg), shared["attn"], cfg, acache, ftc)
            x = x + h
            x = x + ffn(_norm(x, shared["ln2"], cfg), shared["ffn"], act=act, ftc=ftc)
            attn_caches.append(ac2)
        new_cache = {
            "mamba": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *mamba_caches),
            "shared_attn": jax.tree.map(lambda *xs: jnp.stack(xs, 0), *attn_caches),
        }

    elif cfg.family == "encdec":
        enc = cache["enc"]
        xcfg = ed.CrossAttnConfig(cfg.d_model, cfg.n_heads)
        cache_parts = []
        for lo, hi, fc in _layer_splits(cfg.n_layers, ftc):
            blocks = _cast(_slice_layers(params["blocks"], lo, hi), cfg.dtype)
            def f(x, inp, fc=fc):
                lp, c = inp
                h, c2 = gqa_decode(layernorm(x, lp["ln1"]), lp["attn"], cfg.attn_cfg, c, fc)
                x = x + h
                x = x + ed.cross_attn(layernorm(x, lp["ln_x"]), enc, lp["xattn"], xcfg, fc)
                x = x + ffn(layernorm(x, lp["ln2"]), lp["ffn"], act=jax.nn.gelu, ftc=fc)
                return x, c2
            x, ca = _decode_scan(f, x, (blocks, _slice_layers(cache["attn"], lo, hi)), cfg)
            cache_parts.append(ca)
        new_cache = {"attn": _concat_cache_parts(cache_parts), "enc": enc}
    else:
        raise ValueError(cfg.family)

    return _logits(x, params, cfg, ftc), new_cache
