"""Mixture-of-Experts FFN with GShard-style capacity dispatch, token-grouped.

Dispatch/combine are einsums over a one-hot (…, tokens, experts, capacity)
tensor so expert parallelism falls out of sharding the expert axis over the
mesh "model" axis.  Two §Perf-critical layout decisions (both found by the
roofline probes, see EXPERIMENTS.md):

  * tokens are processed in GROUPS of ``group_size`` WITHIN each batch row —
    the batch axis stays data-sharded and every device works on its local
    tokens each group step.  (Grouping across the batch axis makes the scan
    iterate a sharded dimension: GSPMD reshards every step — 4.9 GiB of
    all-reduce per layer per microbatch.)  A naive ungrouped dispatch is
    O(N²) in tokens — terabytes at prefill_32k.
  * the k-slot axis is collapsed BEFORE the capacity one-hot, so the live
    tensor is (…, N, E, C), never the top-k× larger (k, …, N, E, C).

Experts whose count does not divide the model axis are PADDED (``pad_to``):
the router logits of padded experts are masked to -inf, so they are never
routed to; their weights exist only to make the expert axis shardable
(granite-moe's 40 experts -> 48 = 3 per device on a 16-way axis).

Covers the assigned MoE archs: deepseek-moe-16b (fine-grained: 64 routed
top-6 + 2 shared experts), deepseek-v2-lite (the same block with the top-6
gates left unnormalised) and granite-moe (40 routed top-8, no shared).

Expert parallelism: a layer can be told which experts it holds
(``MoEConfig.held``).  It routes over every expert, keeps the held ones'
dispatch and combine only, and holds only their weights, so the expert
matmuls grid over the held experts; a pair routed to an expert held
elsewhere adds nothing here.  The shares of all holders, with the shared
experts counted once, sum to the whole layer.  Routing and the dispatch and
combine einsums run under the device scope ``moe.route`` (on the TPU the
dispatch einsum fuses into the experts' operand, whose scope is
``moe.expert``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.ftcontext import site_matmul
from repro.dist.sharding import shard
from repro.models.layers import Params, dense_init, ffn, ffn_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    n_experts: int
    top_k: int
    d_expert: int  # per-expert FFN hidden size
    n_shared: int = 0
    d_shared: int = 0  # shared-expert FFN hidden (fine-grained MoE)
    capacity_factor: float = 1.25
    group_size: int = 2048  # tokens per dispatch group (GShard group dim)
    pad_to: int = 0         # pad expert count so it shards (0 = no padding)
    norm_topk: bool = True  # renormalise the chosen top-k gates to sum to 1
    held: tuple[int, int] | None = None  # experts [lo, hi) held here; None: all

    def __post_init__(self):
        lo, hi = self.held_range
        if not 0 <= lo < hi <= self.n_padded:
            raise ValueError(f"held experts {self.held} outside [0, {self.n_padded})")

    @property
    def n_padded(self) -> int:
        return max(self.pad_to, self.n_experts)

    @property
    def held_range(self) -> tuple[int, int]:
        return self.held or (0, self.n_padded)

    @property
    def n_held(self) -> int:
        lo, hi = self.held_range
        return hi - lo


def moe_init(key, cfg: MoEConfig) -> Params:
    """The router over every expert and the held experts' weights: expert
    ``e`` gets the same weights whichever share holds it."""
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_padded, cfg.d_model, cfg.d_expert
    lo, hi = cfg.held_range
    p = {
        "router": dense_init(ks[0], d, e, scale=0.006),
        "gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * 0.02)[lo:hi],
        "up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * 0.02)[lo:hi],
        "down": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * 0.02)[lo:hi],
    }
    if cfg.n_shared:
        p["shared"] = ffn_init(ks[4], d, cfg.d_shared or cfg.d_expert * cfg.n_shared)
    return p


def _topk_dispatch(gates: jax.Array, top_k: int, capacity: int, *, norm_topk: bool = True,
                   held: tuple[int, int] | None = None):
    """gates: (B, G, E) probabilities. Returns dispatch (B, G, E_held, C)
    one-hot and combine weights over the held experts [lo, hi) (all of
    them by default); capacity-dropped tokens get zero weight.  The top-k
    is taken over all E, and ``norm_topk`` renormalises the chosen gates."""
    b, g, e = gates.shape
    lo, hi = held or (0, e)
    topv, topi = jax.lax.top_k(gates, top_k)  # (B, G, k)
    if norm_topk:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(jnp.moveaxis(topi, -1, 0), e, dtype=jnp.float32)  # (k,B,G,E)
    onehot, e = onehot[..., lo:hi], hi - lo   # queues are per expert: keep the held ones
    # queue position per token within its expert, counted across (slot, token)
    flat = jnp.moveaxis(onehot, 0, 1).reshape(b, top_k * g, e)  # slot-major
    pos = jnp.moveaxis(
        jnp.cumsum(flat, axis=1).reshape(b, top_k, g, e), 1, 0
    ) - 1.0  # (k, B, G, E)
    keep = (pos < capacity) * onehot
    # a token occupies at most one slot per expert -> collapse k first
    pos_ne = (pos * onehot).sum(0)  # (B, G, E)
    keep_ne = keep.sum(0)           # (B, G, E)
    gate_ne = jnp.einsum("bgk,kbge->bge", topv, onehot)
    dispatch = keep_ne[..., None] * jax.nn.one_hot(
        pos_ne.astype(jnp.int32), capacity, dtype=jnp.float32
    )  # (B, G, E, C)
    combine = dispatch * gate_ne[..., None]
    return dispatch, combine


def _group_forward(
    xg: jax.Array, p: Params, cfg: MoEConfig, ftc=None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """xg: (B, G, d) one token group per batch row. Returns (out, aux, load),
    ``load`` (B, E_held) the tokens of each row dispatched to each held
    expert."""
    b, g, d = xg.shape
    lo, hi = cfg.held_range
    logits = site_matmul(ftc, "moe.router")(xg, p["router"]).astype(jnp.float32)  # (B, G, E_pad)
    with jax.named_scope("moe.route"):
        if cfg.n_padded != cfg.n_experts:  # mask padded experts out of routing
            dead = jnp.arange(cfg.n_padded) >= cfg.n_experts
            logits = jnp.where(dead, -1e30, logits)
        gates = jax.nn.softmax(logits, axis=-1)
        capacity = max(1, int(cfg.capacity_factor * cfg.top_k * g / cfg.n_experts))
        dispatch, combine = _topk_dispatch(gates, cfg.top_k, capacity, norm_topk=cfg.norm_topk,
                                           held=cfg.held)
        xe = jnp.einsum("bgec,bgd->becd", dispatch.astype(xg.dtype), xg)  # (B,E_held,C,d)
    xe = shard(xe, "batch", "expert", None, None)
    # per-expert matmuls: each expert is one virtual-array execution
    ein = (lambda s, a, w: ftc.einsum(s, a, w, site="moe.expert")) if ftc is not None else jnp.einsum
    h = jax.nn.silu(ein("becd,edf->becf", xe, p["gate"].astype(xg.dtype)))
    h = h * ein("becd,edf->becf", xe, p["up"].astype(xg.dtype))
    ye = ein("becf,efd->becd", h, p["down"].astype(xg.dtype))
    with jax.named_scope("moe.route"):
        out = jnp.einsum("bgec,becd->bgd", combine.astype(xg.dtype), ye)
    # load-balancing aux loss (Switch-style), over the held real experts
    real_hi = min(hi, cfg.n_experts)
    me = gates[..., lo:real_hi].mean((0, 1))
    ce = dispatch[..., : real_hi - lo, :].sum(-1).mean((0, 1))
    aux = cfg.n_experts * jnp.sum(me * ce)
    return out, aux, dispatch.sum((1, 3))


def moe_forward(
    x: jax.Array, p: Params, cfg: MoEConfig, *, unroll: bool = False, ftc=None
) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d). Returns (out, aux_loss).  Tokens stream through dispatch
    groups of ``cfg.group_size`` within each batch row, so the batch axis
    stays data-sharded through the group scan."""
    b, s, d = x.shape
    gsz = min(cfg.group_size, s)
    if s % gsz:  # awkward sequence lengths: one group per row
        gsz = s
    n_groups = s // gsz

    if n_groups == 1:
        out, aux, _ = _group_forward(x, p, cfg, ftc)
        return out + _shared(x, p, ftc), aux

    xg = jnp.moveaxis(x.reshape(b, n_groups, gsz, d), 1, 0)  # (n_g, B, G, d)

    def body(carry, xgi):
        out, aux, _ = _group_forward(xgi, p, cfg, ftc)
        return carry + aux, out

    if unroll:
        auxs = jnp.zeros((), jnp.float32)
        outs = []
        for i in range(n_groups):
            auxs, o = body(auxs, xg[i])
            outs.append(o)
        aux_sum, ys = auxs, jnp.stack(outs)
    else:
        aux_sum, ys = jax.lax.scan(body, jnp.zeros((), jnp.float32), xg)
    out = jnp.moveaxis(ys, 0, 1).reshape(b, s, d)
    return out + _shared(x, p, ftc), aux_sum / n_groups


def moe_decode(x: jax.Array, p: Params, cfg: MoEConfig, *, ftc=None) -> tuple[jax.Array, jax.Array]:
    """x: (B, 1, d), one token a row, as :func:`moe_forward` computes it.
    Returns (out, load): ``load`` (B, E_held) int32, 1 where the row's
    token was dispatched to the held expert."""
    out, _, load = _group_forward(x, p, cfg, ftc)
    return out + _shared(x, p, ftc), load.astype(jnp.int32)


def _shared(x: jax.Array, p: Params, ftc=None) -> jax.Array:
    return ffn(x, p["shared"], ftc=ftc) if "shared" in p else jnp.zeros_like(x)
