"""Fused fault-tolerant matmul — the beyond-paper kernel family.

The paper's pipeline is two-pass: (1) the faulty array writes its (partly
corrupted) outputs to the output buffer, (2) the DPPU recomputes faulty tiles
and overwrites them.  On TPU that costs an extra HBM round-trip for every
repaired tile plus the gather/scatter traffic.

Observation: in the Pallas formulation, the "DPPU recompute" of a repaired
tile produces *exactly* the clean accumulation the grid cell already holds in
VMEM — so repair can be fused into the drain: a repaired tile simply skips the
fault-injection mux.  One kernel, one HBM write per tile, zero scatter:

    healthy tile            -> clean accumulate, clean drain
    faulty & repaired tile  -> clean accumulate, clean drain  (DPPU semantics)
    faulty & unrepaired     -> stuck-at applied at drain      (degraded array)
    pruned (RepairPlan)     -> zero at drain                  (plan epilogue)

The kernel consumes *pre-resolved* per-PE metadata: the packed
``(rows, cols)`` int32 grid of :func:`~repro.core.engine.fault_meta_grid`,
whose ``eff`` bit is ``faulty & ~repaired`` (the only case that leaves the
fault in), already gathered through the RepairPlan's ``col_map`` — so a
plan's remap costs nothing at run time.  The grid rides in SMEM as a scalar-
prefetch operand (flattened, ``rows·cols`` words), and each grid cell reads
its PE's word at ``(i % rows, j % cols)``.  The stuck-at mux is applied at
the kernel family's (bm, bn) tile→PE granularity (the paper's per-element
mapping is the ``bm = bn = 1`` special case, shared with ``os_array_matmul``
and the ``ref`` oracles).

Plan *pruning* is different: the engine zeroes pruned PEs' outputs at
ELEMENT granularity (``out[i, j]`` → PE(i % rows, j % cols)), and the
FTContext dispatch layer promises engine-identical prune placement at any
block size.  The kernel therefore takes ``prune_mask`` — an int32 AND-mask
(``-1`` keep, ``0`` zero: bit pattern 0 IS +0.0) applied to the f32
accumulator's bits at drain.  Because the PE mapping is periodic, a single
``(bm, bn)`` mask tile suffices whenever ``bm % rows == 0 and
bn % cols == 0`` (it is fetched once and reused by every grid cell —
constant index map); otherwise the caller passes the full padded ``(m, n)``
mask and each cell reads its own block.  Either way the prune lands in the
drain — no post-kernel gather/overwrite pass over the output.

Operands enter in the dtype the caller serves (bf16 or f32, ``x`` and ``w``
alike): each tile is multiplied as it arrives into an f32 VMEM accumulator
(``preferred_element_type=jnp.float32``), and the drain and the output are
f32.  A bf16 product is exact in f32, so on the chip bf16 operands give
bit-for-bit the output of the same values up-cast — and the fault masks act
on the same f32 accumulator bits — without an f32 copy of any weight
(interpret mode runs XLA:CPU's dot, whose bf16 form may sum a row in
another order than its f32 form).  On the chip ``bm`` is a multiple of the
operand dtype's sublane tile (8 in f32, 16 in bf16).

Two grid layouts share the drain epilogue:

  * :func:`ft_matmul` — 2-D ``(M, K) @ (K, N)``; leading dims of N-D inputs
    are collapsed into M by the caller;
  * :func:`ft_matmul_batched` — per-expert ``(E, M, K) @ (E, K, N)`` with the
    expert axis as the outermost grid dimension, so MoE expert matmuls run as
    ONE kernel launch instead of falling back to the two-pass engine.

This preserves the paper's data semantics (property-tested against
``ref.ft_matmul_ref`` and, at ``bm = bn = 1``, bit-exactly against the
element-granular ``engine.hyca_matmul``) while removing 2·F·bm·bn·4 B of HBM
traffic per protected matmul.  Block sizes come from the autotuner
(``kernels.autotune``) when the context is built with ``fused_block="auto"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.engine import META_BIT_MASK, META_EFF_SHIFT, META_VAL_SHIFT


def _drain_tile(acc, word, pmask):
    """Shared drain epilogue for one tile whose PE has the packed meta
    ``word``: an effective fault forces its stuck bit, a clean or repaired PE
    passes the accumulator through (the scalar AND/OR pair of
    ``engine.apply_fault_epilogue``), then the element-granular prune
    AND-mask."""
    stuck = jnp.left_shift(jnp.int32(1), word & META_BIT_MASK)
    eff = ((word >> META_EFF_SHIFT) & 1) > 0
    one = ((word >> META_VAL_SHIFT) & 1) > 0
    and_m = jnp.where(eff & ~one, ~stuck, jnp.int32(-1))
    or_m = jnp.where(eff & one, stuck, jnp.int32(0))
    raw = jax.lax.bitcast_convert_type(acc, jnp.int32)
    return jax.lax.bitcast_convert_type(((raw & and_m) | or_m) & pmask, jnp.float32)


def _pe_word(meta_ref, i, j, rows: int, cols: int):
    """The packed meta word of the PE that owns output tile (i, j)."""
    return meta_ref[(i % rows) * cols + j % cols]


def _prune_spec(mask_shape, bm: int, bn: int, batched: bool):
    """BlockSpec for the prune mask: a (bm, bn) periodic tile is broadcast
    to every grid cell; a full (m, n) mask is read per-tile."""
    tile = mask_shape == (bm, bn)
    if batched:
        return pl.BlockSpec((bm, bn), lambda b, i, j, k, meta: (0, 0) if tile else (i, j))
    return pl.BlockSpec((bm, bn), lambda i, j, k, meta: (0, 0) if tile else (i, j))


def _kernel(meta_ref, x_ref, w_ref, pmask_ref, o_ref, acc_ref, *, rows, cols):
    # program ids are read at top level: interpret mode cannot lower them
    # inside a pl.when branch
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _drain():
        word = _pe_word(meta_ref, i, j, rows, cols)
        o_ref[...] = _drain_tile(acc_ref[...], word, pmask_ref[...])


def _keep_all(bm: int, bn: int) -> jax.Array:
    return jnp.full((bm, bn), -1, jnp.int32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def ft_matmul(
    x: jax.Array,
    w: jax.Array,
    meta: jax.Array,
    prune_mask: jax.Array | None = None,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Single-pass protected matmul.  ``meta`` is the packed (rows, cols)
    int32 PE grid of ``engine.fault_meta_grid``, already plan-gathered;
    ``prune_mask`` is an int32 AND-mask of shape (bm, bn) (periodic tile) or
    (m, n), or None for no pruning."""
    m, kdim = x.shape
    _, n = w.shape
    rows, cols = meta.shape
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0
    gm, gn, gk = m // bm, n // bn, kdim // bk

    if prune_mask is None:
        prune_mask = _keep_all(bm, bn)
    assert prune_mask.shape in ((bm, bn), (m, n))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, meta: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k, meta: (k, j)),
            _prune_spec(prune_mask.shape, bm, bn, batched=False),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, meta: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, cols=cols),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="ft_matmul",
    )(meta.reshape(-1).astype(jnp.int32), x, w, prune_mask)


def _kernel_batched(meta_ref, x_ref, w_ref, pmask_ref, o_ref, acc_ref, *, rows, cols):
    i, j, k = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], w_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(3) - 1)
    def _drain():
        word = _pe_word(meta_ref, i, j, rows, cols)
        o_ref[0] = _drain_tile(acc_ref[...], word, pmask_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def ft_matmul_batched(
    x: jax.Array,
    w: jax.Array,
    meta: jax.Array,
    prune_mask: jax.Array | None = None,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Batched-weight protected matmul: ``x (E, M, K) @ w (E, K, N)`` with the
    expert axis as the outermost grid dimension — the MoE expert-matmul path.
    Every expert runs on the same virtual PE array (each expert's matmul is
    one virtual-array execution, so the tile→PE map — and the prune mask —
    repeats per expert)."""
    e, m, kdim = x.shape
    _, _, n = w.shape
    rows, cols = meta.shape
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0
    gm, gn, gk = m // bm, n // bn, kdim // bk

    if prune_mask is None:
        prune_mask = _keep_all(bm, bn)
    assert prune_mask.shape in ((bm, bn), (m, n))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e, gm, gn, gk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b, i, j, k, meta: (b, i, k)),
            pl.BlockSpec((1, bk, bn), lambda b, i, j, k, meta: (b, k, j)),
            _prune_spec(prune_mask.shape, bm, bn, batched=True),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, k, meta: (b, i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel_batched, rows=rows, cols=cols),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, m, n), jnp.float32),
        interpret=interpret,
        name="ft_matmul_batched",
    )(meta.reshape(-1).astype(jnp.int32), x, w, prune_mask)
