"""Grouped-DPPU recompute kernel (paper Section IV-C1).

The DPPU recomputes the output tiles named by the fault PE table (FPT),
reading the *same* inputs/weights the faulty PEs consumed.  The paper's AGU —
which turns FPT coordinates into register-file read addresses — becomes Pallas
scalar prefetch: the FPT rides in SMEM and the BlockSpec index_maps use it to
steer the HBM→VMEM DMAs of x-row-panels and w-col-panels, exactly an address
generation unit for the memory pipeline.

Grid = (F, K/bk): fault-major so each fault's K-loop accumulates in the VMEM
scratch (the DPPU adder tree's pipelined accumulation).  The grouped-DPPU
parallelism across faults maps to TPU grid-level pipelining rather than
spatial lanes — the hardware-adaptation note in DESIGN.md §2.

Padded FPT entries (coordinates < 0) are clamped to tile (0, 0); recomputing a
healthy tile writes back identical data, so padding is harmless (and the ops
wrapper masks it out of the scatter anyway).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(rows_ref, cols_ref, x_ref, w_ref, o_ref, acc_ref):
    del rows_ref, cols_ref  # consumed by the index maps (the AGU)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == pl.num_programs(1) - 1)
    def _drain():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def dppu_recompute(
    x: jax.Array,
    w: jax.Array,
    fpt: jax.Array,  # (F, 2) int32 tile coords, -1 padded
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns (F, bm, bn) recomputed tiles (padded entries = tile (0,0))."""
    m, kdim = x.shape
    _, n = w.shape
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0
    f = fpt.shape[0]
    gk = kdim // bk
    trow = jnp.maximum(fpt[:, 0], 0).astype(jnp.int32)
    tcol = jnp.maximum(fpt[:, 1], 0).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(f, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda fi, k, rows, cols: (rows[fi], k)),
            pl.BlockSpec((bk, bn), lambda fi, k, rows, cols: (k, cols[fi])),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda fi, k, rows, cols: (fi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((f, bm, bn), jnp.float32),
        interpret=interpret,
        name="dppu_recompute",
    )(trow, tcol, x, w)


# --------------------------------------------------------------------------- #
# DPPU scan probe: batched AR == BAR + PR check (paper Section IV-D)
# --------------------------------------------------------------------------- #
def probe_check_ref(
    px: jax.Array, pw: jax.Array, ar: jax.Array, *, window: int
) -> jax.Array:
    """Reference AR == BAR + PR mismatch check over a row-block of PEs.

    ``px``: (block, K) probe activations, ``pw``: (K, cols) probe weights,
    ``ar``: (block, cols) accumulator results read back from the (possibly
    faulty) array.  The DPPU lanes recompute the partial result PR over the
    first ``window`` MACs and the before-window accumulation BAR over the
    rest; a PE is flagged iff AR != BAR + PR.  int32-exact (the paper's
    datapath) — returns a (block, cols) bool mismatch mask.
    """
    w = min(window, px.shape[-1])
    pr = jnp.matmul(
        px[..., :w].astype(jnp.int32), pw[:w].astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )
    bar = jnp.matmul(
        px[..., w:].astype(jnp.int32), pw[w:].astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )
    return ar.astype(jnp.int32) != pr + bar


def _probe_kernel(px_ref, pw_ref, ar_ref, o_ref, acc_ref):
    # Same lane structure as the recompute kernel: the K-grid accumulates in
    # VMEM scratch (the first K-block is PR, the rest is BAR — the split is
    # positional, the sum is what the comparator sees at drain).
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        px_ref[...].astype(jnp.float32),
        pw_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == pl.num_programs(0) - 1)
    def _drain():
        o_ref[...] = (ar_ref[...] != acc_ref[...].astype(jnp.int32)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def probe_check(
    px: jax.Array,
    pw: jax.Array,
    ar: jax.Array,
    *,
    bk: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Pallas AR == BAR + PR scan probe: one fused pass over the row-block.

    Grid = (K/bk,): each step accumulates one K-panel (the first panel is the
    partial result PR, the remainder the before-window BAR) and the drain
    step compares against the array's accumulator readback — the checking-
    list-buffer comparator of Section IV-D.  f32 accumulation is exact for
    the small-int probe operands (|acc| << 2^24).  Returns (block, cols)
    int32 mismatch flags.

    ``bk`` defaults to a K-block the TPU compiler accepts for any K: 128-lane
    panels when K is a multiple of 128, else the whole K in one panel.
    Where the PR/BAR boundary falls only regroups the sum, so the flags do
    not depend on it.
    """
    block, kdim = px.shape
    _, cols = pw.shape
    if bk is None:
        bk = 128 if kdim % 128 == 0 else kdim
    assert kdim % bk == 0, (kdim, bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(kdim // bk,),
        in_specs=[
            pl.BlockSpec((block, bk), lambda k: (0, k)),
            pl.BlockSpec((bk, cols), lambda k: (k, 0)),
            pl.BlockSpec((block, cols), lambda k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, cols), lambda k: (0, 0)),
        scratch_shapes=[pltpu.VMEM((block, cols), jnp.float32)],
    )
    return pl.pallas_call(
        _probe_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((block, cols), jnp.int32),
        interpret=interpret,
        name="probe_check",
    )(px.astype(jnp.int32), pw.astype(jnp.int32), ar.astype(jnp.int32))


def scatter_overwrite(
    corrupted: jax.Array, tiles: jax.Array, fpt: jax.Array, *, bm: int, bn: int
) -> jax.Array:
    """Output-buffer overwrite with byte mask (paper Fig. 5 step 4): write each
    recomputed tile over the faulty PE's output region; padded entries no-op."""

    def body(i, out):
        ti, tj = fpt[i, 0], fpt[i, 1]
        valid = ti >= 0
        ti_ = jnp.maximum(ti, 0) * bm
        tj_ = jnp.maximum(tj, 0) * bn
        cur = jax.lax.dynamic_slice(out, (ti_, tj_), (bm, bn))
        new = jnp.where(valid, tiles[i], cur)
        return jax.lax.dynamic_update_slice(out, new, (ti_, tj_))

    return jax.lax.fori_loop(0, fpt.shape[0], body, corrupted)
