"""Output-stationary 2-D-array matmul with per-PE stuck-at fault injection.

TPU adaptation of the paper's 32×32 PE array (Section III-A): the MXU-tiled
matmul is the TPU-native analogue — one (bm, bn) output tile plays the role of
one PE's output feature, accumulated output-stationary in a VMEM scratch
across the K grid dimension (the PE's stationary accumulator register).  The
tile→PE map is (ti % rows, tj % cols).

Faults are stuck-at bits on the accumulator (paper Section III-B): at the last
K step the accumulator's f32 bit pattern gets the stuck bit forced before the
tile is drained to the output buffer (HBM).

Per-tile fault metadata arrives pre-gathered to grid shape (gm, gn) by the
ops-layer AGU (address generation unit) so the kernel body needs no dynamic
scalar indexing — each grid cell reads its own (1, 1) SMEM block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _stuck_at(acc: jax.Array, bit: jax.Array, val: jax.Array) -> jax.Array:
    raw = jax.lax.bitcast_convert_type(acc, jnp.int32)
    mask = jnp.left_shift(jnp.int32(1), bit)
    bad = jnp.where(val > 0, raw | mask, raw & ~mask)
    return jax.lax.bitcast_convert_type(bad, jnp.float32)


def _kernel(x_ref, w_ref, bit_ref, val_ref, faulty_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _drain():
        acc = acc_ref[...]
        bad = _stuck_at(acc, bit_ref[0, 0], val_ref[0, 0])
        o_ref[...] = jnp.where(faulty_ref[0, 0] > 0, bad, acc)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "rows", "cols", "interpret")
)
def os_array_matmul(
    x: jax.Array,
    w: jax.Array,
    pe_bit: jax.Array,  # (rows, cols) int32
    pe_val: jax.Array,  # (rows, cols) int32
    pe_faulty: jax.Array,  # (rows, cols) bool/int32
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    rows: int = 32,
    cols: int = 32,
    interpret: bool = False,
) -> jax.Array:
    m, kdim = x.shape
    _, n = w.shape
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, (m, n, kdim, bm, bn, bk)
    gm, gn, gk = m // bm, n // bn, kdim // bk

    # AGU: pre-gather per-tile fault metadata to grid shape.
    ti = jnp.arange(gm) % rows
    tj = jnp.arange(gn) % cols
    bit = pe_bit[ti[:, None], tj[None, :]].astype(jnp.int32)
    val = pe_val[ti[:, None], tj[None, :]].astype(jnp.int32)
    faulty = pe_faulty[ti[:, None], tj[None, :]].astype(jnp.int32)

    meta_spec = pl.BlockSpec(
        (1, 1), lambda i, j, k: (i, j), memory_space=pltpu.SMEM
    )
    return pl.pallas_call(
        _kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            meta_spec,
            meta_spec,
            meta_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="os_array_matmul",
    )(x, w, bit, val, faulty)
