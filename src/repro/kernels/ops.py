"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` auto-selects: compiled on TPU, interpret-mode elsewhere
(this container is CPU-only; TPU v5e is the target, interpret mode validates
kernel-body semantics per the repro methodology).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (FaultState, HyCAConfig, _pe_grids, fault_meta_grid,
                               repaired_grid)
from repro.kernels import ref
from repro.kernels.dppu_recompute import dppu_recompute, scatter_overwrite
from repro.kernels.ft_matmul import ft_matmul
from repro.kernels.os_array_matmul import os_array_matmul


def _interp(interpret: bool | None) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def fault_grids_device(state: FaultState, rows: int, cols: int, capacity: int):
    """FPT → dense (rows, cols) bit/val/faulty/repaired grids, entirely on
    device: jit/vmap-composable, so a *batched* FaultState (leading config
    axis — ``campaign.batched_fault_states``) can drive the kernel pipeline
    without a host round-trip per fault configuration.  Bit-identical to the
    host AGU (:func:`fault_grids`) — asserted in tests/test_campaign.py."""
    bit, val, faulty = _pe_grids(state, rows, cols)
    repaired = repaired_grid(state, rows, cols, capacity)
    return bit, val, faulty, repaired


def fault_grids(state: FaultState, rows: int, cols: int, capacity: int):
    """FPT → dense (rows, cols) bit/val/faulty/repaired grids (host AGU).
    Traced states (inside jit/vmap — the campaign's batched repair path) are
    routed to :func:`fault_grids_device` automatically."""
    if isinstance(state.fpt, jax.core.Tracer):
        return fault_grids_device(state, rows, cols, capacity)
    fpt = np.asarray(state.fpt)
    bit = np.zeros((rows, cols), np.int32)
    val = np.zeros((rows, cols), np.int32)
    faulty = np.zeros((rows, cols), bool)
    repaired = np.zeros((rows, cols), bool)
    for i, (r, c) in enumerate(fpt):
        if r < 0:
            continue
        bit[r, c] = int(np.asarray(state.stuck_bit)[i])
        val[r, c] = int(np.asarray(state.stuck_val)[i])
        faulty[r, c] = True
        repaired[r, c] = i < capacity  # FPT is leftmost-sorted
    return (
        jnp.asarray(bit),
        jnp.asarray(val),
        jnp.asarray(faulty),
        jnp.asarray(repaired),
    )


def faulty_array_matmul(
    x, w, state: FaultState, cfg: HyCAConfig, *, bm=128, bn=128, bk=128,
    interpret: bool | None = None,
):
    """Pass 1 of the paper pipeline: the faulty 2-D array's matmul."""
    bit, val, faulty, _ = fault_grids(state, cfg.rows, cfg.cols, cfg.capacity)
    return os_array_matmul(
        x, w, bit, val, faulty, bm=bm, bn=bn, bk=bk, rows=cfg.rows,
        cols=cfg.cols, interpret=_interp(interpret),
    )


def hyca_protected_matmul_twopass(
    x, w, state: FaultState, cfg: HyCAConfig, *, bm=128, bn=128, bk=128,
    interpret: bool | None = None,
):
    """Paper-faithful two-pass pipeline: faulty array pass + DPPU recompute +
    output-buffer overwrite (Fig. 5)."""
    corrupted = faulty_array_matmul(
        x, w, state, cfg, bm=bm, bn=bn, bk=bk, interpret=interpret
    )
    m, n = corrupted.shape
    gm, gn = m // bm, n // bn
    # tile-level FPT: every (tile) mapped to a repaired PE, leftmost-first,
    # truncated to DPPU capacity worth of *PEs* (each PE may own many tiles).
    fpt_pe = np.asarray(state.fpt)
    tiles = []
    for i, (r, c) in enumerate(fpt_pe):
        if r < 0 or i >= cfg.capacity:
            continue
        for ti in range(int(r), gm, cfg.rows):
            for tj in range(int(c), gn, cfg.cols):
                tiles.append((ti, tj))
    if not tiles:
        return corrupted
    tile_fpt = jnp.asarray(np.asarray(tiles, np.int32))
    recomputed = dppu_recompute(
        x, w, tile_fpt, bm=bm, bn=bn, bk=bk, interpret=_interp(interpret)
    )
    return scatter_overwrite(corrupted, recomputed, tile_fpt, bm=bm, bn=bn)


def hyca_protected_matmul_fused(
    x, w, state: FaultState, cfg: HyCAConfig, *, bm=128, bn=128, bk=128,
    interpret: bool | None = None,
):
    """Beyond-paper single-pass fused kernel (see ft_matmul.py)."""
    return ft_matmul(
        x, w, fault_meta_grid(state, cfg), bm=bm, bn=bn, bk=bk,
        interpret=_interp(interpret),
    )


__all__ = [
    "os_array_matmul",
    "dppu_recompute",
    "scatter_overwrite",
    "ft_matmul",
    "ref",
    "fault_grids",
    "fault_grids_device",
    "faulty_array_matmul",
    "hyca_protected_matmul_twopass",
    "hyca_protected_matmul_fused",
]
