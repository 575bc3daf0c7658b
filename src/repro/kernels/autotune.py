"""Block-size autotuning for the fused ``ft_matmul`` kernel family.

The right (bm, bn, bk) depends on the matmul shape, operand dtype, and
backend — a decode-time (4, 64) projection wastes 16× the work if it is
padded to a 128-row block, while a prefill-sized panel wants the full MXU
tile.  This module keys measured block choices on ``(m, n, k, dtype,
backend)``, the dtype being the one the operands enter the kernel in
(``…:bfloat16:pallas`` for bf16 serving on the chip), and
persists them to a JSON cache (``experiments/autotune/ft_matmul.json`` by
default, override dir with ``REPRO_AUTOTUNE_DIR``) that
``build_ftcontext(fused_block="auto")`` loads once per process; unseen
shapes fall back to a shape-aware heuristic (:func:`default_block`) rather
than a fixed 128³.

Cache file format (one object, one entry per shape key)::

    {
      "4x64x64:float32:interpret": {"block": [8, 128, 128], "ms": 0.41},
      ...
    }

Re-tune on new hardware by deleting stale entries (or pointing
``REPRO_AUTOTUNE_DIR`` at a fresh dir) and running::

    python -m repro.kernels.autotune M N K [--dtype bfloat16] [--backend pallas]

or passing ``autotune_shapes=[(m, n, k), ...]`` to ``build_ftcontext`` on a
TPU host (docs/kernels.md).  Measurements are min-of-repeats wall time of
the real kernel on random operands — the fault table contents cannot change
the runtime (the mux is branch-free), so tuning is fault-agnostic.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

# Candidate grid for the measured search: MXU-aligned tiles plus small-M
# blocks for decode shapes.  bn/bk stay 128-multiples (lane tiling); bm may
# shrink to the operand dtype's sublane tile (8 in f32, 16 in bf16) for
# skinny activations — smaller candidates are skipped on the chip.
DEFAULT_CANDIDATES: tuple[tuple[int, int, int], ...] = (
    (8, 128, 128),
    (16, 128, 128),
    (32, 128, 128),
    (64, 128, 128),
    (128, 128, 128),
    (128, 256, 128),
    (256, 128, 128),
    (256, 256, 128),
    (128, 128, 256),
)

_CACHE: dict[str, dict] | None = None
_CACHE_PATH: str | None = None


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def cache_path() -> str:
    """Resolve the persisted cache file: ``$REPRO_AUTOTUNE_DIR/ft_matmul.json``
    or ``<repo>/experiments/autotune/ft_matmul.json``."""
    base = os.environ.get("REPRO_AUTOTUNE_DIR")
    if base is None:
        repo = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
        base = os.path.join(repo, "experiments", "autotune")
    return os.path.join(base, "ft_matmul.json")


def _key(m: int, n: int, k: int, dtype, backend: str) -> str:
    return f"{m}x{n}x{k}:{jnp.dtype(dtype).name}:{backend}"


def load_cache(path: str | None = None, *, reload: bool = False) -> dict[str, dict]:
    """Load (and memoise) the autotune cache.  Missing/corrupt files load as
    empty — an absent cache must never break context build."""
    global _CACHE, _CACHE_PATH
    path = path or cache_path()
    if _CACHE is not None and _CACHE_PATH == path and not reload:
        return _CACHE
    cache: dict[str, dict] = {}
    try:
        with open(path) as f:
            raw = json.load(f)
        if isinstance(raw, dict):
            for key, entry in raw.items():
                blk = entry.get("block") if isinstance(entry, dict) else None
                if (isinstance(blk, list) and len(blk) == 3
                        and all(isinstance(b, int) and b > 0 for b in blk)):
                    cache[key] = entry
    except (OSError, ValueError):
        pass
    _CACHE, _CACHE_PATH = cache, path
    return cache


def save_cache(cache: dict[str, dict], path: str | None = None) -> str:
    global _CACHE, _CACHE_PATH
    path = path or cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
        f.write("\n")
    _CACHE, _CACHE_PATH = dict(cache), path
    return path


def reset_cache() -> None:
    """Drop the in-memory cache (tests repoint REPRO_AUTOTUNE_DIR)."""
    global _CACHE, _CACHE_PATH
    _CACHE, _CACHE_PATH = None, None


def sublane_tile(dtype) -> int:
    """Rows of one TPU sublane tile for operands of ``dtype``: 8 for 4-byte
    values, 16 for 2-byte ones (two packed to a 32-bit sublane word)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


# a matmul of at most this many rows runs as one row block of the kernel
# grid: each weight tile is then read once (a decode step with its prompt
# chunk is bound by weight reads, not by the rows' FLOPs)
ONE_BLOCK_ROWS = 512


def default_block(m: int, n: int, k: int, *, backend: str = "pallas",
                  dtype=jnp.float32) -> tuple[int, int, int]:
    """Shape-aware heuristic for shapes the cache has not seen: 128-wide
    MXU tiles in n and k; in m, every row in one block (in sublane-tile
    steps of the operand dtype) up to :data:`ONE_BLOCK_ROWS`, so a (4, N)
    decode row is padded to 8 rows in f32 or 16 in bf16 and a decode step's
    320 rows read each weight tile once, and 128-row blocks beyond."""
    del backend  # same heuristic everywhere the kernel runs
    rows = _round_up(max(m, 1), sublane_tile(dtype))
    return (rows if rows <= ONE_BLOCK_ROWS else 128, 128, 128)


def validate_fused_block(block, *, backend: str, dtype=jnp.float32) -> tuple[int, int, int]:
    """Validate an explicit ``fused_block`` against backend tile constraints
    — a clear error instead of a Pallas lowering failure.  Non-divisible
    *input shapes* are fine (the dispatch zero-pads to block multiples); the
    block itself must be positive and, for the compiled TPU kernel, aligned
    to the operand dtype's (sublane, 128, 128) tile.  Context build checks
    the f32 tile; each fused call checks again with its operand dtype."""
    if (not isinstance(block, (tuple, list)) or len(block) != 3
            or not all(isinstance(b, int) and not isinstance(b, bool) and b > 0 for b in block)):
        raise ValueError(
            f"fused_block must be 'auto' or a (bm, bn, bk) tuple of positive "
            f"ints, got {block!r}"
        )
    bm, bn, bk = (int(b) for b in block)
    sub = sublane_tile(dtype)
    if backend == "pallas" and (bm % sub or bn % 128 or bk % 128):
        raise ValueError(
            f"fused_block {(bm, bn, bk)} violates the TPU tile constraints: "
            f"bm must be a multiple of {sub} and bn/bk multiples of 128 "
            f"({jnp.dtype(dtype).name} sublane×lane tiling); pick an aligned "
            f"block or use fused_block='auto'"
        )
    return (bm, bn, bk)


def resolve_block(m: int, n: int, k: int, *, dtype=jnp.float32,
                  backend: str = "pallas") -> tuple[int, int, int]:
    """The ``fused_block="auto"`` lookup: persisted cache hit, else the
    heuristic, both keyed on the operand dtype.  Called at trace time with
    static shapes — the result is a compile-time constant."""
    entry = load_cache().get(_key(m, n, k, dtype, backend))
    if entry is not None:
        return tuple(entry["block"])
    return default_block(m, n, k, backend=backend, dtype=dtype)


def _time_block(m: int, n: int, k: int, dtype, block: tuple[int, int, int],
                *, interpret: bool, rows: int, cols: int,
                repeats: int, steps: int) -> float:
    from repro.kernels.ft_matmul import ft_matmul  # deferred: pallas import

    bm, bn, bk = block
    rng = np.random.default_rng(0)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    x = jnp.asarray(rng.standard_normal((mp, kp)), dtype)
    w = jnp.asarray(rng.standard_normal((kp, np_)), dtype)
    run = functools.partial(
        ft_matmul, x, w, jnp.zeros((rows, cols), jnp.int32),
        bm=bm, bn=bn, bk=bk, interpret=interpret,
    )
    jax.block_until_ready(run())  # compile + warmup
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = run()
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / steps)
    return best * 1e3


def autotune_block(
    m: int, n: int, k: int, *,
    dtype=jnp.float32,
    backend: str | None = None,
    candidates: tuple[tuple[int, int, int], ...] = DEFAULT_CANDIDATES,
    rows: int = 32, cols: int = 32,
    repeats: int = 3, steps: int = 8,
    persist: bool = True,
) -> tuple[tuple[int, int, int], float]:
    """Measured search over ``candidates`` for one (m, n, k, dtype) shape;
    returns (best block, best ms) and persists the winner.  ``backend``
    defaults to ``pallas`` on TPU and ``interpret`` elsewhere (interpret
    timings tune the interpret path only — re-run on real hardware for
    production numbers; see docs/kernels.md)."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "interpret"
    interpret = backend != "pallas"
    sub = sublane_tile(dtype)
    best_blk, best_ms = None, float("inf")
    for cand in candidates:
        if backend == "pallas" and cand[0] % sub:
            continue  # bm below the dtype's sublane tile: Mosaic refuses it
        blk = validate_fused_block(cand, backend=backend, dtype=dtype)
        ms = _time_block(m, n, k, dtype, blk, interpret=interpret,
                         rows=rows, cols=cols, repeats=repeats, steps=steps)
        if ms < best_ms:
            best_blk, best_ms = blk, ms
    cache = dict(load_cache())
    cache[_key(m, n, k, dtype, backend)] = {
        "block": list(best_blk), "ms": round(best_ms, 4),
    }
    if persist:
        save_cache(cache)
    else:
        global _CACHE
        _CACHE = cache
    return best_blk, best_ms


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("m", type=int)
    ap.add_argument("n", type=int)
    ap.add_argument("k", type=int)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--backend", default=None, choices=[None, "pallas", "interpret"])
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--cols", type=int, default=32)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args(argv)
    blk, ms = autotune_block(
        args.m, args.n, args.k, dtype=jnp.dtype(args.dtype),
        backend=args.backend, rows=args.rows, cols=args.cols, steps=args.steps,
    )
    print(f"[autotune] {args.m}x{args.n}x{args.k}:{args.dtype}: "
          f"block={blk} ({ms:.3f} ms) -> {cache_path()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
