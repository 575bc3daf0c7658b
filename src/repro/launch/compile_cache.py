"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``chip_smoke.py``, ``launch/serve.main``, ``launch/train.main``)
call :func:`enable_compile_cache` before they compile anything; importing
this module changes nothing.

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it as its own option, so
    the cache goes to that directory and no other is set;
  * unset — the cache goes to :data:`CACHE_DIR`, a fixed directory inside
    the checkout (listed in ``.gitignore``).  It is never built from a
    temporary name, a PID or the time: the path is part of the cache key,
    so a directory that moves never hits.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_compile_cache")
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
