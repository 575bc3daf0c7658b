"""Production mesh factory.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Every axis is ``Auto``: the model code places activations with
``with_sharding_constraint`` (``dist.sharding.shard``), which only accepts
Auto axes, and ``jax.make_mesh`` would otherwise make them ``Explicit``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1, devices=None):
    """``(data, model)`` mesh over ``devices`` (default: every locally
    visible device)."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    assert n % model == 0
    return _mesh((n // model, model), ("data", "model"), devices)
