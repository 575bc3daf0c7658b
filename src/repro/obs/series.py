"""Device-side time-series telemetry: the :class:`SeriesBuffer` ring.

A ``SeriesBuffer`` is a pytree of fixed-capacity ring buffers (one per named
channel) plus a single write cursor.  :meth:`SeriesBuffer.record` appends one
row to every channel with ``lax.dynamic_update_slice_in_dim`` — a pure
functional update, so the buffer can ride any jitted program as a carried
leaf: the vectorized fleet engine threads one through its ``lax.scan`` chunk
program (``run_vfleet(FleetConfig(series=True))``, a leading replica axis on
every channel).  The host-loop server needs no ring: it already holds one
StepRecord per step, and ``FaultTolerantServer.series_host`` reads the same
channels from them.

Design rules, mirrored from ``repro.obs.counters``:

  * **no host sync on the write path** — ``record`` is trace-time jnp ops;
    the only device→host transfer is :meth:`harvest` (the fleet driver calls
    it once per chunk);
  * **leaf-only** — the buffer's capacity and channel set are fixed at
    creation, so swapping fault tables, chaos maps, or the buffer itself
    never retraces the compiled program (asserted à la test_ftcontext);
  * **ring semantics** — past ``capacity`` writes the oldest rows are
    overwritten; ``harvest`` returns only rows still resident, chronologically.

The persisted artifact (:func:`save_series` / :func:`load_series`) is a
single ``.npz``: one array per channel, first axis = time, plus a JSON
``__meta__`` blob (step offset, channel names, run labels) — the series half
of what ``python -m repro.obs.replay`` joins with the event JSONL.
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SeriesBuffer:
    """Fixed-capacity multi-channel ring buffer pytree.

    ``data[name]`` has shape ``(capacity, *row_shape)``; ``cursor`` is the
    total number of rows ever recorded (an int32 scalar leaf — it wraps into
    the ring as ``cursor % capacity``).
    """

    data: dict[str, jax.Array]
    cursor: jax.Array

    def tree_flatten(self):
        names = tuple(sorted(self.data))
        leaves = tuple(self.data[k] for k in names) + (self.cursor,)
        return leaves, names

    @classmethod
    def tree_unflatten(cls, names, leaves):
        return cls(data=dict(zip(names, leaves[:-1])), cursor=leaves[-1])

    # ------------------------------------------------------------------ #
    @classmethod
    def create(cls, capacity: int,
               spec: dict[str, tuple[tuple[int, ...], np.dtype]]) -> "SeriesBuffer":
        """Allocate a zeroed buffer: ``spec`` maps channel name to
        ``(row_shape, dtype)`` — e.g. ``{"tokens": ((R,), jnp.int32)}``."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        return cls(
            data={k: jnp.zeros((capacity,) + tuple(shape), dtype)
                  for k, (shape, dtype) in spec.items()},
            cursor=jnp.int32(0),
        )

    @property
    def capacity(self) -> int:
        return next(iter(self.data.values())).shape[0]

    def record(self, values: dict[str, jax.Array]) -> "SeriesBuffer":
        """Append one row per channel (pure; returns the updated buffer).
        ``values`` must name exactly the channels the buffer was created
        with — a missing or extra channel is a wiring bug, not data."""
        if set(values) != set(self.data):
            raise ValueError(
                f"series channels mismatch: buffer has {sorted(self.data)}, "
                f"record got {sorted(values)}"
            )
        idx = self.cursor % self.capacity
        data = {
            k: jax.lax.dynamic_update_slice_in_dim(
                arr, jnp.asarray(values[k], arr.dtype)[None], idx, axis=0)
            for k, arr in self.data.items()
        }
        return SeriesBuffer(data=data, cursor=self.cursor + 1)

    # ------------------------------------------------------------------ #
    @property
    def written(self) -> int:
        """Total rows ever recorded (device→host sync)."""
        return int(self.cursor)

    def harvest(self, start: int = 0) -> dict[str, np.ndarray]:
        """Rows ``[start, written)`` in write order, as host arrays.  Rows
        older than ``written - capacity`` have been overwritten and raise —
        the caller (e.g. the per-chunk fleet harvest) must keep up with the
        ring."""
        end = self.written
        if start > end:
            raise ValueError(f"harvest start {start} is past cursor {end}")
        if end - start > self.capacity:
            raise ValueError(
                f"rows [{start}, {end}) exceed ring capacity {self.capacity}; "
                f"oldest resident row is {end - self.capacity}"
            )
        idx = np.arange(start, end) % self.capacity
        return {k: np.asarray(v)[idx] for k, v in sorted(self.data.items())}


# --------------------------------------------------------------------------- #
# artifact I/O (the replay CLI's series half)
# --------------------------------------------------------------------------- #
def save_series(path: str, series: dict[str, np.ndarray],
                meta: dict | None = None) -> str:
    """Persist harvested series as one ``.npz``: a float/int array per
    channel (first axis = time) plus a JSON ``__meta__`` blob.  Returns the
    path actually written (``.npz`` appended by numpy when missing)."""
    arrays = {k: np.asarray(v) for k, v in series.items()}
    lengths = {v.shape[0] for v in arrays.values()}
    if len(lengths) > 1:
        raise ValueError(f"channel lengths differ: { {k: v.shape[0] for k, v in arrays.items()} }")
    meta = dict(meta or {})
    meta.setdefault("channels", sorted(arrays))
    meta.setdefault("length", lengths.pop() if lengths else 0)
    with open(path if path.endswith(".npz") else path + ".npz", "wb") as f:
        np.savez(f, __meta__=np.asarray(json.dumps(meta)), **arrays)
    return path if path.endswith(".npz") else path + ".npz"


def load_series(path: str) -> tuple[dict[str, np.ndarray], dict]:
    """Load a :func:`save_series` artifact -> (channel dict, meta dict)."""
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"])) if "__meta__" in z else {}
        series = {k: z[k] for k in z.files if k != "__meta__"}
    return series, meta
