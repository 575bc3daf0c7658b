"""Per-layer metrics of the ``--trace 1`` run, computed from the reduced trace.

Each metric of ``BENCHMARK.json``'s ``per_layer`` list has a reader file,
``bench/metrics/<name>.py``, that defines ``read(ctx) -> float | None``;
most delegate to a function here.  A reader that finds nothing to read
returns None, and the metric is left out of the result line.

Names the readers look for in the trace (they come from the program and can
change with it; a reader that no longer finds them goes silent):

* ``STEP_MODULE``: the decode step's program, ``jax.jit`` of
  ``ModelBundle``'s ``_step``;
* ``KERNEL``: the protected matmul's Pallas call, which the compiler names
  after the jitted ``ft_matmul`` wrapper (``%ft_matmul.<n> = ...
  custom-call(...), custom_call_target="tpu_custom_call"``).
"""
from __future__ import annotations

import dataclasses
import pathlib
import types

from bench import families, trace_reduce

STEP_MODULE = r"^jit__step\("
KERNEL = r"^%ft_matmul(\.\d+)? = .*tpu_custom_call"
METRICS_DIR = pathlib.Path(__file__).resolve().parent / "metrics"


@dataclasses.dataclass
class Context:
    window: trace_reduce.Window   # the traced window on the (first) chip
    config: dict                  # the cell's configuration file
    peaks: object                 # bench.peaks.ChipPeaks of the chip
    step_load: list               # (active slots, attended positions) per traced step
    family: types.ModuleType | None = None   # bench.families; by the config's name if None

    def __post_init__(self):
        if self.family is None:
            self.family = families.load(self.config)


def _mean(xs) -> float | None:
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def idle_share(ctx: Context) -> float | None:
    """Share of the traced window in which no operation ran on the chip (%)."""
    w = ctx.window
    return 100.0 * (1.0 - w.busy_s / w.seconds) if w.seconds > 0 else None


def host_ms_per_step(ctx: Context) -> float | None:
    """Wall time of ``server.step()`` not covered by device operations (ms)."""
    w = ctx.window
    v = _mean(s.dur - trace_reduce.covered(w.busy, s.start, s.end) for s in w.spans_named("step"))
    return None if v is None else 1e3 * v


def scan_ms_per_step(ctx: Context) -> float | None:
    """Wall time of the fault manager's ``scan_step`` per server step (ms)."""
    v = _mean(s.dur for s in ctx.window.spans_named("scan_step"))
    return None if v is None else 1e3 * v


def decode_device_ms(ctx: Context) -> float | None:
    """Device time of one execution of the jitted decode step (ms)."""
    v = _mean(m.dur for m in ctx.window.modules_matching(STEP_MODULE))
    return None if v is None else 1e3 * v


def decode_mfu(ctx: Context) -> float | None:
    """Model FLOPs of the traced steps over the window, as a share of the
    chip's bf16 peak (%)."""
    w = ctx.window
    if not ctx.step_load or w.seconds <= 0:
        return None
    flops = sum(ctx.family.step_model_flops(ctx.config, a, c) for a, c in ctx.step_load)
    return 100.0 * flops / w.seconds / ctx.peaks.bf16_flops


def ft_matmul_roofline(ctx: Context) -> float | None:
    """Least time of every protected matmul of the traced decode steps over
    the kernels' summed time (%).  Silent unless each step ran exactly one
    kernel call per model call."""
    w = ctx.window
    steps = w.modules_matching(STEP_MODULE)
    kernels = w.ops_within(steps, KERNEL)
    calls = ctx.family.decode_calls(ctx.config, int(ctx.config["n_slots"]))
    if not steps or len(kernels) != len(steps) * sum(c.count for c in calls):
        return None
    least = len(steps) * sum(c.least_s(ctx.peaks) for c in calls)
    return 100.0 * least / sum(k.dur for k in kernels)


def read(name: str, ctx: Context, metrics_dir: pathlib.Path = METRICS_DIR) -> float | None:
    """Run the reader file of metric ``name``."""
    return families.load_file(metrics_dir / f"{name}.py").read(ctx)
