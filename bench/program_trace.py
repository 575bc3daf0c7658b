"""The program's own spans and site scopes in the run's profiler trace.

The program names its phases on the host (``jax.profiler.TraceAnnotation``
spans starting ``hyca.``, ``repro.obs.host``) and its protected matmuls and
weight cast on the device (``jax.named_scope``: one scope per ``FTContext``
site, ``weights.cast`` around the per-step cast).  ``bench/trace_reduce.py``
keeps only the harness's ``bench.`` spans, and a reader is handed only the
reduced window (``ctx.window``), so this module finds the trace again:

* the run's ``.xplane.pb`` is the newest one under a ``bench-trace-*``
  directory in ``tempfile.gettempdir()`` (where ``harness.Tracer`` records,
  until the run ends) whose first ``bench.step`` span starts exactly at
  ``ctx.window.lo``, read on the same clock in the same arithmetic;
* from it, the ``hyca.*`` host spans and the first device plane's
  ``XLA Ops`` with the name scope of each: the ``tf_op`` stat of the op's
  event metadata (the JAX name stack of the op,
  ``jit(_step)/while/body/.../ffn/...``).  ``jax.profiler.ProfileData``
  shows an event's own stats but not its metadata's, so the file is read a
  second time with the XSpace schema that TensorFlow's profiler protos ship
  (``tsl/profiler/protobuf/xplane_pb2.py``, loaded on its own, without
  importing TensorFlow).

Each file is read once per process.  Where no trace matches, or the matching
trace holds no ``hyca.server.step`` span (a program without these spans),
:func:`find` returns None and the readers stay silent.
"""
from __future__ import annotations

import bisect
import dataclasses
import importlib.util
import pathlib
import re
import tempfile

from bench import layer_metrics, trace_reduce
from bench.trace_reduce import Event

PREFIX = "hyca."
ROOT_SPAN = "hyca.server.step"
SCAN_SYNC = "hyca.fault.scan.sync"
GC_SPAN = "hyca.python.gc"
SCOPE_STAT = "tf_op"
# the program's protection sites (repro.core.ftcontext.SITES), each a scope
SITES = frozenset(("attn.qkv", "attn.out", "ffn", "moe.router", "moe.expert",
                   "ssm.in", "ssm.out", "head", "mm.proj"))
WEIGHT_CAST = "weights.cast"


@dataclasses.dataclass(frozen=True)
class ScopedOp(Event):
    scope: str = ""

    @property
    def scopes(self) -> list[str]:
        return self.scope.split("/")


@dataclasses.dataclass
class ProgramTrace:
    first_step: float | None    # start of the first bench.step span (s)
    spans: list                 # host Events named hyca.*, by start
    ops: list                   # ScopedOp of the first device plane's XLA Ops

    def spans_named(self, name: str, lo: float, hi: float) -> list:
        return [s for s in self.spans if s.name == name and s.start >= lo and s.end <= hi]


_cache: dict[str, ProgramTrace] = {}


def _xplane_schema():
    """The ``xplane_pb2`` module of the installed TensorFlow, or None."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    file = pathlib.Path(spec.submodule_search_locations[0]) / "tsl/profiler/protobuf/xplane_pb2.py"
    if not file.is_file():
        return None
    mspec = importlib.util.spec_from_file_location("bench_xplane_pb2", file)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def metadata_scopes(path: str, plane_name: str) -> dict[str, str]:
    """``{event metadata name: tf_op}`` of one plane, from the stats of its
    event metadata; empty where the schema is not installed."""
    schema = _xplane_schema()
    if schema is None:
        return {}
    space = schema.XSpace()
    space.ParseFromString(pathlib.Path(path).read_bytes())
    for plane in space.planes:
        if plane.name != plane_name:
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        out = {}
        for md in plane.event_metadata.values():
            for st in md.stats:
                if names.get(st.metadata_id) == SCOPE_STAT:
                    out[md.name] = st.str_value or names.get(st.ref_value, "")
        return out
    return {}


def load(path: str) -> ProgramTrace:
    """The ``hyca.*`` spans and scoped device ops of one ``.xplane.pb``."""
    if path in _cache:
        return _cache[path]
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    steps, spans, ops = [], [], {}
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops[plane.name] = [
                        Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append(Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
                    elif e.name == trace_reduce.SPAN_PREFIX + "step":
                        steps.append(e.start_ns * 1e-9)
    spans.sort(key=lambda e: e.start)
    first = []
    if ops:
        plane = min(ops)
        scopes = metadata_scopes(path, plane)
        first = [ScopedOp(o.name, o.start, o.dur, scopes.get(o.name, "")) for o in ops[plane]]
    pt = ProgramTrace(first_step=min(steps) if steps else None, spans=spans, ops=first)
    _cache[path] = pt
    return pt


def candidates() -> list[str]:
    """``.xplane.pb`` files under ``bench-trace-*`` directories, newest first."""
    found = pathlib.Path(tempfile.gettempdir()).glob("bench-trace-*/**/*.xplane.pb")
    return [str(p) for p in sorted(found, key=lambda p: p.stat().st_mtime, reverse=True)]


def find(window: trace_reduce.Window) -> ProgramTrace | None:
    """The program trace of the run whose reduced window is ``window``."""
    for path in candidates():
        pt = load(path)
        if pt.first_step == window.lo:
            return pt if pt.spans_named(ROOT_SPAN, window.lo, window.hi) else None
    return None


# --------------------------------------------------------------------------- #
# what the readers compute
# --------------------------------------------------------------------------- #
def _roots(ctx) -> tuple[ProgramTrace, list] | None:
    pt = find(ctx.window)
    if pt is None:
        return None
    return pt, pt.spans_named(ROOT_SPAN, ctx.window.lo, ctx.window.hi)


def unattributed_idle_ms_per_step(ctx) -> float | None:
    """Idle chip time inside ``hyca.server.step`` under none of its child
    spans, per traced step (ms)."""
    found = _roots(ctx)
    if found is None:
        return None
    pt, roots = found
    busy = ctx.window.busy
    total = 0.0
    for r in roots:
        inside = [s for s in pt.spans if s is not r and s.start >= r.start and s.end <= r.end]
        idle = r.dur - trace_reduce.covered(busy, r.start, r.end)
        attributed = sum(b - a - trace_reduce.covered(busy, a, b)
                         for a, b in trace_reduce.union(inside, r.start, r.end))
        total += idle - attributed
    return 1e3 * total / len(roots)


def _span_ms_per_step(ctx, name: str) -> float | None:
    found = _roots(ctx)
    if found is None:
        return None
    pt, roots = found
    spans = pt.spans_named(name, ctx.window.lo, ctx.window.hi)
    return 1e3 * sum(s.dur for s in spans) / len(roots)


def scan_sync_ms_per_step(ctx) -> float | None:
    """Host time of the fault scan's device->host readbacks per step (ms)."""
    return _span_ms_per_step(ctx, SCAN_SYNC)


def gc_ms_per_step(ctx) -> float | None:
    """Time in the Python collector per step, over the traced window (ms)."""
    return _span_ms_per_step(ctx, GC_SPAN)


def scoped_device_ms(ctx, keep) -> float | None:
    """Device time per decode step of the step's operations whose scope
    path passes ``keep(components, op)`` (ms); None when no operation of a
    step carries any of the program's scopes."""
    pt = find(ctx.window)
    if pt is None:
        return None
    steps = ctx.window.modules_matching(layer_metrics.STEP_MODULE)
    if not steps:
        return None
    steps = sorted(steps, key=lambda m: m.start)
    starts = [m.start for m in steps]
    inside = []
    for o in pt.ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.end <= steps[i].end:
            inside.append(o)
    ops = trace_reduce.leaves(inside)
    named = [o for o in ops if (set(o.scopes) & SITES) or WEIGHT_CAST in o.scopes]
    if not named:
        return None
    return 1e3 * sum(o.dur for o in ops if keep(o.scopes, o)) / len(steps)


def ft_wrap_device_ms(ctx) -> float | None:
    """Device time per decode step of operations under a protection site's
    scope other than the ``ft_matmul`` kernel: casts, pads, the fault grid,
    the slice (ms)."""
    kernel = re.compile(layer_metrics.KERNEL)
    return scoped_device_ms(
        ctx, lambda scopes, op: bool(set(scopes) & SITES) and not kernel.search(op.name))


def weight_cast_device_ms(ctx) -> float | None:
    """Device time per decode step under the ``weights.cast`` scope (ms)."""
    return scoped_device_ms(ctx, lambda scopes, op: WEIGHT_CAST in scopes)
