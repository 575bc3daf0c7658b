"""From a JAX profiler trace (``.xplane.pb``) to device busy time, idle gaps
labelled by host span, and kernel time.

On a TPU the trace has one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Ops`` holds one event per executed HLO operation (a ``while`` loop is
an event that contains its body's events) and whose line ``XLA Modules``
holds one event per executed program (``jit_<function>(<id>)``).  The host
plane ``/host:CPU`` holds the benchmark's own spans
(``jax.profiler.TraceAnnotation``, names starting ``bench.``).  Device and
host events share the trace's clock; times here are in seconds.

Busy time is the union of the operation intervals; idle is the rest of the
window.  Idle time is labelled by the innermost ``bench.`` span open during
it: what the host was doing while the chip waited.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: dict            # device plane name -> [Event] of "XLA Ops"
    modules: dict        # device plane name -> [Event] of "XLA Modules"
    spans: list          # host [Event] named "bench.*"


def load(path: str) -> Trace:
    """Read the events the reduction needs from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events]
                    (ops if line.name == OPS_LINE else modules)[plane.name] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    spans.sort(key=lambda e: e.start)
    return Trace(ops=ops, modules=modules, spans=spans)


# --------------------------------------------------------------------------- #
# intervals
# --------------------------------------------------------------------------- #
def union(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals covered by ``events``, clipped to [lo, hi]."""
    iv = sorted((max(e.start, lo), min(e.end, hi)) for e in events if e.end > lo and e.start < hi)
    out: list[list[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the merged intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that the merged intervals leave uncovered."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def innermost(spans, t: float) -> str:
    """The name of the shortest span open at time ``t``, or ``"no span"``."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name if best is not None else "no span"


def leaves(events) -> list[Event]:
    """The events that contain no other event (a loop's body ops, not the loop)."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt.start < e.end and nxt.end <= e.end and nxt is not e:
            continue
        out.append(e)
    return out


def op_name(hlo_text: str) -> str:
    """``%ft_matmul.36 = f32[...] custom-call(...)`` -> ``ft_matmul.36``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


# --------------------------------------------------------------------------- #
# what the metrics and the breakdown read
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Window:
    """One device's view of the traced window [lo, hi]."""
    lo: float
    hi: float
    ops: list
    modules: list
    spans: list

    def __post_init__(self):
        self.busy = union(self.ops, self.lo, self.hi)

    @property
    def seconds(self) -> float:
        return self.hi - self.lo

    @property
    def busy_s(self) -> float:
        return covered(self.busy, self.lo, self.hi)

    def spans_named(self, name: str) -> list:
        full = SPAN_PREFIX + name
        return [s for s in self.spans if s.name == full and s.start >= self.lo and s.end <= self.hi]

    def modules_matching(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [m for m in self.modules if rx.search(m.name) and m.start >= self.lo and m.end <= self.hi]

    def ops_within(self, outer, pattern: str) -> list:
        """Operations matching ``pattern`` that lie inside one of the
        (disjoint) events ``outer``."""
        rx = re.compile(pattern)
        outer = sorted(outer, key=lambda m: m.start)
        starts = [m.start for m in outer]
        out = []
        for o in self.ops:
            i = bisect.bisect_right(starts, o.start) - 1
            if i >= 0 and o.end <= outer[i].end and rx.search(o.name):
                out.append(o)
        return out

    def idle_by_span(self) -> dict:
        """Idle seconds by the innermost host span open during them."""
        out = collections.Counter()
        for s, e in gaps(self.busy, self.lo, self.hi):
            near = [sp for sp in self.spans if sp.end > s and sp.start < e]
            cuts = sorted({s, e} | {t for sp in near for t in (sp.start, sp.end) if s < t < e})
            for a, b in zip(cuts, cuts[1:]):
                out[innermost(near, 0.5 * (a + b))] += b - a
        return dict(out)

    def breakdown(self, top: int = 10) -> dict:
        by_op = collections.Counter()
        for o in leaves([o for o in self.ops if o.end > self.lo and o.start < self.hi]):
            by_op[op_name(o.name)] += min(o.end, self.hi) - max(o.start, self.lo)
        idle = self.idle_by_span()
        return {
            "device_ops": [[k, v] for k, v in by_op.most_common(top)],
            "idle_gaps": [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
        }


def windows(trace: Trace, step_span: str = "step") -> list[Window]:
    """One window per device plane, from the first to the last complete
    ``bench.<step_span>`` span of the trace."""
    steps = [s for s in trace.spans if s.name == SPAN_PREFIX + step_span]
    if not steps:
        return []
    lo, hi = steps[0].start, steps[-1].end
    return [Window(lo, hi, trace.ops.get(p, []), trace.modules.get(p, []), trace.spans)
            for p in sorted(trace.ops)]
