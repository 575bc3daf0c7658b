"""Host spans on the profiler's clock, and the Python collector's pauses.

:func:`span` names one phase of the program in the JAX profiler's trace
(``jax.profiler.TraceAnnotation``), so a captured ``.xplane.pb`` shows what
the host was doing while the chip waited.  Every name starts ``hyca.``.
The profiler's TraceMe builds its metadata only while a trace is being
recorded; with no trace running a span costs one object and one native call
(about a microsecond).

The span tree of one ``FaultTolerantServer.step()`` (docs/observability.md):

    hyca.server.step          step, active, positions, tokens, prefill_tokens,
                              queue (+ moe_pairs, moe_max_load for an MoE model)
      hyca.fault.inject       (fault_rate > 0 only)
      hyca.fault.scan
        hyca.fault.scan.sync    every device->host readback of the scan
        hyca.fault.scan.probe   host probe operands + the probe dispatch
      hyca.repair             (only when the repair hook plans)
      hyca.sched.admit        capacity limit, admission, expiries
      hyca.cache.reset        (only when a slot was admitted)
      hyca.decode.feed        plan_feed (with the prompt chunk), the fault
                              state, the feed's copy
      hyca.decode.dispatch    the jitted decode step's dispatch
      hyca.decode.sample      argmax and its device->host copy (with the
                              routing load, for a traced MoE step)
      hyca.sched.commit
      hyca.metrics.record     StepRecord and the series append

:func:`install_gc_hook` adds a ``gc.callbacks`` hook that opens a
``hyca.python.gc`` span (attribute ``generation``) for every collection and
counts, per generation, the collections and their seconds.
:func:`repro.obs.export.gc_text` exports those counters as
``hyca_python_gc_seconds_total`` and ``hyca_python_gc_collections_total``.
"""
from __future__ import annotations

import gc
import time

import jax

PREFIX = "hyca."


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """A profiler span named ``hyca.<name>`` carrying ``attrs`` as stats.
    Use it as a context manager; ``set_metadata(**attrs)`` on it adds
    attributes known only at the end."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **attrs)


class GCSpans:
    """The collector hook: one ``hyca.python.gc`` span per collection, and
    per-generation counters of collections and seconds."""

    def __init__(self):
        self.collections = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._open = None          # (span, generation, perf_counter_ns at start)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            s = span("python.gc", generation=info["generation"])
            s.__enter__()
            self._open = (s, info["generation"], time.perf_counter_ns())
        elif phase == "stop" and self._open is not None:
            s, gen, t0 = self._open
            self._open = None
            self.seconds[gen] += (time.perf_counter_ns() - t0) * 1e-9
            self.collections[gen] += 1
            s.__exit__(None, None, None)

    def counters(self) -> dict[str, list]:
        return {"gc_seconds_total": list(self.seconds),
                "gc_collections_total": list(self.collections)}


_gc_hook: GCSpans | None = None


def install_gc_hook() -> GCSpans:
    """Install the collector hook once per process (the collector is
    process-wide); later calls return the installed one."""
    global _gc_hook
    if _gc_hook is None:
        _gc_hook = GCSpans()
        gc.callbacks.append(_gc_hook)
    return _gc_hook


def gc_hook() -> GCSpans | None:
    """The installed collector hook, or None before :func:`install_gc_hook`."""
    return _gc_hook
