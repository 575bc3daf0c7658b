"""repro.obs — unified observability for the fault-tolerant runtime.

Seven layers (docs/observability.md):

  * :mod:`repro.obs.host` — program spans on the profiler's clock:
    :func:`span` wraps ``jax.profiler.TraceAnnotation`` under the ``hyca.``
    prefix (the server step, the fault scan, decode feed / dispatch /
    sample), and a ``gc.callbacks`` hook spans and counts the Python
    collector's pauses.

  * :mod:`repro.obs.counters` — device-side FT counters: a :class:`Counters`
    pytree folded beside the compiled step (one jitted :func:`fold_ledger`
    per step) from a statically-discovered call ledger + the engine's own
    fault grids.  Exact element accounting (fault / recomputed / corrupted /
    pruned MACs, per-site dispatch counts) with zero retrace on fault-table
    or plan swaps; the decode step is the same program with counters off.
  * :mod:`repro.obs.events` — structured fault-lifecycle tracing: a
    JSONL-serializable :class:`EventLog` wired through the injector, the
    FaultManager, the repair hook, and the fleet sim; detection and repair
    latency derive from it (exact under chaos injection — injection steps
    are known).
  * :mod:`repro.obs.trace` — per-entity lifecycle spans over the event log:
    request traces (enqueue → admit → prefill → decode → complete) and
    fault traces (inject → suspect → confirmed → repair), OTLP-style JSONL
    with deterministic ids; ``python -m repro.obs.trace`` derives/validates.
  * :mod:`repro.obs.series` — device-side time-series telemetry: a
    :class:`SeriesBuffer` ring pytree carried through the jitted vfleet
    chunk program (per-tick queue depth, tokens, fault counts, capacity —
    zero host sync until harvest); the server reads the same channels from
    its StepRecords.
  * :mod:`repro.obs.export` / :mod:`repro.obs.schema` — a Prometheus-style
    text exporter (gauges + latency histograms) for ``--metrics-out``, the
    stdlib HTTP ``/metrics`` scrape endpoint (:mod:`repro.obs.httpd`), and
    the event-schema validator the CI ``obs-smoke`` lane runs over emitted
    logs.
  * ``python -m repro.obs.replay`` — postmortem CLI joining the event JSONL
    with a series artifact into a per-incident chaos timeline.

The bench regression gate (``benchmarks/regress.py``) closes the loop:
committed ``experiments/bench/*.json`` baselines become per-metric budgets
(``benchmarks/obs_overhead.py`` pins the telemetry tax itself).
"""
from repro.obs.counters import (  # noqa: F401
    Counters,
    SiteCall,
    fold_ledger,
    ledger_stats,
    trace_site_calls,
)
from repro.obs.events import (  # noqa: F401
    Event,
    EventLog,
    detection_records,
    repair_records,
)
from repro.obs.export import gc_text, prometheus_text, write_metrics_out  # noqa: F401
from repro.obs.fallbacks import (  # noqa: F401
    fallback_summary,
    record_site_fallback,
    reset_site_fallbacks,
    site_fallback_total,
)
from repro.obs.host import install_gc_hook, span  # noqa: F401
from repro.obs.series import (  # noqa: F401
    SeriesBuffer,
    load_series,
    save_series,
)
_TRACE_EXPORTS = ("Span", "Trace", "build_traces", "fault_traces",
                  "request_traces", "write_spans", "validate_span",
                  "validate_spans_jsonl")


def __getattr__(name):
    # lazy: `python -m repro.obs.schema` / `-m repro.obs.trace` import this
    # package first, and an eager import here would double-import the CLI
    # module (runpy warns about exactly that)
    if name in ("validate_event", "validate_jsonl", "KIND_SCHEMAS"):
        from repro.obs import schema

        return getattr(schema, name)
    if name in _TRACE_EXPORTS:
        from repro.obs import trace

        return getattr(trace, name)
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
