"""Host-side registry of fused-dispatch fallbacks.

A fallback decision (``dispatch="fused"`` site lowering through the twopass
engine instead of the kernel) is *trace-time static* — it depends only on
shapes, dtypes, and the spec string, never on array values — so it has no
place in the device-side :class:`~repro.obs.counters.Counters`, which fold
per-step values beside the compiled step.  This
module records it at trace time instead: a process-wide counter keyed on
``(site, reason)`` (the Prometheus ``site_fallback_total{site,reason}``
series) plus a one-time ``warnings.warn`` per key so a silently-degraded
fused context is visible the first time it traces.

Because the record happens while tracing, a jit cache hit will not re-count
— the numbers answer "which (site, reason) pairs fell back", not "how many
times did the compiled program run" (the device counters answer that).
"""
from __future__ import annotations

import warnings

_FALLBACKS: dict[tuple[str, str], int] = {}
_WARNED: set[tuple[str, str]] = set()


def record_site_fallback(site: str, reason: str) -> None:
    """Count a fused→twopass lowering for ``site`` and warn once per
    (site, reason).  Called from FTContext at trace time."""
    key = (site, reason)
    _FALLBACKS[key] = _FALLBACKS.get(key, 0) + 1
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(
            f"FTContext dispatch='fused' fell back to twopass at site "
            f"'{site}' ({reason}); the protected path is paying the "
            f"two-pass tax here — see docs/kernels.md",
            RuntimeWarning,
            stacklevel=3,
        )


def site_fallback_total() -> dict[tuple[str, str], int]:
    """Snapshot of the ``site_fallback_total{site,reason}`` counters."""
    return dict(_FALLBACKS)


def fallback_summary() -> dict[str, int]:
    """Flat ``{"site/reason": count}`` view for the metrics exporter."""
    return {f"{site}/{reason}": n for (site, reason), n in sorted(_FALLBACKS.items())}


def reset_site_fallbacks() -> None:
    """Clear counters and the warned-once set (tests / bench isolation)."""
    _FALLBACKS.clear()
    _WARNED.clear()
