"""Device-side FT counters: exact fault/recompute/dispatch accounting.

A :class:`Counters` pytree is folded beside the compiled step, never
inside it: after each step the caller runs :func:`fold_ledger` over the
step's call ledger, fault state and repair plan.  Counter values, fault
tables and repair plans are all traced leaves of the fold — swapping any of
them never retraces (asserted in tests/test_obs.py, the same contract
tests/test_ftcontext.py pins for the fault table).

Why a static call ledger instead of accumulating inside ``hyca_matmul``:
the model layer stacks execute under ``jax.lax.scan`` with the FTContext
*closed over* (see repro.models.lm), so a counter updated inside the scan
body would be an inner-scan tracer — reading it after the scan is a tracer
leak.  But every per-call statistic the counters need depends only on
(fault state, plan, array geometry, output shape) — never on activations —
and state/plan are loop-invariant across the layer scan.  So the call
profile is discovered ONCE per (model, shapes) by abstractly tracing the
step (:func:`trace_site_calls` — ``jax.eval_shape``, no FLOPs), with scan
multiplicities captured by observing ``lax.scan`` lengths during the trace;
at run time :func:`ledger_stats` computes each unique (site, shape)'s
element counts from the live state/plan leaves and scales by multiplicity.
The decode step is the same program with counters on or off, which makes
the counters-on == counters-off bit-exactness structural rather than at
the mercy of XLA fusion choices.

Counters are int32 (JAX x64 is disabled): at smoke scale (~1e5 elements per
step) they hold ~20k steps before ``total_elems`` wraps; the lifecycle
counts and per-site call counters are nowhere near the limit.  Fold to host
ints (``to_host``) before long-horizon aggregation.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.engine import HyCAConfig, RepairPlan, protected_view_stats

# element-count fields, accumulated from repro.core.engine.protected_view_stats
STAT_FIELDS = (
    "total_elems",
    "fault_elems",
    "recomputed_elems",
    "corrupted_elems",
    "pruned_elems",
    "fault_col_elems",
)


@dataclasses.dataclass(frozen=True)
class SiteCall:
    """One ledger entry: a protected-or-plain matmul call site with its
    flattened output shape and static multiplicity (scan length × expert
    batch × repeats).  Hashable — the ledger tuple is FTContext aux data."""

    site: str
    m: int              # flattened leading dim of the output view
    n: int              # output channels
    count: int          # static calls per step with this (site, shape)
    dispatch: str       # resolved dispatch: plain | twopass | fused
    protected: bool     # routed through the fault-aware engine path
    operand_dtype: str  # common dtype the operands are multiplied in


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Counters:
    """The counter pytree: int32 scalars + a per-site call dict.  All leaves
    traced; ``to_host`` folds to plain ints (and derived fractions) only at
    read time."""

    steps: jax.Array             # folded steps
    protected_calls: jax.Array   # matmul calls through the engine path
    plain_calls: jax.Array       # matmul calls lowered to plain jnp.matmul
    site_calls: dict             # {site: int32} — per-site dispatch counts
    total_elems: jax.Array
    fault_elems: jax.Array
    recomputed_elems: jax.Array  # DPPU-recomputed output elements
    corrupted_elems: jax.Array   # corruption that reached the output
    pruned_elems: jax.Array      # zeroed by the active RepairPlan
    fault_col_elems: jax.Array   # elements in channels on corrupting columns

    def tree_flatten(self):
        fields = tuple(f.name for f in dataclasses.fields(self))
        return tuple(getattr(self, name) for name in fields), fields

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(**dict(zip(aux, leaves)))

    @classmethod
    def zero(cls, sites: tuple[str, ...] | None = None) -> "Counters":
        if sites is None:
            from repro.core.ftcontext import SITES  # deferred: ftcontext imports obs lazily

            sites = SITES
        z = functools.partial(jnp.zeros, (), jnp.int32)
        return cls(
            steps=z(), protected_calls=z(), plain_calls=z(),
            site_calls={s: z() for s in sites},
            **{f: z() for f in STAT_FIELDS},
        )

    def to_host(self) -> dict:
        """Fold to a plain host dict: ints plus derived fractions.  The only
        device→host sync point — the accumulation itself never leaves jit."""
        d = {
            "steps": int(self.steps),
            "protected_calls": int(self.protected_calls),
            "plain_calls": int(self.plain_calls),
            "site_calls": {k: int(v) for k, v in sorted(self.site_calls.items())},
        }
        for f in STAT_FIELDS:
            d[f] = int(getattr(self, f))
        total = d["total_elems"]
        for f in ("fault_elems", "recomputed_elems", "corrupted_elems", "pruned_elems"):
            d[f.replace("_elems", "_fraction")] = d[f] / total if total else 0.0
        return d


# --------------------------------------------------------------------------- #
# ledger discovery
# --------------------------------------------------------------------------- #
_SCAN_STACK: list[int] = []


@contextlib.contextmanager
def _observe_scan_lengths():
    """While active, ``jax.lax.scan`` pushes its length onto a stack for the
    duration of the (single) body trace — nested scans multiply.  A body
    traces once however many iterations execute, so a recorder firing inside
    it must scale by the product of enclosing scan lengths.  Discovery-time
    only; the patch never runs under user jit."""
    orig = jax.lax.scan

    def scan(f, init, xs=None, length=None, **kwargs):
        if length is not None:
            n = int(length)
        else:
            leaves = jax.tree_util.tree_leaves(xs)
            n = int(leaves[0].shape[0]) if leaves else 0
        _SCAN_STACK.append(n)
        try:
            return orig(f, init, xs, length=length, **kwargs)
        finally:
            _SCAN_STACK.pop()

    jax.lax.scan = scan
    try:
        yield
    finally:
        jax.lax.scan = orig


def trace_site_calls(fn: Callable, ftc, *args, **kwargs) -> tuple[SiteCall, ...]:
    """Discover the static call ledger of ``fn(ftc, *args, **kwargs)``.

    Abstractly traces ``fn`` (``jax.eval_shape`` — shapes only, no compute)
    with the context's record hook armed; every ``ftc.matmul``/``einsum``
    call appends a (site, shape, dispatch, operand dtype) row scaled by the
    product of enclosing ``lax.scan`` lengths (the layer stacks trace their
    body once but execute it per layer).  Identical rows are merged with
    summed counts, so a 24-layer stack contributes one ledger entry per
    distinct (site, shape), not 24.

    ``args``/``kwargs`` may be concrete arrays or ShapeDtypeStructs; models
    that branch on ``cfg.unroll`` record correctly either way (unrolled
    bodies fire the hook once per layer with no scan multiplier).
    """
    raw: list[SiteCall] = []

    def record(*, site, m, n, count, dispatch, protected, operand_dtype):
        mult = int(count)
        for k in _SCAN_STACK:
            mult *= k
        raw.append(SiteCall(site, int(m), int(n), mult, dispatch, protected, operand_dtype))

    prev = ftc._obs_record
    ftc._obs_record = record
    try:
        with _observe_scan_lengths():
            jax.eval_shape(functools.partial(fn, ftc), *args, **kwargs)
    finally:
        ftc._obs_record = prev

    merged: dict[tuple, int] = {}
    for c in raw:
        key = (c.site, c.m, c.n, c.dispatch, c.protected, c.operand_dtype)
        merged[key] = merged.get(key, 0) + c.count
    return tuple(
        SiteCall(site=k[0], m=k[1], n=k[2], count=v, dispatch=k[3], protected=k[4],
                 operand_dtype=k[5])
        for k, v in sorted(merged.items(), key=lambda kv: kv[0])
    )


# --------------------------------------------------------------------------- #
# accumulation
# --------------------------------------------------------------------------- #
def _plan_for(plan, site: str):
    if plan is None or isinstance(plan, RepairPlan):
        return plan
    return plan.get(site)


def ledger_stats(ledger: tuple, counters: Counters, state, plan, hyca: HyCAConfig) -> Counters:
    """One step's accumulation: fold every ledger entry's element-exact
    engine stats — computed from the live (state, plan) leaves — into
    ``counters``.  Pure; :func:`fold_ledger` is its jitted form.  Shapes
    repeated across layers cost one stats computation (ledger rows are
    pre-merged)."""
    site_calls = dict(counters.site_calls)
    protected_calls = counters.protected_calls
    plain_calls = counters.plain_calls
    stats = {f: getattr(counters, f) for f in STAT_FIELDS}
    for call in ledger:
        if call.site in site_calls:
            site_calls[call.site] = site_calls[call.site] + jnp.int32(call.count)
        if call.protected:
            protected_calls = protected_calls + jnp.int32(call.count)
            s = protected_view_stats(state, hyca, _plan_for(plan, call.site), call.m, call.n)
            for f in STAT_FIELDS:
                stats[f] = stats[f] + s[f] * jnp.int32(call.count)
        else:
            plain_calls = plain_calls + jnp.int32(call.count)
            stats["total_elems"] = stats["total_elems"] + jnp.int32(call.m * call.n * call.count)
    return Counters(
        steps=counters.steps + 1,
        protected_calls=protected_calls,
        plain_calls=plain_calls,
        site_calls=site_calls,
        **stats,
    )


# the fold a caller runs after each step, outside it: ``ledger`` and ``hyca``
# are static, the counters, fault state and plan traced leaves
fold_ledger = jax.jit(ledger_stats, static_argnums=(0, 4))


def elems_on_coords(ledger: tuple, coords, rows: int, cols: int) -> int:
    """Host-side: output elements per step mapped onto a PE coordinate set
    (e.g. the manager's repaired set → DPPU recompute volume per step in the
    serving runtime, where the engine models repair by exclusion and its
    recompute counter is structurally zero)."""
    import numpy as np

    from repro.core.engine import _pe_multiplicity

    total = 0
    mask = np.zeros((rows, cols), bool)
    for r, c in coords:
        mask[r, c] = True
    for call in ledger:
        if not call.protected:
            continue
        mult = _pe_multiplicity(call.m, call.n, rows, cols)
        total += int((mult * mask).sum()) * call.count
    return total
