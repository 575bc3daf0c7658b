"""run_vfleet — the vectorized fleet engine: one jitted program per tick.

The legacy ``run_fleet`` loop steps every replica's FaultTolerantServer in
Python — O(replicas · steps) host iterations, each with its own jitted decode
call.  This engine replays the SAME fleet semantics as batched integer/bool
array programs with a leading replica axis, chunked through ``jax.lax.scan``:
1000 replicas × 10 000 steps is a handful of compiled calls, minutes on CPU.

What is vectorized, and how it stays *exact* (pinned by tests/test_vfleet.py
against ``run_fleet`` on identical FleetConfig + TrafficSpec):

  * **fault truth + scan pipeline** — per-replica (rows, cols) fault/stuck-at
    grids; every tick probes each replica's cursor row-block with the shared
    :func:`repro.core.scan.probe_operands` schedule and the same int32
    corruption math as ``FaultInjector.corrupted_probe``, so the hit/confirm
    trajectory is bit-identical.  Chaos injection draws its stuck-at
    signatures from :func:`repro.core.campaign.chaos_signatures` — the same
    grids the legacy loop injects.
  * **request flow** — the queue is an (age × class) count matrix, decode
    slots are per-class countdown histograms (a request of class k occupies
    a slot for ``prompt+gen-1`` steps and emits a token on the last ``gen``
    of them — exactly the scheduler's accounting when every prefilling slot
    feeds one prompt token a step, eos-free).  The proxy still models that
    token-level feed: the server matches it with ``prefill_chunk=1`` or a
    model without a chunk, not the default chunk of 256 prompt tokens a
    step, which shortens prefill; ``run_vfleet`` refuses a server that
    would feed a longer chunk.  Arrivals come from the shared
    :func:`~repro.serving.traffic.sample_trace`; least-loaded routing with
    lowest-index tie-break is an exact water-fill (binary-searched level +
    lowest-index extras).  SLA expiry reproduces ``pop_ready`` exactly for
    any class mix: an expired request is dropped iff the admission walk
    reaches it before free capacity runs out (a masked cumsum over the
    age-desc/class-asc pop order).
  * **capacity / retire / spares** — surviving-column prefix, effective
    slots, the retire threshold, and pool- vs region-policy spare grants are
    integer lax ops; grants follow replica index order like the legacy loop.

Zero recompilations across fault-rate points: the rate is a traced scalar
into ``jax.random.poisson``, fault grids and the chaos map are fixed-shape
leaves, and the step geometry (:class:`_Geom`) is the only static argument —
a fault-rate sweep reuses one compiled program (asserted via ``_TRACES``,
the tests/test_ftcontext.py idiom).

Autoscaling runs as a host hook between jitted chunks (decision cadence =
``FleetConfig.chunk_steps``): an :class:`AutoscaleSpec` scales the
provisioned replica set between min/max on mean queue depth, emitting
``fleet.autoscale`` events through the repro.obs event log.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.campaign import chaos_maps, chaos_signatures
from repro.core.engine import empty_fault_state
from repro.core.scan import probe_operands
from repro.obs.series import SeriesBuffer
from repro.runtime.elastic import initial_spares
from repro.serving.fleet import FleetConfig
from repro.serving.server import prompt_chunk
from repro.serving.traffic import sample_trace

_INF = np.int32(1 << 30)

# one entry appended per trace of the chunk program — the no-recompile
# witness (tests assert its length is flat across a fault-rate sweep)
_TRACES: list = []


@dataclasses.dataclass(frozen=True)
class AutoscaleSpec:
    """Queue-depth autoscaling policy (host hook between jitted chunks)."""

    min_replicas: int = 1
    max_replicas: int = 8
    high_queue: float = 8.0    # mean queued requests / live replica -> scale out
    low_queue: float = 0.5     # -> scale in (idle replicas only)
    step_size: int = 1


@dataclasses.dataclass(frozen=True)
class _Geom:
    """Static tick geometry — the ONLY static argument of the chunk program
    (hashable; every workload/fault knob is a traced leaf)."""

    n_replicas: int            # R — replica-axis size (max_replicas w/ autoscale)
    rows: int
    cols: int
    block: int                 # scan_block (rows probed per tick)
    window: int                # probe window
    confirm_hits: int
    capacity: int              # DPPU repair capacity (HyCAConfig.capacity)
    n_slots: int
    thresh: int                # retire iff surviving_cols <= thresh
    n_regions: int             # spare-pool regions (1 under "pool")
    policy: str                # "pool" | "region"
    age_bins: int              # A — queue-age histogram depth
    slot_bins: int             # C — slot countdown bins (max service + 1)
    # per-request-class statics (from the TrafficSpec quantization)
    service: tuple[int, ...]   # prompt+gen-1 slot-occupancy steps
    gen: tuple[int, ...]       # decode tokens per request
    wait: tuple[int, ...]      # max queue age before SLA expiry (age_bins = none)
    has_sla: tuple[bool, ...]


def _retire_threshold(cols: int, retire_fraction: float) -> int:
    """Largest surviving-column count that still retires — computed with the
    SAME float comparison the legacy loop applies per replica
    (``capacity_fraction <= retire_fraction``), so both engines retire on
    exactly the same integer boundary."""
    return max(s for s in range(cols + 1) if s / cols <= retire_fraction)


def _water_fill(load, live, n):
    """Distribute ``n`` arrivals greedily least-loaded, lowest index on ties
    — the exact per-request ``min()`` routing of the legacy loop, closed
    form: binary-search the final load level L, fill everyone to L-1, then
    one extra each to the lowest-index replicas still at L-1."""
    l = jnp.where(live, load, _INF).astype(jnp.int32)
    minl = jnp.min(l)

    def fill_at(level):
        return jnp.where(live, jnp.clip(level - l, 0), 0).sum()

    def body(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        ge = fill_at(mid) >= n
        return jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi)

    lo, hi = jax.lax.fori_loop(
        0, 32, body, (minl, minl + jnp.maximum(n, 1).astype(jnp.int32))
    )
    level = hi
    base = jnp.where(live, jnp.clip(level - 1 - l, 0), 0)
    extras = n - base.sum()
    eligible = live & (l <= level - 1)
    first = jnp.cumsum(eligible) - eligible.astype(jnp.int32)  # exclusive
    extra = (eligible & (first < extras)).astype(jnp.int32)
    return jnp.where(n > 0, base + extra, 0).astype(jnp.int32)


def _tick(geom: _Geom, state: dict, params: dict, t):
    R, rows, cols = geom.n_replicas, geom.rows, geom.cols
    K, A, C = len(geom.service), geom.age_bins, geom.slot_bins
    live = state["provisioned"] & ~state["dead"]
    fault, sbit, sval = state["fault"], state["sbit"], state["sval"]
    queue, slots = state["queue"], state["slots"]
    counters = dict(state["counters"])

    # 1. chaos: merge the sampled maps into live replicas' truth at chaos_at
    hit = (t == params["chaos_at"]) & live[:, None, None]
    inj = params["chaos_mask"] & ~fault & hit
    sbit = jnp.where(inj, params["chaos_bits"], sbit)
    sval = jnp.where(inj, params["chaos_vals"], sval)
    fault = fault | inj
    counters["chaos_injected"] += inj.sum()

    # 2. arrivals: per-class sequential water-fill (trace emits classes in
    # ascending order; the legacy loop routes in that same order)
    counts_t = params["counts"][t]
    any_live = live.any()
    load = queue.sum((1, 2)) + slots.sum((1, 2))
    for k in range(K):
        n_k = counts_t[k]
        counters["requests_unrouted"] += jnp.where(any_live, 0, n_k)
        new_k = _water_fill(load, live, jnp.where(any_live, n_k, 0))
        queue = queue.at[:, 0, k].add(new_k)
        load = load + new_k

    # 3. wearout: Poisson new faults per live replica, uniform over healthy
    # PEs (exact top-up placement); the rate is a TRACED scalar, so a
    # fault-rate sweep reuses this compiled program
    key = jax.random.fold_in(state["key"], t)
    k_n, k_place = jax.random.split(key)
    n_new = jax.random.poisson(k_n, params["fault_rate"], (R,)).astype(jnp.int32)
    pri = jax.random.uniform(k_place, (R, rows * cols))
    pri = jnp.where(fault.reshape(R, -1), 2.0, pri)
    rank = jnp.argsort(jnp.argsort(pri, axis=1), axis=1)
    new = (rank < n_new[:, None]) & (pri < 1.5) & live[:, None]
    new = new.reshape(R, rows, cols)
    sbit = jnp.where(new, params["wear_bits"], sbit)
    sval = jnp.where(new, params["wear_vals"], sval)
    fault = fault | new

    # 4. scan: probe each live replica's cursor row-block against the shared
    # per-sweep operand schedule (int32 math identical to corrupted_probe)
    sweep_i = jnp.clip(state["sweep"], 0, params["px_sched"].shape[0] - 1)
    px_s = params["px_sched"][sweep_i]                     # (R, rows, W)
    pw_s = params["pw_sched"][sweep_i]                     # (R, W, cols)
    row0 = state["cursor"] * geom.block
    row_idx = row0[:, None] + jnp.arange(geom.block)[None, :]
    r_ix = jnp.arange(R)[:, None]
    px_b = px_s[r_ix, row_idx]                             # (R, block, W)
    clean = jnp.einsum(
        "rbk,rkc->rbc", px_b.astype(jnp.int32), pw_s.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )
    fm_b, sb_b, sv_b = (a[r_ix, row_idx] for a in (fault, sbit, sval))

    def corrupt(out):
        mask = jnp.left_shift(jnp.int32(1), sb_b)
        bad = jnp.where(sv_b > 0, out | mask, out & ~mask)
        return jnp.where(fm_b, bad, out)

    flags = (corrupt(clean) != clean) | (corrupt(-clean) != -clean)
    hits_b = state["hits"][r_ix, row_idx]
    countable = flags & (hits_b < geom.confirm_hits) & live[:, None, None]
    hits = state["hits"].at[
        r_ix[:, :, None], row_idx[:, :, None], jnp.arange(cols)[None, None, :]
    ].add(countable.astype(jnp.int32))
    last = state["cursor"] == (rows // geom.block) - 1
    cursor = jnp.where(live, jnp.where(last, 0, state["cursor"] + 1), state["cursor"])
    sweep = state["sweep"] + (last & live).astype(jnp.int32)

    # 5. capacity: confirmed overflow retires the column suffix (leftmost-
    # first repair priority), effective slots shrink proportionally
    conf = hits >= geom.confirm_hits
    nconf = conf.sum((1, 2))
    csum = jnp.cumsum(conf.sum(1), axis=1)                 # (R, cols)
    surv = jnp.where(
        nconf <= geom.capacity, cols,
        jnp.argmax(csum >= geom.capacity + 1, axis=1).astype(jnp.int32),
    )
    eff = jnp.where(
        surv >= cols, geom.n_slots,
        jnp.where(surv == 0, 0,
                  jnp.maximum(1, (geom.n_slots * surv) // cols)),
    )
    eff = jnp.where(live, eff, 0).astype(jnp.int32)

    # 6. admission: walk the FIFO in pop order (age-desc, class-asc within an
    # age — the submit order).  ``pop_ready`` drops an SLA-expired request
    # only when the walk *reaches* it with free capacity left, and the walk
    # stops at the admission filling the last free slot — expired requests
    # parked behind a fresher admissible one stay queued.  "Reached" is
    # exactly `admissible-before-me < free`, so one masked cumsum reproduces
    # the legacy per-item loop for any class mix.
    active = slots.sum((1, 2))
    free = jnp.clip(eff - active, 0)
    q_pop = queue[:, ::-1, :].reshape(R, A * K)             # pop order
    pop_age = np.repeat(np.arange(A)[::-1], K)
    pop_cls = np.tile(np.arange(K), A)
    exp_mask = jnp.asarray(pop_age > np.asarray(geom.wait)[pop_cls])
    adm = jnp.where(exp_mask[None, :], 0, q_pop)            # admissible counts
    excl = jnp.cumsum(adm, axis=1) - adm                    # admissible before b
    reached = excl < free[:, None]
    drop = jnp.where(exp_mask[None, :] & reached, q_pop, 0)
    take = jnp.clip(free[:, None] - excl, 0, adm)
    queue = (q_pop - drop - take).reshape(R, A, K)[:, ::-1, :]
    drop_k = drop.reshape(R, A, K).sum((0, 1))              # per class
    counters["requests_expired"] += drop_k.sum()
    counters["slo_miss"] += sum(
        (drop_k[k] for k in range(K) if geom.has_sla[k]), jnp.int32(0)
    )
    take_ak = take.reshape(R, A, K)[:, ::-1, :]             # (R, age, class)
    counters["wait_hist"] += take_ak.sum(0).T.astype(jnp.int32)   # (K, A)
    for k in range(K):
        slots = slots.at[:, k, geom.service[k]].add(take_ak[:, :, k].sum(1))

    # 7. decode proxy: a slot at countdown c emits a token iff c <= gen
    # (the last `gen` occupancy steps — token-level chunked prefill
    # accounting), completes at c == 1.  All completions are on time: SLA
    # admission guarantees finish <= deadline (queue.pop_ready's invariant).
    c_ix = jnp.arange(C)
    tokens_r = jnp.zeros(R, jnp.int32)
    for k in range(K):
        tok_mask = ((c_ix >= 1) & (c_ix <= geom.gen[k])).astype(jnp.int32)
        tokens_r = tokens_r + (slots[:, k, :] * tok_mask).sum(1)
        done_k = slots[:, k, 1]
        counters["requests_completed"] += done_k.sum()
        if geom.has_sla[k]:
            counters["slo_met"] += done_k.sum()
    counters["tokens_total"] += tokens_r.sum()
    unconfirmed = (fault & (hits < geom.confirm_hits)).any((1, 2))
    counters["clean_tokens"] += jnp.where(~unconfirmed, tokens_r, 0).sum()

    # series: one per-replica row per tick, captured at the SAME pipeline
    # point the legacy server records its StepRecord — post-scan, post-
    # admission, pre-commit/aging/retire (parity-pinned in test_obs_trace);
    # pure leaf updates, so series-on reuses nothing of and changes nothing
    # in the report math
    series = state.get("series")
    if series is not None:
        spsw = rows // geom.block
        probes = sweep * spsw + cursor
        series = series.record({
            "tokens": tokens_r,
            "queue_depth": queue.sum((1, 2)),
            "active": slots.sum((1, 2)),
            "confirmed": nconf,
            "effective_slots": eff,
            "true_faults": fault.sum((1, 2)).astype(jnp.int32),
            "surviving_cols": surv,
            "scan_coverage": jnp.minimum(1.0, probes.astype(jnp.float32) / spsw),
            "capacity_fraction": surv.astype(jnp.float32) / cols,
            "quality_fraction": jnp.ones(R, jnp.float32),
            "live": live,
        })

    slots = jnp.concatenate(                                # countdown shift
        [jnp.zeros((R, K, 1), jnp.int32), slots[:, :, 2:],
         jnp.zeros((R, K, 1), jnp.int32)], axis=2,
    )

    # 8. queue aging (post-step, so age == steps waited; clamps at A-1)
    queue = jnp.concatenate(
        [jnp.zeros((R, 1, K), jnp.int32), queue[:, : A - 2, :],
         (queue[:, A - 2, :] + queue[:, A - 1, :])[:, None, :]], axis=1,
    )

    # 9. retire + spare replacement (post-step check, replica index order)
    dying = live & (surv <= geom.thresh)
    active_post = slots.sum((1, 2))
    counters["retirements"] += dying.sum()
    counters["requests_lost"] += jnp.where(dying, active_post, 0).sum()
    for k in range(K):
        if geom.has_sla[k]:
            counters["slo_miss"] += jnp.where(
                dying, slots[:, k, :].sum(1), 0
            ).sum()
    spares = state["spares"]
    if geom.policy == "pool":
        order = jnp.cumsum(dying)
        grant = dying & (order <= spares[0])
        spares = spares.at[0].add(-grant.sum())
    else:
        grant = jnp.zeros(R, bool)
        for rg in range(geom.n_regions):
            in_rg = dying & (params["region"] == rg)
            g = in_rg & (jnp.cumsum(in_rg) <= spares[rg])
            spares = spares.at[rg].add(-g.sum())
            grant = grant | g
    counters["replacements"] += grant.sum()
    # granted: a fresh server takes over — clean array, reset scan state,
    # queued work survives (resubmitted).  Not granted: the replica is dead,
    # in-flight AND queued work is lost.
    g3 = grant[:, None, None]
    fault = jnp.where(g3, False, fault)
    sbit = jnp.where(g3, params["wear_bits"], sbit)
    sval = jnp.where(g3, params["wear_vals"], sval)
    hits = jnp.where(g3, 0, hits)
    cursor = jnp.where(grant, 0, cursor)
    sweep = jnp.where(grant, 0, sweep)
    unlucky = dying & ~grant
    stranded_q = jnp.where(unlucky, queue.sum((1, 2)), 0)
    counters["requests_lost"] += stranded_q.sum()
    for k in range(K):
        if geom.has_sla[k]:
            counters["slo_miss"] += jnp.where(
                unlucky, queue[:, :, k].sum(1), 0
            ).sum()
    queue = jnp.where(unlucky[:, None, None], 0, queue)
    slots = jnp.where(dying[:, None, None], 0, slots)
    dead = state["dead"] | unlucky

    alive = (state["provisioned"] & ~dead).sum().astype(jnp.int32)
    new_state = dict(
        state, fault=fault, sbit=sbit, sval=sval, hits=hits, cursor=cursor,
        sweep=sweep, queue=queue, slots=slots, spares=spares, dead=dead,
        counters=counters,
    )
    if series is not None:
        new_state["series"] = series
    ys = {
        "tokens": tokens_r.sum().astype(jnp.int32),
        "alive": alive,
        "queue_depth": queue.sum().astype(jnp.int32),
        "active": slots.sum().astype(jnp.int32),
    }
    return new_state, ys


@functools.partial(jax.jit, static_argnames=("geom",))
def _chunk(geom: _Geom, state: dict, params: dict, ts):
    _TRACES.append(ts.shape)

    def body(st, t):
        return _tick(geom, st, params, t)

    return jax.lax.scan(body, state, ts)


def _weighted_percentile(values: np.ndarray, weights: np.ndarray, q: float):
    w = np.asarray(weights, np.float64)
    if w.sum() <= 0:
        return None
    order = np.argsort(values)
    v, w = np.asarray(values, np.float64)[order], w[order]
    cdf = np.cumsum(w) / w.sum()
    return float(v[np.searchsorted(cdf, q / 100.0, side="left")])


def batched_confirmed_states(hits, sbit, sval, *, confirm_hits: int):
    """Fold the engine's per-replica confirmed grids into ONE batched
    :class:`~repro.core.engine.FaultState` (leading replica axis, leftmost-
    sorted entries — the ``campaign.batched_fault_states`` layout), ready for
    ``vmap`` over protected forward passes or cross-validation against the
    legacy managers' ``confirmed_state``."""
    hits = jnp.asarray(hits)
    n, rows, cols = hits.shape
    empty = empty_fault_state(rows * cols)
    pack = jax.vmap(lambda m, b, v: empty.merge(m, stuck_bit=b, stuck_val=v))
    return pack(hits >= confirm_hits, jnp.asarray(sbit), jnp.asarray(sval))


# --------------------------------------------------------------------------- #
# driver
# --------------------------------------------------------------------------- #
def _build(cfg: FleetConfig):
    s = cfg.server
    if cfg.traffic is None:
        raise ValueError("run_vfleet needs FleetConfig.traffic (a TrafficSpec)")
    if s.mode != "protected":
        raise ValueError("run_vfleet models the protected serving mode only")
    if s.repair != "none":
        raise ValueError("run_vfleet does not model repro.repair remediation")
    if s.rows % s.scan_block:
        raise ValueError("scan_block must divide rows")
    lm = (get_smoke_config if s.smoke else get_config)(s.arch)
    if prompt_chunk(s, lm) > 1:
        raise ValueError(
            "run_vfleet models one prompt token a step per prefilling slot, "
            f"but {s.arch} serves a prompt chunk of up to {prompt_chunk(s, lm)} "
            "tokens a step: set ServerConfig.prefill_chunk=1 to compare the engines")

    auto = cfg.autoscale
    R = max(cfg.n_replicas, auto.max_replicas) if auto is not None else cfg.n_replicas
    trace = sample_trace(cfg.traffic, cfg.steps, cfg.n_replicas, s.smax)
    classes = trace.classes
    service = tuple(c.service_steps for c in classes)
    gen = tuple(c.max_new_tokens for c in classes)
    steps_per_sweep = s.rows // s.scan_block
    A = max(cfg.age_bins,
            max((c.wait_budget + 2 for c in classes if c.wait_budget is not None),
                default=0))
    wait = tuple(A if c.wait_budget is None else c.wait_budget for c in classes)
    n_regions_eff = cfg.n_regions if cfg.spare_policy == "region" else 1
    geom = _Geom(
        n_replicas=R, rows=s.rows, cols=s.cols, block=s.scan_block,
        window=8, confirm_hits=s.confirm_hits,
        capacity=s.hyca().capacity, n_slots=s.n_slots,
        thresh=_retire_threshold(s.cols, cfg.retire_fraction),
        n_regions=n_regions_eff, policy=cfg.spare_policy,
        age_bins=A, slot_bins=max(service) + 1,
        service=service, gen=gen, wait=wait,
        has_sla=tuple(c.sla_steps is not None for c in classes),
    )

    n_sweeps = cfg.steps // steps_per_sweep + 2
    ops = [probe_operands(s.rows, s.cols, sw, geom.window) for sw in range(n_sweeps)]
    wr = np.random.default_rng([cfg.seed, 0x3EA4])
    if cfg.chaos is not None:
        cmask = np.zeros((R, s.rows, s.cols), bool)
        maps = chaos_maps(cfg.chaos, cfg.n_replicas, s.rows, s.cols)
        for i in cfg.chaos.targets(cfg.n_replicas):
            cmask[i] = maps[i]
        cbits, cvals = chaos_signatures(cfg.chaos, cfg.n_replicas, s.rows, s.cols)
        cbits = np.concatenate([cbits, np.zeros((R - cfg.n_replicas, s.rows, s.cols), np.int32)])
        cvals = np.concatenate([cvals, np.zeros((R - cfg.n_replicas, s.rows, s.cols), np.int32)])
        chaos_at = cfg.chaos.at_step
    else:
        cmask = np.zeros((R, s.rows, s.cols), bool)
        cbits = np.zeros((R, s.rows, s.cols), np.int32)
        cvals = np.zeros((R, s.rows, s.cols), np.int32)
        chaos_at = -1
    params = {
        "counts": jnp.asarray(trace.counts),
        "fault_rate": jnp.float32(cfg.fault_rate),
        "chaos_at": jnp.int32(chaos_at),
        "chaos_mask": jnp.asarray(cmask),
        "chaos_bits": jnp.asarray(cbits),
        "chaos_vals": jnp.asarray(cvals),
        "wear_bits": jnp.asarray(
            wr.integers(0, 32, size=(R, s.rows, s.cols), dtype=np.int32)),
        "wear_vals": jnp.asarray(
            wr.integers(0, 2, size=(R, s.rows, s.cols), dtype=np.int32)),
        "px_sched": jnp.asarray(np.stack([px for px, _ in ops])),
        "pw_sched": jnp.asarray(np.stack([pw for _, pw in ops])),
        "region": jnp.asarray(np.arange(R, dtype=np.int32) % max(cfg.n_regions, 1)),
    }
    zeros_i = jnp.int32(0)
    counters = {k: zeros_i for k in (
        "tokens_total", "clean_tokens", "chaos_injected", "retirements",
        "replacements", "requests_lost", "requests_unrouted",
        "requests_completed", "requests_expired", "slo_met", "slo_miss",
    )}
    counters["wait_hist"] = jnp.zeros((len(classes), A), jnp.int32)
    state = {
        "fault": jnp.zeros((R, s.rows, s.cols), bool),
        "sbit": params["wear_bits"],
        "sval": params["wear_vals"],
        "hits": jnp.zeros((R, s.rows, s.cols), jnp.int32),
        "cursor": jnp.zeros(R, jnp.int32),
        "sweep": jnp.zeros(R, jnp.int32),
        "queue": jnp.zeros((R, A, len(classes)), jnp.int32),
        "slots": jnp.zeros((R, len(classes), geom.slot_bins), jnp.int32),
        "provisioned": jnp.asarray(np.arange(R) < cfg.n_replicas),
        "dead": jnp.zeros(R, bool),
        "spares": jnp.asarray(
            initial_spares(cfg.n_spares, cfg.spare_policy, cfg.n_regions),
            jnp.int32),
        "key": jax.random.key(cfg.seed),
        "counters": counters,
    }
    if cfg.series:
        # ring capacity = one chunk: the driver harvests at every chunk
        # boundary, so no row is ever overwritten before it is read
        cap = min(max(1, cfg.chunk_steps), cfg.steps)
        i32, f32 = jnp.int32, jnp.float32
        state["series"] = SeriesBuffer.create(cap, {
            "tokens": ((R,), i32), "queue_depth": ((R,), i32),
            "active": ((R,), i32), "confirmed": ((R,), i32),
            "effective_slots": ((R,), i32), "true_faults": ((R,), i32),
            "surviving_cols": ((R,), i32),
            "scan_coverage": ((R,), f32), "capacity_fraction": ((R,), f32),
            "quality_fraction": ((R,), f32), "live": ((R,), jnp.bool_),
        })
    return geom, params, state, trace


def _autoscale(cfg: FleetConfig, geom: _Geom, state: dict, step: int, log):
    """Host-side scaling decision at chunk boundaries."""
    auto = cfg.autoscale
    prov = np.asarray(state["provisioned"]).copy()
    dead = np.asarray(state["dead"])
    live = prov & ~dead
    n_live = int(live.sum())
    if n_live == 0:
        return state
    qd = np.asarray(state["queue"]).sum((1, 2))
    busy = qd + np.asarray(state["slots"]).sum((1, 2))
    q_mean = float(qd[live].sum() / n_live)
    action, n = None, 0
    if q_mean >= auto.high_queue and n_live < auto.max_replicas:
        idle_slots = np.nonzero(~prov & ~dead)[0]
        n = min(auto.step_size, auto.max_replicas - n_live, len(idle_slots))
        if n > 0:
            prov[idle_slots[:n]] = True
            action = "scale_out"
    elif q_mean <= auto.low_queue and n_live > auto.min_replicas:
        idle = np.nonzero(live & (busy == 0))[0]
        n = min(auto.step_size, n_live - auto.min_replicas, len(idle))
        if n > 0:
            prov[idle[-n:]] = False                         # drop highest index
            action = "scale_in"
    if action is None:
        return state
    if log is not None:
        log.step = step
        log.emit(
            "fleet.autoscale", action=action, n=int(n),
            queue_depth_mean=q_mean,
            capacity_mean=float(busy[live].mean()),
            live=int((prov & ~dead).sum()),
        )
    return dict(state, provisioned=jnp.asarray(prov))


def run_vfleet(cfg: FleetConfig, *, log=None) -> dict:
    """Vectorized fleet campaign: same FleetConfig + TrafficSpec, same report
    keys and — on the shared-semantics subset (goodput, retirements, spare
    consumption, SLO counts…) — the same VALUES as ``run_fleet`` (see
    tests/test_vfleet.py).  ``log``: optional repro.obs EventLog receiving
    ``fleet.autoscale`` events.  Adds ``sim_wall_s`` (wall time of the
    simulation loop, first-call compilation included) and latency
    percentiles derived from the admission-wait histogram."""
    geom, params, state, trace = _build(cfg)
    chunk = max(1, cfg.chunk_steps)
    ys_all = []
    series_rows: list[dict] = []
    harvested = 0
    t0 = time.perf_counter()
    step = 0
    while step < cfg.steps:
        n = min(chunk, cfg.steps - step)
        ts = jnp.arange(step, step + n, dtype=jnp.int32)
        state, ys = _chunk(geom, state, params, ts)
        ys_all.append(jax.tree.map(np.asarray, ys))
        step += n
        if "series" in state:
            # the one device→host sync of the telemetry path: drain the ring
            # at the chunk boundary, before its rows can be overwritten
            series_rows.append(state["series"].harvest(start=harvested))
            harvested = state["series"].written
        if cfg.autoscale is not None and step < cfg.steps:
            state = _autoscale(cfg, geom, state, step, log)
    wall = time.perf_counter() - t0

    c = {k: (int(v) if np.ndim(v) == 0 else np.asarray(v))
         for k, v in jax.tree.map(np.asarray, state["counters"]).items()}
    tok = np.concatenate([y["tokens"] for y in ys_all])
    alive = np.concatenate([y["alive"] for y in ys_all])
    qdepth = np.concatenate([y["queue_depth"] for y in ys_all])
    hist = c["wait_hist"]                                   # (K, A)
    waits = np.tile(np.arange(geom.age_bins), len(geom.service))
    e2e = np.concatenate([
        np.arange(geom.age_bins) + geom.service[k] - 1
        for k in range(len(geom.service))
    ])
    w = hist.reshape(-1)
    slo_requests = c["slo_met"] + c["slo_miss"]
    spares_rem = int(np.asarray(state["spares"]).sum())
    report = {
        "engine": "vfleet",
        "steps": cfg.steps,
        "fault_rate": cfg.fault_rate,
        "spare_policy": cfg.spare_policy,
        "goodput_tokens": int(tok.sum()),
        "goodput_per_step": float(tok.mean()) if tok.size else 0.0,
        "clean_tokens": c["clean_tokens"],
        "alive_final": int(alive[-1]) if alive.size else cfg.n_replicas,
        "alive_mean": float(alive.mean()) if alive.size else float(cfg.n_replicas),
        "queue_depth_mean": float(qdepth.mean()) if qdepth.size else 0.0,
        "chaos_injected": c["chaos_injected"],
        "chaos_at_step": cfg.chaos.at_step if cfg.chaos is not None else None,
        "retirements": c["retirements"],
        "replacements": c["replacements"],
        "requests_total": trace.total_requests,
        "requests_completed": c["requests_completed"],
        "requests_expired": c["requests_expired"],
        "requests_lost": c["requests_lost"],
        "requests_unrouted": c["requests_unrouted"],
        "slo_requests": slo_requests,
        "slo_met": c["slo_met"],
        "slo_misses": c["slo_miss"],
        "slo_attainment": (c["slo_met"] / slo_requests) if slo_requests else None,
        "slo_attainment_defined": bool(slo_requests),
        "spares_remaining": spares_rem,
        "latency_wait_p50": _weighted_percentile(waits, w, 50),
        "latency_wait_p99": _weighted_percentile(waits, w, 99),
        "latency_e2e_p50": _weighted_percentile(e2e, w, 50),
        "latency_e2e_p99": _weighted_percentile(e2e, w, 99),
        "sim_wall_s": wall,
        "n_replicas": cfg.n_replicas,
    }
    if series_rows:
        # (steps, R) per channel — time-major, replica axis preserved
        report["series"] = {
            k: np.concatenate([rows[k] for rows in series_rows])
            for k in series_rows[0]
        }
    return report
