"""Fault-tolerant continuous-batching inference server.

The step loop wires the scheduler and fault manager around one jitted decode:

    every step:
      1. hardware wearout      — the injector may grow the fault map;
      2. one scan step         — the fault manager probes one row-block of
                                 PEs (``scan_block`` rows × all columns, the
                                 batched ScanEngine — IV-D with p parallel
                                 DPPU groups);
      3. capacity update       — confirmed faults beyond DPPU capacity shrink
                                 the surviving column prefix, and with it the
                                 number of decode slots admission may fill;
      4. admission             — freed slots take queued requests (their KV
                                 cache slots are zeroed in place);
      5. batched decode        — ONE decode_step over all slots, plus the
                                 step's prompt chunk of up to
                                 ``prefill_chunk`` tokens of one prefilling
                                 slot (attention families; a step with no
                                 prefilling slot runs the token-only
                                 program, no chunk rows); every weight
                                 matmul of the protected layer fraction
                                 (attention projections, FFN, experts, LM
                                 head) runs through the FTContext dispatcher
                                 on the HyCA virtual array, once for the
                                 slots' rows and the chunk's together,
                                 corrupted by whatever faults the runtime
                                 has not yet confirmed;
      6. commit                — prefill slots advance the prompt tokens
                                 they fed, decode slots append the sampled
                                 token, finished requests free their slots.

Mode is a *data* difference, not a code difference — all three modes run the
identical compiled step, fed different fault views:

  * ``off``          — empty fault state (the reference run);
  * ``protected``    — truth minus confirmed (confirmed faults are DPPU-
                       repaired or column-remapped, hence clean);
  * ``unprotected``  — the full truth (no detection, no repair: Fig. 2's
                       accuracy collapse, here a goodput collapse).

That makes the paper's headline claim testable end-to-end: with every fault
confirmed (BIST) and #faults ≤ capacity, ``protected`` serves tokens
bit-exact with ``off``.

Past DPPU capacity, ``ServerConfig.repair`` enables the repro.repair
remediation (docs/repair.md): over-capacity confirmed faults become REMAPPED
— they stay in the served fault state while the active RepairPlan (a traced
leaf next to the fault table) prunes salience-chosen channels onto them —
and ``repair="retrain"`` additionally fine-tunes this replica's params on a
budget and swaps them into the running step.  Both swaps are leaf-only:
the compiled step never retraces.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.engine import FaultState, HyCAConfig, empty_fault_state, identity_plan
from repro.core.ftcontext import ProtectPolicy, build_ftcontext
from repro.core.redundancy import DPPUConfig
from repro.models.lm import CHUNK_FAMILIES, LMConfig, decode_step, init_cache, init_params
from repro.obs.counters import Counters, fold_ledger
from repro.obs.events import EventLog
from repro.obs.host import install_gc_hook, span
from repro.repair.plan import remap_plan
from repro.repair.remap import weight_salience
from repro.serving.fault_manager import FaultInjector, FaultManager, FaultManagerConfig
from repro.serving.metrics import ServingMetrics, StepRecord
from repro.serving.queue import CompletedRequest, Request, RequestQueue
from repro.serving.scheduler import ContinuousBatchingScheduler


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    arch: str = "qwen1.5-0.5b"
    # model size: the registry's reduced smoke config (CPU tests, CI) or,
    # with smoke=False, the arch's published widths (configs/<arch>.config)
    smoke: bool = True
    n_slots: int = 4
    smax: int = 96                 # KV capacity per slot
    # prompt tokens of one prefilling slot that ride in each decode step
    # (attention families; capped at smax).  The chunk's rows share every
    # weight matmul with the slots' rows, so a decode step bound by weight
    # reads takes them at little cost.
    prefill_chunk: int = 256
    mode: str = "protected"        # off | protected | unprotected
    rows: int = 8                  # virtual PE array (serving-scale)
    cols: int = 8
    dppu_size: int = 4             # DPPU capacity ~= repairable faults
    protect_fraction: float = 1.0  # fraction of main-stack layers on the array
    dispatch: str = "twopass"      # twopass | fused (FTContext kernel dispatch)
    scan_block: int = 1            # PE-grid rows probed per scan step (ScanEngine)
    confirm_hits: int = 2
    bist: bool = True              # power-on: confirm the factory fault map
    boot_scan: bool = False        # probe-based power-on sweep instead
    fault_rate: float = 0.0        # Poisson new faults per step (wearout)
    # model-side remediation past DPPU capacity (repro.repair, docs/repair.md):
    #   none    — overflow faults RETIRE columns (throughput cliff, PR-1..4)
    #   remap   — overflow columns are REMAPPED: a salience-chosen pruned
    #             residue class lands on them; the replica keeps full slots
    #   retrain — remap + a budgeted fault-aware fine-tune of this replica's
    #             params (the repaired params are swapped into the running
    #             server — the background repair hook)
    repair: str = "none"
    retrain_steps: int = 4         # fine-tune budget when repair == "retrain"
    max_remap_fraction: float = 0.5
    # repro.obs device-side counters: fold a Counters pytree beside each
    # compiled step from the call ledger of the feed that ran
    # (docs/observability.md).  Off by default — the ledger discovery trace
    # at bundle build and one jitted fold per step are the cost; the decode
    # step is the same program either way.
    counters: bool = False
    # steps ``series_host()`` returns: the last N StepRecords as the
    # per-step channels run_vfleet records per replica
    series_capacity: int = 4096
    # ABFT canary on the scan path (repro.transient.abft, docs/faults.md):
    # each scan step also carries the probe matmul's checksum pair and emits
    # abft.alarm on non-zero syndromes — whole-array, step-granular coverage
    # of transient corruption the block cursor would only meet next sweep
    abft: bool = False
    seed: int = 0

    def hyca(self) -> HyCAConfig:
        # mode is fixed "unprotected": the *fault state fed per step* encodes
        # off/protected/unprotected, so all modes share one compiled step.
        return HyCAConfig(
            rows=self.rows, cols=self.cols,
            dppu=DPPUConfig(size=self.dppu_size, group_size=min(8, self.dppu_size)),
            mode="unprotected",
        )


def prompt_chunk(cfg: ServerConfig, lm: LMConfig) -> int:
    """The step's prompt chunk size: ``prefill_chunk`` capped at ``smax``,
    or 0 where the family takes no chunk (a recurrent state has no
    positions to write a chunk into)."""
    return min(cfg.prefill_chunk, cfg.smax) if lm.family in CHUNK_FAMILIES else 0


# --------------------------------------------------------------------------- #
# compiled pieces (shareable across fleet replicas)
# --------------------------------------------------------------------------- #
class ModelBundle:
    """Params + jitted step/reset for one (arch, n_slots, smax, hyca) shape.
    Fleet replicas share a bundle so XLA compiles the step exactly once."""

    def __init__(self, cfg: ServerConfig, lm: LMConfig | None = None):
        if cfg.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {cfg.prefill_chunk}")
        self.cfg = cfg
        self.lm = lm or (get_smoke_config if cfg.smoke else get_config)(cfg.arch)
        self.prefill_chunk = prompt_chunk(cfg, self.lm)
        self.hyca = cfg.hyca()
        self.params = init_params(jax.random.key(cfg.seed), self.lm)
        self.max_faults = cfg.rows * cfg.cols
        self.empty_state = empty_fault_state(self.max_faults)
        # the identity RepairPlan: every step carries a plan leaf, so when
        # the repair hook swaps in a real remap plan the compiled step is
        # reused (leaf-only change — zero recompiles, docs/repair.md)
        self.identity_plan = identity_plan(cfg.rows, cfg.cols)
        self._salience: np.ndarray | None = None
        # One FTContext per bundle: static dispatch/policy chosen here; the
        # per-step fault table is swapped in with with_state (a traced leaf,
        # so the jitted step never recompiles on fault-table updates).
        self.ftc = build_ftcontext(
            self.empty_state, self.hyca,
            policy=ProtectPolicy(layer_fraction=cfg.protect_fraction),
            dispatch=cfg.dispatch,
            plan=self.identity_plan,
        )
        ftc, lmc = self.ftc, self.lm

        # feed: decode_step's batch — a bare (n_slots, 1) token array (one
        # token a slot, every row fed), or {"token", "advance", "chunk"}
        # (ContinuousBatchingScheduler.chunk_feed) for a step with a chunk
        def _step(params, cache, feed, fstate, plan):
            batch = feed if isinstance(feed, dict) else {"token": feed}
            return decode_step(params, lmc, cache, batch,
                               ftc=ftc.with_state(fstate).with_plan(plan))

        # with counters, the static call ledger of each feed of
        # feed_shapes(), discovered by abstractly tracing the step once
        # (shapes only); the server folds it beside each step
        # (repro.obs.counters)
        self.ledgers: tuple | None = None
        if cfg.counters:
            from repro.obs.counters import trace_site_calls

            cache_shapes = jax.eval_shape(self.fresh_cache)
            self.ledgers = tuple(trace_site_calls(
                lambda c, p, ch, f: _step(p, ch, f, c.state, c.plan),
                ftc, self.params, cache_shapes, feed,
            ) for feed in self.feed_shapes())

        def _reset(cache, slot):
            def f(path, leaf):
                name = str(getattr(path[-1], "key", path[-1]))
                if name == "enc":
                    return leaf.at[slot].set(jnp.zeros_like(leaf[0]))
                return leaf.at[:, slot].set(jnp.zeros_like(leaf[:, 0]))
            return jax.tree_util.tree_map_with_path(f, cache)

        self._step = jax.jit(_step, donate_argnums=(1,))
        self._step_compiled = False
        self.step_fn = self._step
        self.reset_fn = jax.jit(_reset, donate_argnums=(0,))

    @property
    def salience(self) -> np.ndarray:
        """Weight-norm salience per PE residue class — the remap planner's
        default importance signal for this model.  Computed lazily on the
        first repair event: servers with ``repair="none"`` (the default)
        never pay the full-parameter host sweep."""
        if self._salience is None:
            self._salience = weight_salience(self.params, self.cfg.cols)
        return self._salience

    def fresh_cache(self) -> Any:
        return init_cache(self.lm, self.cfg.n_slots, self.cfg.smax)

    def feed_shapes(self) -> tuple:
        """Shapes of each ``feed`` the step takes (the batch of
        ``decode_step``): the bare token array, then, with a chunk size,
        the tokens beside a prompt chunk."""
        i32 = jnp.int32
        tok = jax.ShapeDtypeStruct((self.cfg.n_slots, 1), i32)
        if not self.prefill_chunk:
            return (tok,)
        return tok, {"token": tok,
                     "advance": jax.ShapeDtypeStruct((self.cfg.n_slots,), jnp.bool_),
                     "chunk": {"tokens": jax.ShapeDtypeStruct((self.prefill_chunk,), i32),
                               "slot": jax.ShapeDtypeStruct((), i32),
                               "len": jax.ShapeDtypeStruct((), i32)}}

    def compile_step(self, params, cache, fstate, plan) -> None:
        """Compile the step for every feed of :meth:`feed_shapes`, and with
        counters the fold of each feed's ledger, once per bundle, so that a
        step with no prompt chunk to run (the token-only program) compiles
        no more mid-run than one with a chunk.  Lowering reads only the
        arguments' shapes: the donated cache stays valid."""
        if self._step_compiled:
            return
        for shapes in self.feed_shapes():
            feed = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
            self._step.lower(params, cache, feed, fstate, plan).compile()
        for ledger in self.ledgers or ():
            fold_ledger.lower(ledger, Counters.zero(), fstate, plan, self.hyca).compile()
        self._step_compiled = True


# --------------------------------------------------------------------------- #
# the server
# --------------------------------------------------------------------------- #
# the integer series channels and the StepRecord field each is read from
_SERIES_FIELDS = {
    "active": "active_slots", "confirmed": "confirmed_faults",
    "effective_slots": "effective_slots", "queue_depth": "queue_depth",
    "surviving_cols": "surviving_cols", "tokens": "tokens_generated",
    "true_faults": "true_faults",
}


class FaultTolerantServer:
    def __init__(self, cfg: ServerConfig, *, bundle: ModelBundle | None = None,
                 injector: FaultInjector | None = None):
        if cfg.mode not in ("off", "protected", "unprotected"):
            raise ValueError(f"unknown mode {cfg.mode!r}")
        if cfg.repair not in ("none", "remap", "retrain"):
            raise ValueError(f"unknown repair mode {cfg.repair!r}")
        self.cfg = cfg
        self.bundle = bundle or ModelBundle(cfg)
        self.lm = self.bundle.lm
        self.cache = self.bundle.fresh_cache()
        # per-replica view of the bundle params: the retrain repair hook
        # swaps repaired params into THIS server without touching fleet
        # siblings sharing the compiled bundle
        self.params = self.bundle.params
        self.plan = self.bundle.identity_plan
        self._repair_key: tuple[int, int] | None = None
        # repro.obs: one event log per server, shared with the injector and
        # the manager; step() stamps the cursor, so injections and lifecycle
        # transitions carry serving-time steps (docs/observability.md)
        self.log = EventLog()
        # the collector's pauses become hyca.python.gc spans and counters
        install_gc_hook()
        self.counters = Counters.zero() if cfg.counters else None
        self.injector = injector or FaultInjector(cfg.rows, cfg.cols, seed=cfg.seed + 1)
        self.injector.log = self.log
        self.manager = FaultManager(
            self.bundle.hyca, self.injector,
            FaultManagerConfig(
                confirm_hits=cfg.confirm_hits, scan_block=cfg.scan_block,
                remap=cfg.repair != "none",
                max_remap_fraction=cfg.max_remap_fraction,
                abft=cfg.abft,
            ),
        )
        self.manager.log = self.log
        self.log.emit(
            "server.start", mode=cfg.mode, rows=cfg.rows, cols=cfg.cols,
            dppu=cfg.dppu_size, dispatch=cfg.dispatch, arch=self.lm.name,
        )
        self.queue = RequestQueue(self.bundle.prefill_chunk)
        self.scheduler = ContinuousBatchingScheduler(
            cfg.n_slots, cfg.smax, self.bundle.prefill_chunk)
        # request lifecycle events share the server's log: enqueue/admit/
        # first_token/complete correlate by rid into repro.obs.trace spans
        self.queue.log = self.log
        self.scheduler.log = self.log
        self.metrics = ServingMetrics(
            cfg.n_slots, cfg.rows, cfg.cols,
            steps_per_sweep=self.manager.steps_per_sweep,
            log=self.log,
        )
        self.step_idx = 0
        self._next_rid = 0
        self._fstate_key: tuple[int, int, int] | None = None
        self._fstate = self.bundle.empty_state
        if cfg.mode == "protected":
            if cfg.bist:
                self.manager.bist()
            elif cfg.boot_scan:
                self.manager.boot_scan()

    # ------------------------------------------------------------------ #
    def submit(self, prompt, max_new_tokens: int, *, deadline_step: int | None = None,
               eos_id: int | None = None, arrival_step: int | None = None) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self.queue.submit(Request(
            rid=rid, prompt=np.asarray(prompt, np.int32),
            max_new_tokens=max_new_tokens,
            arrival_step=self.step_idx if arrival_step is None else arrival_step,
            deadline_step=deadline_step, eos_id=eos_id,
        ))
        return rid

    @property
    def retired(self) -> bool:
        """Degraded to zero surviving columns — the replica cannot serve."""
        return self.cfg.mode == "protected" and self.manager.surviving_cols == 0

    def _current_fstate(self) -> FaultState:
        if self.cfg.mode == "off":
            return self.bundle.empty_state
        key = (self.injector.version, self.manager.n_confirmed, self.manager.n_remapped)
        if key != self._fstate_key:
            if self.cfg.mode != "protected":
                exclude = frozenset()
            else:
                # repaired faults are DPPU-recomputed and retired faults are
                # disconnected with their column region — both clean.
                # REMAPPED faults stay IN the served state: their PEs still
                # corrupt, and the active RepairPlan is what routes pruned
                # low-salience channels onto them (docs/repair.md).  The
                # engine NEVER repairs anything in the served state — the
                # bundle's HyCAConfig is mode="unprotected" (see
                # ServerConfig.hyca), so DPPU repair is modelled by this
                # exclusion alone and cannot be double-counted against the
                # remapped overflow (regression-pinned in tests/test_repair
                # .py::test_remapped_faults_really_corrupt_without_plan).
                exclude = (
                    self.manager.repaired_coords() | self.manager.retired_coords()
                )
            self._fstate = self.injector.fault_state(
                exclude=exclude, max_faults=self.bundle.max_faults
            )
            self._fstate_key = key
        return self._fstate

    def _effective_slots(self) -> int:
        if self.cfg.mode != "protected":
            return self.cfg.n_slots
        frac = self.manager.capacity_fraction
        if frac >= 1.0:
            return self.cfg.n_slots
        if self.manager.surviving_cols == 0:
            return 0
        return max(1, int(np.floor(self.cfg.n_slots * frac)))

    # ------------------------------------------------------------------ #
    # repro.repair — the background repair hook (docs/repair.md)
    # ------------------------------------------------------------------ #
    def apply_repair(self, *, plan=None, params=None) -> None:
        """Swap a repair plan and/or repaired params into the running server.
        Both are traced leaves of the compiled step — no recompilation."""
        if plan is not None:
            self.plan = plan
        if params is not None:
            self.params = params

    def _maybe_repair(self) -> None:
        if self.cfg.repair == "none" or self.cfg.mode != "protected":
            return
        key = (self.manager.n_confirmed, self.manager.n_remapped)
        if self.manager.n_remapped == 0 or key == self._repair_key:
            return
        with span("repair"):
            self._repair_key = key
            # plan ONLY the columns the manager actually REMAPPED: overflow past
            # the max_remap_fraction budget is RETIRED (column-region discard),
            # and pruning victims for discarded columns would double-charge the
            # quality accounting
            plan = remap_plan(
                self.manager.confirmed_state, self.bundle.hyca, self.bundle.salience,
                broken_cols=self.manager.remapped_cols,
            )
            params = None
            if self.cfg.repair == "retrain" and self.cfg.retrain_steps > 0:
                from repro.repair.retrain import RetrainConfig, retrain

                params, report = retrain(
                    self.params, self.lm,
                    hyca=self.bundle.hyca,
                    state=self.manager.confirmed_state,
                    plan=plan,
                    rc=RetrainConfig(
                        steps=self.cfg.retrain_steps,
                        seq_len=min(32, self.cfg.smax),
                        seed=self.cfg.seed,
                    ),
                )
            self.apply_repair(plan=plan, params=params)
            self.log.emit(
                "repair.plan",
                step=self.step_idx,
                mode=self.cfg.repair,
                n_remapped=self.manager.n_remapped,
                remapped_cols=sorted(self.manager.remapped_cols),
                quality_fraction=self.manager.quality_fraction,
                retrained=params is not None,
            )

    @property
    def repair_events(self) -> list[dict]:
        """Repair-hook applications, as dicts (a view over the event log)."""
        return [dict(e.data, step=e.step) for e in self.log.of_kind("repair.plan")]

    def counters_host(self) -> dict | None:
        """Host-folded device counters (None when ``cfg.counters`` is off)."""
        return None if self.counters is None else self.counters.to_host()

    def series_host(self) -> dict:
        """The per-step telemetry channels run_vfleet records per replica,
        read from the last ``series_capacity`` StepRecords as host arrays,
        oldest first; ``series_start_step()`` gives the step of row 0."""
        start = self.series_start_step()
        recs = self.metrics.steps[start:]
        scans = np.cumsum([r.scan_ok is not None for r in self.metrics.steps])[start:]
        series = {ch: np.array([getattr(r, f) for r in recs], np.int32)
                  for ch, f in _SERIES_FIELDS.items()}
        series["capacity_fraction"] = np.array(
            [r.surviving_cols / self.cfg.cols for r in recs], np.float32)
        series["quality_fraction"] = np.array([r.quality_fraction for r in recs], np.float32)
        series["scan_coverage"] = np.minimum(
            1.0, scans / max(self.metrics.steps_per_sweep, 1)).astype(np.float32)
        return dict(sorted(series.items()))

    def series_start_step(self) -> int:
        return max(0, len(self.metrics.steps) - self.cfg.series_capacity)

    # ------------------------------------------------------------------ #
    def step(self) -> list[CompletedRequest]:
        """One server step.  Every phase runs under a ``hyca.*`` profiler
        span (repro.obs.host) inside the root ``hyca.server.step``."""
        cfg = self.cfg
        step = self.step_idx
        self.log.step = step
        completed: list[CompletedRequest] = []
        root = span("server.step", step=step)
        with root:
            # 1. hardware wearout
            if cfg.mode != "off" and cfg.fault_rate > 0:
                with span("fault.inject"):
                    self.injector.step(cfg.fault_rate)

            # 2. one batched row-block scan step per decode step
            scan_ok: bool | None = None
            if cfg.mode == "protected":
                with span("fault.scan"):
                    scan_ok, _ = self.manager.scan_step()

            # 2b. background repair hook: newly REMAPPED faults trigger a plan
            # rebuild (and, in retrain mode, a budgeted fine-tune) — swapped
            # into the running step as traced leaves, zero recompiles
            self._maybe_repair()

            with span("sched.admit"):
                # 3. degraded capacity -> admission limit
                eff = self._effective_slots()
                self.scheduler.set_effective_slots(eff)

                # 4. admission into freed slots
                admitted, rejected = self.scheduler.admit(self.queue, step)
                completed.extend(rejected)
                for req in self.queue.drained_expired():
                    completed.append(CompletedRequest(
                        rid=req.rid, tokens=np.zeros(0, np.int32), prompt_len=req.prompt_len,
                        arrival_step=req.arrival_step, admitted_step=None,
                        first_token_step=None, finish_step=step, reason="expired",
                        deadline_step=req.deadline_step,
                    ))
            if admitted:
                with span("cache.reset"):
                    for slot in admitted:
                        self.cache = self.bundle.reset_fn(self.cache, jnp.int32(slot.index))

            # 5. one batched decode over all slots (and the prompt chunk)
            with span("decode.feed"):
                tok = self.scheduler.plan_feed()
                n_prefill = self.scheduler.prefill_tokens
                positions = self.scheduler.attended_positions() if root.is_enabled() else None
                chunked = self.scheduler.chunk is not None
                if not chunked:
                    # no prompt chunk to run: the token-only step, which
                    # costs what a step without chunks did
                    feed = jnp.asarray(tok)
                else:
                    feed = jax.device_put({"token": tok, **self.scheduler.chunk_feed()})
                fstate = self._current_fstate()
            with span("decode.dispatch"):
                self.bundle.compile_step(self.params, self.cache, fstate, self.plan)
                logits, self.cache = self.bundle.step_fn(
                    self.params, self.cache, feed, fstate, self.plan,
                )
            with span("decode.sample"):
                best = jnp.argmax(logits[:, -1, :], axis=-1)
                load = self.cache.get("moe_load") if positions is not None else None
                if load is not None:   # traced MoE step: one readback for tokens and load
                    best, load = jax.device_get((best, load))
                    busy = np.array([not s.free for s in self.scheduler.slots])
                    load = load[:, busy].sum(1)          # (MoE layers, held experts)
                sampled = np.asarray(best, np.int32)

            # 6. advance requests
            with span("sched.commit"):
                n_active = self.scheduler.active
                done = self.scheduler.commit(sampled, step)
                completed.extend(done)
                n_decode_tokens = self.scheduler.last_step_tokens

            with span("metrics.record"):
                self.metrics.record_step(StepRecord(
                    step=step,
                    active_slots=n_active,
                    effective_slots=eff,
                    queue_depth=self.queue.depth(),
                    tokens_generated=int(n_decode_tokens),
                    confirmed_faults=self.manager.n_confirmed,
                    true_faults=self.injector.n_faults,
                    surviving_cols=self.manager.surviving_cols,
                    scan_ok=scan_ok,
                    completed=len(completed),
                    remapped=self.manager.n_remapped,
                    quality_fraction=self.manager.quality_fraction,
                    prefill_tokens=n_prefill,
                ), completed)
                if self.counters is not None:
                    # the counters fold beside the step, over the ledger of
                    # the feed that ran and the step's fault state and plan
                    self.counters = fold_ledger(
                        self.bundle.ledgers[1 if chunked else 0], self.counters,
                        fstate, self.plan, self.bundle.hyca,
                    )
                self.step_idx += 1
            if positions is not None:
                root.set_metadata(active=n_active, positions=positions,
                                  tokens=int(n_decode_tokens), prefill_tokens=n_prefill,
                                  queue=self.queue.depth())
                if load is not None:
                    root.set_metadata(moe_pairs=int(load.sum()), moe_max_load=int(load.max()))
        return completed

    # ------------------------------------------------------------------ #
    def run(self, trace: list[dict] | None = None, *, max_steps: int = 256,
            drain: bool = True, on_step=None) -> dict:
        """Drive the server over a request trace.

        ``trace``: list of {"step", "prompt", "max_new_tokens", ...} dicts;
        requests are submitted when the loop reaches their arrival step.
        Runs until the trace is exhausted and all work is done (or
        ``max_steps``).  ``on_step(server)`` — optional hook invoked at the
        top of every loop iteration; the chaos-injection path
        (docs/campaign.md) uses it to merge campaign-sampled fault maps into
        the live injector mid-run.  Returns the metrics summary.
        """
        trace = sorted(trace or [], key=lambda t: t.get("step", 0))
        ti = 0
        while self.step_idx < max_steps:
            self.log.step = self.step_idx
            if on_step is not None:
                on_step(self)
            while ti < len(trace) and trace[ti].get("step", 0) <= self.step_idx:
                t = trace[ti]
                self.submit(
                    t["prompt"], t["max_new_tokens"],
                    deadline_step=t.get("deadline_step"), eos_id=t.get("eos_id"),
                )
                ti += 1
            self.step()
            no_work = ti >= len(trace) and self.queue.depth() == 0 and self.scheduler.active == 0
            if no_work or (self.retired and self.scheduler.active == 0):
                break
        if drain:
            self.metrics.completions.extend(self.scheduler.drain(self.step_idx))
            # never-admitted requests count as failures, not silence
            for req in self.queue.drain_all():
                self.log.emit("request.complete", step=self.step_idx,
                              rid=req.rid, reason="dropped", tokens=0)
                self.metrics.completions.append(CompletedRequest(
                    rid=req.rid, tokens=np.zeros(0, np.int32), prompt_len=req.prompt_len,
                    arrival_step=req.arrival_step, admitted_step=None,
                    first_token_step=None, finish_step=self.step_idx, reason="dropped",
                    deadline_step=req.deadline_step,
                ))
        self.metrics.finish()
        return self.metrics.summary(counters=self.counters_host())

    def completions_by_rid(self) -> dict[int, np.ndarray]:
        return {c.rid: c.tokens for c in self.metrics.completions if c.ok}
