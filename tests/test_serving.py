"""repro.serving acceptance tests — the ISSUE's contract:

  * continuous batching fills freed decode slots within one step;
  * with injected faults <= DPPU capacity the served tokens are bit-exact
    with the fault-free run (mode ``off`` vs ``protected``);
  * with faults > capacity the fault manager reduces admitted batch capacity
    and goodput degrades monotonically, never crashes;

plus unit coverage for the queue/scheduler, the fault lifecycle state
machine, the engine's n_repair capacity clamp, the spare pool, and a fleet
smoke run.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import HyCAConfig, fault_state_from_map, hyca_matmul
from repro.core.redundancy import DPPUConfig
from repro.runtime.elastic import SparePool
from repro.serving import (
    CONFIRMED,
    REPAIRED,
    RETIRED,
    SUSPECT,
    ContinuousBatchingScheduler,
    FaultInjector,
    FaultManager,
    FaultTolerantServer,
    FleetConfig,
    ModelBundle,
    Request,
    RequestQueue,
    ServerConfig,
    run_fleet,
)
from repro.serving.fault_manager import FaultManagerConfig

BASE = ServerConfig(
    arch="qwen1.5-0.5b", n_slots=4, smax=32, mode="off",
    rows=4, cols=4, dppu_size=2, seed=0,
)
CAPACITY = BASE.hyca().capacity  # 2 on the 4x4 array


@pytest.fixture(scope="module")
def bundle():
    """One compiled decode step shared by every server in this module."""
    return ModelBundle(BASE)


def _server(bundle, mode, **kw):
    cfg = dataclasses.replace(BASE, mode=mode, **kw)
    return FaultTolerantServer(cfg, bundle=bundle)


def _trace(n, prompt_len=3, max_new=4, vocab=512, step=0):
    rng = np.random.default_rng(42)
    return [
        {"step": step, "prompt": rng.integers(0, vocab, size=prompt_len),
         "max_new_tokens": max_new}
        for _ in range(n)
    ]


# --------------------------------------------------------------------------- #
# continuous batching
# --------------------------------------------------------------------------- #
def test_freed_slots_refill_within_one_step(bundle):
    srv = _server(bundle, "off")
    for t in _trace(5):  # 5 requests, 4 slots
        srv.submit(t["prompt"], t["max_new_tokens"])
    finish_step = None
    while srv.step_idx < 40:
        done = srv.step()
        if done and finish_step is None:
            finish_step = done[0].finish_step
        if len(srv.metrics.completions) == 5:
            break
    assert len(srv.metrics.completions) == 5
    fifth = next(c for c in srv.metrics.completions if c.rid == 4)
    # the queued request was admitted on the very next step after a slot freed
    assert fifth.admitted_step == finish_step + 1


def test_prefill_then_decode_slot_reuse(bundle):
    """Two sequential requests through one slot: cache position resets."""
    srv = _server(bundle, "off")
    r0 = srv.submit(np.arange(1, 4), 3)
    while not srv.metrics.completions:
        srv.step()
    r1 = srv.submit(np.arange(1, 4), 3)
    while len(srv.metrics.completions) < 2:
        srv.step()
    a, b = (next(c for c in srv.metrics.completions if c.rid == r) for r in (r0, r1))
    # same prompt through the same weights must produce the same tokens,
    # which requires the slot's KV cache to have been reset cleanly
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_ttft_and_queue_metrics(bundle):
    srv = _server(bundle, "off")
    for t in _trace(6, prompt_len=4, max_new=3):
        srv.submit(t["prompt"], t["max_new_tokens"])
    s = srv.run(max_steps=60)
    assert s["requests_completed"] == 6
    # a prompt of 4 fits one chunk, and the slots admitted together take the
    # chunk in turn, one a step: rids 0-3 (admitted at step 0) get their
    # first token at steps 0, 1, 2, 3; rids 4 and 5 wait in the queue for
    # slots freed at steps 2 and 3, and get theirs at steps 4 and 5
    assert s["ttft_mean_steps"] == (0 + 1 + 2 + 3 + 4 + 5) / 6
    assert s["queue_depth_mean"] > 0


# --------------------------------------------------------------------------- #
# bit-exactness under protection
# --------------------------------------------------------------------------- #
def test_protected_bitexact_with_faults_within_capacity(bundle):
    trace = _trace(6, prompt_len=4, max_new=5)
    ref = _server(bundle, "off")
    ref.run([dict(t) for t in trace], max_steps=80)
    reference = ref.completions_by_rid()
    assert len(reference) == 6

    srv = _server(bundle, "protected")
    srv.injector.inject_at(1, 2, bit=30, val=1)
    srv.injector.inject_at(3, 1, bit=25, val=1)
    assert srv.injector.n_faults <= CAPACITY
    srv.manager.bist()
    srv.run([dict(t) for t in trace], max_steps=80)
    prot = srv.completions_by_rid()
    assert set(prot) == set(reference)
    for rid, toks in reference.items():
        np.testing.assert_array_equal(toks, prot[rid])
    # full goodput: every served token matches the fault-free run
    assert srv.metrics.goodput_tokens(reference) == ref.metrics.goodput_tokens(reference)


def test_unprotected_corrupts_with_same_faults(bundle):
    trace = _trace(6, prompt_len=4, max_new=5)
    ref = _server(bundle, "off")
    ref.run([dict(t) for t in trace], max_steps=80)
    reference = ref.completions_by_rid()

    srv = _server(bundle, "unprotected")
    # high-exponent stuck-at-1 faults on every PE row the batch maps onto
    # (bit 30 of the f32 pattern blows the value up -> visibly wrong tokens)
    for r in range(4):
        srv.injector.inject_at(r, r, bit=30, val=1)
    srv.run([dict(t) for t in trace], max_steps=80)
    assert srv.metrics.goodput_tokens(reference) < ref.metrics.goodput_tokens(reference)


# --------------------------------------------------------------------------- #
# degradation past capacity
# --------------------------------------------------------------------------- #
def test_over_capacity_degrades_monotonically_never_crashes(bundle):
    trace = _trace(8, prompt_len=3, max_new=4)
    rng = np.random.default_rng(7)
    cells = [(int(i) // 4, int(i) % 4) for i in rng.permutation(16)]

    eff_final, goodput_per_step, servers = [], [], []
    for n in [0, CAPACITY, CAPACITY + 1, CAPACITY + 2, CAPACITY + 5]:
        srv = _server(bundle, "protected")
        for r, c in cells[:n]:
            srv.injector.inject_at(r, c)
        srv.manager.bist()
        s = srv.run([dict(t) for t in trace], max_steps=200)
        eff_final.append(s["effective_slots_final"])
        goodput_per_step.append(s["goodput_per_step"])
        servers.append(srv)

    # at capacity: full admission; beyond: reduced
    assert eff_final[0] == BASE.n_slots and eff_final[1] == BASE.n_slots
    assert eff_final[2] < BASE.n_slots
    assert all(a >= b for a, b in zip(eff_final, eff_final[1:]))
    assert all(a >= b - 1e-9 for a, b in zip(goodput_per_step, goodput_per_step[1:]))
    # over-capacity faults are confirmed -> remapped, so tokens stay CORRECT
    over = servers[2]
    assert all(c.ok for c in over.metrics.completions) or over.retired


def test_fully_degraded_server_refuses_but_does_not_crash(bundle):
    srv = _server(bundle, "protected")
    # column 0 faults beyond capacity: surviving prefix collapses to zero
    for r in range(4):
        srv.injector.inject_at(r, 0)
    srv.manager.bist()
    assert srv.manager.surviving_cols == 0 and srv.retired
    for t in _trace(3):
        srv.submit(t["prompt"], t["max_new_tokens"])
    s = srv.run(max_steps=20)
    assert s["goodput_tokens"] == 0
    assert s["effective_slots_final"] == 0


# --------------------------------------------------------------------------- #
# fault lifecycle state machine
# --------------------------------------------------------------------------- #
def test_lifecycle_suspect_confirm_repair():
    inj = FaultInjector(4, 4, seed=0)
    mgr = FaultManager(BASE.hyca(), inj, FaultManagerConfig(confirm_hits=2))
    inj.inject_at(2, 3, bit=30, val=1)
    states = []
    for _ in range(3 * 16):
        mgr.scan_step()
        states.append(mgr.pe_state[2, 3])
        if mgr.pe_state[2, 3] == REPAIRED:
            break
    assert REPAIRED in states                 # confirmed within capacity
    assert SUSPECT in states                  # passed through SUSPECT first
    assert states.index(SUSPECT) < states.index(REPAIRED)
    assert mgr.confirmed_coords() == {(2, 3)}
    assert mgr.capacity_fraction == 1.0


def test_lifecycle_retires_overflow_leftmost_first():
    inj = FaultInjector(4, 4, seed=0)
    mgr = FaultManager(BASE.hyca(), inj, FaultManagerConfig(confirm_hits=1))
    for r, c in [(0, 0), (1, 1), (2, 2), (3, 3)]:
        inj.inject_at(r, c, bit=30, val=1)
    for _ in range(2 * 16):
        mgr.scan_step()
    assert mgr.n_confirmed == 4
    # capacity 2: two leftmost repaired, the rest retired
    assert mgr.pe_state[0, 0] == REPAIRED and mgr.pe_state[1, 1] == REPAIRED
    assert mgr.pe_state[2, 2] == RETIRED and mgr.pe_state[3, 3] == RETIRED
    assert mgr.surviving_cols == 2            # first retired fault sits in col 2
    assert mgr.capacity_fraction == pytest.approx(0.5)


def test_bist_confirms_factory_faults():
    inj = FaultInjector(4, 4, seed=3)
    inj.inject_n(3)
    mgr = FaultManager(BASE.hyca(), inj)
    assert mgr.bist() == 3
    assert mgr.confirmed_coords() == frozenset(inj.coords())


# --------------------------------------------------------------------------- #
# engine: n_repair clamp (the DPPU cannot repair beyond its capacity)
# --------------------------------------------------------------------------- #
def test_hyca_matmul_clamps_n_repair_to_capacity(rng):
    cfg = HyCAConfig(rows=4, cols=4, dppu=DPPUConfig(size=2, group_size=2), mode="protected")
    assert cfg.capacity == 2
    fmap = np.zeros((4, 4), bool)
    for r, c in [(0, 0), (1, 1), (2, 2), (3, 3)]:
        fmap[r, c] = True
    state = fault_state_from_map(fmap, max_faults=4)
    # force visible stuck bits (sign bit on the f32 pattern)
    state = dataclasses.replace(
        state, stuck_bit=jnp.full(4, 31, jnp.int32), stuck_val=jnp.ones(4, jnp.int32)
    )
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    clean = hyca_matmul(x, w, None, cfg=dataclasses.replace(cfg, mode="off"))
    ask_all = hyca_matmul(x, w, state, cfg=cfg, n_repair=4)   # asks beyond capacity
    at_cap = hyca_matmul(x, w, state, cfg=cfg, n_repair=2)
    # the clamp makes "repair everything" identical to "repair capacity"...
    np.testing.assert_array_equal(np.asarray(ask_all), np.asarray(at_cap))
    # ...and the unrepaired overflow stays corrupted
    assert not np.array_equal(np.asarray(ask_all), np.asarray(clean))


# --------------------------------------------------------------------------- #
# queue + scheduler units
# --------------------------------------------------------------------------- #
def test_queue_drops_unmeetable_deadlines():
    q = RequestQueue()
    q.submit(Request(rid=0, prompt=np.arange(4), max_new_tokens=4, deadline_step=3))
    q.submit(Request(rid=1, prompt=np.arange(4), max_new_tokens=4, deadline_step=100))
    got = q.pop_ready(step=0)  # needs 4+4-1=7 steps; deadline 3 unmeetable
    assert got is not None and got.rid == 1
    dropped = q.drained_expired()
    assert [r.rid for r in dropped] == [0]


def test_queue_admits_exactly_feasible_deadline(bundle):
    # admitted at step s, a request finishes at s + min_steps_to_finish(C) - 1;
    # a deadline equal to that must be admitted (and met), not dropped.
    # One prompt token a step (C = 0): 4 + 4 - 1 = 7 steps
    q = RequestQueue()
    req = Request(rid=0, prompt=np.arange(4), max_new_tokens=4, deadline_step=6)
    assert req.min_steps_to_finish() == 7
    q.submit(req)
    assert q.pop_ready(step=0) is req and not q.drained_expired()
    # the server's chunk takes the whole prompt in the admission step, whose
    # logits give the first token: 1 + 4 - 1 = 4 steps
    srv = _server(bundle, "off")
    c = srv.bundle.prefill_chunk
    assert c >= 4 and req.min_steps_to_finish(c) == 4
    q = RequestQueue(c)
    for rid, deadline in enumerate((2, 3)):
        q.submit(Request(rid=rid, prompt=np.arange(4), max_new_tokens=4, deadline_step=deadline))
    assert q.pop_ready(step=0).rid == 1 and [r.rid for r in q.drained_expired()] == [0]
    srv.submit(np.arange(4), 4, deadline_step=3)
    srv.run(max_steps=20)
    (done,) = srv.metrics.completions
    assert done.reason == "done" and done.finish_step == 3


def test_run_accounts_never_admitted_requests(bundle):
    srv = _server(bundle, "protected")
    for r in range(4):  # column-0 overflow: server refuses all admission
        srv.injector.inject_at(r, 0)
    srv.manager.bist()
    for t in _trace(3):
        srv.submit(t["prompt"], t["max_new_tokens"])
    s = srv.run(max_steps=10)
    assert s["requests_failed"] == 3  # dropped, not silently lost


def test_scheduler_expires_inflight_requests():
    # the SLA-aware queue refuses unmeetable deadlines upfront, so build the
    # in-flight state directly: the commit-time guard is the safety net for
    # requests that stall mid-decode
    sched = ContinuousBatchingScheduler(n_slots=1, smax=64)
    slot = sched.slots[0]
    slot.request = Request(rid=0, prompt=np.arange(2), max_new_tokens=50, deadline_step=4)
    slot.phase = "prefill"
    slot.admitted_step = 0
    done = []
    for step in range(8):
        sched.plan_feed()
        done += sched.commit(np.zeros(1, np.int32), step)
    assert len(done) == 1 and done[0].reason == "expired"
    assert done[0].finish_step == 4
    assert sched.slots[0].free


def test_scheduler_rejects_oversized_requests():
    sched = ContinuousBatchingScheduler(n_slots=2, smax=8)
    q = RequestQueue()
    q.submit(Request(rid=0, prompt=np.arange(20), max_new_tokens=10))
    q.submit(Request(rid=1, prompt=np.arange(2), max_new_tokens=2))
    admitted, rejected = sched.admit(q, step=0)
    assert [c.rid for c in rejected] == [0]
    assert len(admitted) == 1 and admitted[0].request.rid == 1


def test_scheduler_respects_effective_slots():
    sched = ContinuousBatchingScheduler(n_slots=4, smax=32)
    sched.set_effective_slots(2)
    q = RequestQueue()
    for i in range(4):
        q.submit(Request(rid=i, prompt=np.arange(3), max_new_tokens=2))
    admitted, _ = sched.admit(q, step=0)
    assert len(admitted) == 2 and sched.active == 2


# --------------------------------------------------------------------------- #
# spare pool + fleet
# --------------------------------------------------------------------------- #
def test_spare_pool_policies():
    pool = SparePool(2, policy="pool", n_regions=4)
    assert pool.try_allocate(0) and pool.try_allocate(3)
    assert not pool.try_allocate(1) and pool.remaining == 0

    region = SparePool(2, policy="region", n_regions=2)
    assert region.try_allocate(0)
    assert not region.try_allocate(0)     # region 0 exhausted
    assert region.try_allocate(1)         # region 1 still has its own spare


def test_fleet_smoke_runs_and_reports():
    cfg = FleetConfig(
        n_replicas=2, n_spares=1, steps=12, fault_rate=0.0, request_rate=0.5,
        server=dataclasses.replace(BASE, mode="protected", n_slots=2),
    )
    r = run_fleet(cfg)
    assert r["steps"] == 12
    assert r["alive_final"] == 2 and r["retirements"] == 0
    assert r["goodput_tokens"] >= 0
    assert len(r["replica_summaries"]) == 2
