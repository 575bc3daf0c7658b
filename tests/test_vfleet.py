"""repro.serving.vfleet acceptance tests — the vectorized fleet engine.

The contract (ISSUE 8): ``run_vfleet`` replays ``run_fleet`` semantics as
one jitted program per chunk, bit-exact on the shared report keys for
pinned small-fleet configs (chaos + trace-driven traffic, zero wearout so
both engines see identical fault truth), deterministic across runs, and
with ZERO recompilations across fault-rate sweep points.  Plus unit
coverage for the traffic model (class quantization / clamps / trace
determinism), SLA accounting in ``ServingMetrics.summary()``, autoscale
event emission against the repro.obs schema, and the batched
confirmed-state packer.
"""
import dataclasses

import numpy as np
import pytest

from repro.obs.events import EventLog
from repro.obs.schema import validate_event, validate_jsonl
from repro.serving import (
    AutoscaleSpec,
    ChaosSpec,
    FaultTolerantServer,
    FleetConfig,
    ServerConfig,
    TrafficSpec,
    request_classes,
    run_fleet,
    run_vfleet,
    sample_trace,
)
from repro.serving.vfleet import _TRACES, batched_confirmed_states

# prefill_chunk=1: the chunk feeds one prompt token a step, so every
# prefilling slot advances one token a step — the token-level feed that the
# vectorized engine's decode proxy models
SERVER = ServerConfig(
    n_slots=2, smax=32, mode="protected", scan_block=2,
    rows=4, cols=4, dppu_size=2, prefill_chunk=1,
)

# the pinned cross-engine parity configs: fault_rate=0 (wearout RNG is the
# one engine-private random stream), chaos supplies the fault truth both
# engines share via chaos_signatures
PARITY_POOL = FleetConfig(
    n_replicas=3, n_spares=2, spare_policy="pool", n_regions=1, steps=48,
    fault_rate=0.0, retire_fraction=0.25, seed=0,
    chaos=ChaosSpec(per=0.3, at_step=10, seed=3),
    traffic=TrafficSpec(request_rate=0.8, sla_steps=12, seed=5),
    server=SERVER,
)
PARITY_REGION = FleetConfig(
    n_replicas=4, n_spares=2, spare_policy="region", n_regions=2, steps=40,
    fault_rate=0.0, retire_fraction=0.25, seed=7,
    chaos=ChaosSpec(per=0.5, at_step=6, seed=11),
    traffic=TrafficSpec(request_rate=1.2, sla_steps=14, seed=9,
                        n_classes=2, tail=0.6),
    server=SERVER,
)

PARITY_KEYS = (
    "goodput_tokens", "requests_completed", "requests_expired",
    "requests_lost", "requests_unrouted", "retirements", "replacements",
    "spares_remaining", "chaos_injected", "alive_final",
    "slo_requests", "slo_met", "slo_misses",
)


@pytest.mark.parametrize("cfg", [PARITY_POOL, PARITY_REGION],
                         ids=["pool-1class", "region-2class"])
def test_vfleet_matches_legacy_fleet(cfg):
    legacy = run_fleet(cfg)
    vec = run_vfleet(cfg)
    diffs = {k: (legacy[k], vec[k]) for k in PARITY_KEYS if legacy[k] != vec[k]}
    assert not diffs, f"engine divergence: {diffs}"
    assert legacy["alive_mean"] == vec["alive_mean"]


def test_vfleet_deterministic():
    a = run_vfleet(PARITY_POOL)
    b = run_vfleet(PARITY_POOL)
    for k in a:
        if k == "sim_wall_s":
            continue
        assert a[k] == b[k], f"{k}: {a[k]} != {b[k]}"


def test_legacy_fleet_deterministic():
    a = run_fleet(PARITY_POOL)
    b = run_fleet(PARITY_POOL)
    for k in PARITY_KEYS:
        assert a[k] == b[k]


def test_no_recompile_across_fault_rates():
    # warm the (geom, chunk-shape) caches, then sweep the fault rate: the
    # rate is a traced leaf, so no new traces may appear (the _TRACES
    # idiom from tests/test_ftcontext.py)
    run_vfleet(dataclasses.replace(PARITY_POOL, fault_rate=0.01))
    n0 = len(_TRACES)
    for i, rate in enumerate((0.0, 0.05, 0.2)):
        run_vfleet(dataclasses.replace(PARITY_POOL, fault_rate=rate, seed=i))
    assert len(_TRACES) == n0, "fault-rate sweep retraced the chunk program"


# --------------------------------------------------------------------------- #
# traffic model
# --------------------------------------------------------------------------- #
def test_request_classes_fit_kv_and_sla():
    spec = TrafficSpec(prompt_len=64, max_new_tokens=64, tail=1.5,
                       n_classes=4, sla_steps=1)
    for cls in request_classes(spec, smax=32):
        assert cls.prompt_len + cls.max_new_tokens <= 32   # KV budget
        # sla clamped so a fresh arrival is still admittable
        assert cls.wait_budget is not None and cls.wait_budget >= 0


def test_sample_trace_deterministic_and_scaled():
    spec = TrafficSpec(request_rate=1.5, seed=42, n_classes=2,
                       burst_rate=0.1, diurnal_amplitude=0.3)
    a = sample_trace(spec, 128, 4, 32)
    b = sample_trace(spec, 128, 4, 32)
    assert np.array_equal(a.counts, b.counts)
    assert a.counts.shape == (128, 2)
    # the mean arrival rate tracks request_rate * n_replicas
    assert a.total_requests > 0.5 * 1.5 * 4 * 128


# --------------------------------------------------------------------------- #
# SLA accounting in ServingMetrics (satellite: deadline enforcement)
# --------------------------------------------------------------------------- #
def test_metrics_summary_counts_expired_as_slo_misses():
    srv = FaultTolerantServer(dataclasses.replace(SERVER, n_slots=1))
    # slot 0 busy for 5 steps; the second request's deadline dies in queue
    srv.submit(np.arange(3), max_new_tokens=3, deadline_step=20)
    srv.submit(np.arange(3), max_new_tokens=3, deadline_step=5)
    for _ in range(12):
        srv.step()
    srv.metrics.finish()
    s = srv.metrics.summary()
    assert s["requests_expired"] == 1
    assert s["slo_requests"] == 2
    assert s["slo_met"] == 1
    assert s["slo_misses"] == 1
    assert s["slo_attainment"] == 0.5


def test_fleet_report_slo_block():
    r = run_fleet(PARITY_POOL)
    assert r["slo_requests"] == r["slo_met"] + r["slo_misses"]
    assert r["slo_attainment"] == pytest.approx(r["slo_met"] / r["slo_requests"])
    v = run_vfleet(PARITY_POOL)
    assert v["slo_requests"] == v["slo_met"] + v["slo_misses"]


# --------------------------------------------------------------------------- #
# autoscale
# --------------------------------------------------------------------------- #
def test_autoscale_emits_schema_valid_events(tmp_path):
    log = EventLog()
    cfg = dataclasses.replace(
        PARITY_POOL,
        n_replicas=2, n_spares=0, steps=96, chunk_steps=8, chaos=None,
        traffic=TrafficSpec(request_rate=4.0, sla_steps=64, seed=1),
        autoscale=AutoscaleSpec(min_replicas=1, max_replicas=6,
                                high_queue=2.0, low_queue=0.0),
    )
    report = run_vfleet(cfg, log=log)
    scale = log.of_kind("fleet.autoscale")
    assert scale, "overloaded fleet never scaled out"
    assert any(e.data["action"] == "scale_out" for e in scale)
    for e in scale:
        validate_event(e.to_json())
    path = tmp_path / "autoscale.jsonl"
    log.to_jsonl(str(path))
    assert validate_jsonl(str(path)) == len(log.events)
    assert report["alive_final"] > 2


# --------------------------------------------------------------------------- #
# batched confirmed-state packer
# --------------------------------------------------------------------------- #
def test_batched_confirmed_states_matches_single_merge():
    from repro.core.engine import empty_fault_state

    rng = np.random.default_rng(0)
    hits = rng.integers(0, 3, size=(3, 4, 4)).astype(np.int32)
    sbit = rng.integers(0, 32, size=(3, 4, 4)).astype(np.int32)
    sval = rng.integers(0, 2, size=(3, 4, 4)).astype(np.int32)
    batched = batched_confirmed_states(hits, sbit, sval, confirm_hits=2)
    for i in range(3):
        ref = empty_fault_state(16).merge(
            hits[i] >= 2, stuck_bit=sbit[i], stuck_val=sval[i])
        assert np.array_equal(batched.fpt[i], ref.fpt)
        assert np.array_equal(batched.stuck_bit[i], ref.stuck_bit)
        assert np.array_equal(batched.stuck_val[i], ref.stuck_val)
