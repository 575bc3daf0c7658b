"""repro.obs acceptance tests — counters, event tracing, export, regression gate.

  * Counters are folded beside the jitted step: decode-then-fold is
    BIT-EXACT with decode alone across all ten registry configs in both
    dispatch modes, with zero recompilations of either program across
    fault-table / plan / counter swaps (the same contract
    tests/test_ftcontext.py pins for the fault table); the server's step
    lowers to the same program with counters on or off;
  * protected_view_stats matches a per-element numpy brute force of the
    engine's out[i, j] -> PE(i % rows, col_map[j % cols]) mapping;
  * ledger discovery sees through lax.scan: per-site counts carry the layer
    multiplicity;
  * EventLog roundtrips through JSONL and validates against the schema;
    chaos-injected serves report detection latencies matching the known
    injection steps exactly;
  * ServingMetrics.summary() edge cases: zero completions, scan-free runs,
    reference-mismatch goodput, lazy wall clock;
  * benchmarks/regress.py passes on the committed baselines and flags a
    synthetic 2x ft_overhead regression.
"""
import dataclasses
import importlib.util
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.core.engine import (
    RepairPlan,
    empty_fault_state,
    fault_state_from_map,
    identity_plan,
    protected_view_stats,
)
from repro.core.ftcontext import SITES, build_ftcontext
from repro.models.lm import forward, init_params
from repro.obs.counters import (
    Counters,
    elems_on_coords,
    fold_ledger,
    ledger_stats,
    trace_site_calls,
)
from repro.obs.events import (
    EventLog,
    detection_records,
    latency_summary,
    repair_records,
)
from repro.obs.export import prometheus_text, write_metrics_out
from repro.obs.schema import validate_event, validate_jsonl
from repro.serving.metrics import ServingMetrics, StepRecord
from repro.serving.queue import CompletedRequest
from repro.serving.server import FaultTolerantServer, ModelBundle, ServerConfig

from test_ftcontext import _batch_for, _f32, _hyca, _seq_for, _state

ROWS = COLS = 8


# --------------------------------------------------------------------------- #
# counters pytree basics
# --------------------------------------------------------------------------- #
def test_counters_zero_and_to_host():
    c = Counters.zero()
    h = c.to_host()
    assert h["steps"] == 0 and h["total_elems"] == 0
    assert h["fault_fraction"] == 0.0  # zero total must not divide by zero
    assert set(h["site_calls"]) == set(SITES)
    # a Counters is a pytree of int32 leaves — jit-transparable
    leaves = jax.tree_util.tree_leaves(c)
    assert all(leaf.dtype == jnp.int32 for leaf in leaves)


# --------------------------------------------------------------------------- #
# protected_view_stats vs. per-element brute force
# --------------------------------------------------------------------------- #
def _brute_force(fmap, repaired, col_map, prune, m, n, rows, cols):
    """Element-by-element replay of the engine mapping
    out[i, j] -> PE(i % rows, col_map[j % cols])."""
    out = dict.fromkeys(
        ("fault_elems", "recomputed_elems", "corrupted_elems",
         "pruned_elems", "fault_col_elems"), 0)
    corrupting = fmap & ~repaired & ~prune
    for i in range(m):
        for j in range(n):
            pr, pc = i % rows, int(col_map[j % cols])
            out["fault_elems"] += int(fmap[pr, pc])
            out["recomputed_elems"] += int(fmap[pr, pc] and repaired[pr, pc])
            out["corrupted_elems"] += int(corrupting[pr, pc])
            out["pruned_elems"] += int(prune[pr, pc])
            out["fault_col_elems"] += int(corrupting[:, pc].any())
    return out


@pytest.mark.parametrize("mode", ["protected", "unprotected"])
@pytest.mark.parametrize("with_plan", [False, True])
def test_protected_view_stats_matches_bruteforce(mode, with_plan, rng):
    rows = cols = 4
    m, n = 10, 13  # deliberately not multiples of the array dims
    cfg = dataclasses.replace(_hyca(mode, dppu=2), rows=rows, cols=cols)
    fmap = np.zeros((rows, cols), bool)
    idx = rng.choice(rows * cols, size=5, replace=False)
    fmap.reshape(-1)[idx] = True
    state = fault_state_from_map(fmap, max_faults=8)

    plan = None
    col_map = np.arange(cols)
    prune = np.zeros((rows, cols), bool)
    if with_plan:
        col_map = rng.permutation(cols)
        prune = rng.random((rows, cols)) < 0.3
        plan = RepairPlan(jnp.asarray(col_map, jnp.int32), jnp.asarray(prune))

    got = {k: int(v) for k, v in protected_view_stats(state, cfg, plan, m, n).items()}
    assert got["total_elems"] == m * n

    # replicate the engine's capacity clamp: repaired = first `capacity`
    # leftmost-sorted FPT entries in protected mode, nothing in unprotected
    repaired = np.zeros((rows, cols), bool)
    if mode == "protected":
        fpt = np.asarray(state.fpt)
        for r, c in fpt[: cfg.capacity]:
            if r >= 0:
                repaired[r, c] = True
    want = _brute_force(fmap, repaired, col_map, prune, m, n, rows, cols)
    for k, v in want.items():
        assert got[k] == v, (k, got[k], v)


def test_view_stats_off_mode_is_all_zero(rng):
    cfg = _hyca("off")
    got = protected_view_stats(_state(3, seed=0), cfg, None, 16, 16)
    assert int(got["total_elems"]) == 256
    for k in ("fault_elems", "recomputed_elems", "corrupted_elems", "pruned_elems"):
        assert int(got[k]) == 0


# --------------------------------------------------------------------------- #
# ledger discovery: eval_shape tracing with scan multiplicities
# --------------------------------------------------------------------------- #
def test_ledger_sees_through_layer_scan(rng):
    cfg = _f32(get_smoke_config("qwen1.5-0.5b"))
    params = init_params(jax.random.key(0), cfg)
    batch = _batch_for(cfg, 1, _seq_for(cfg), rng)
    ftc = build_ftcontext(_state(2, seed=1), _hyca("protected"))
    ledger = trace_site_calls(
        lambda c, p, b: forward(p, cfg, b, ftc=c), ftc, params, batch
    )
    assert ledger, "empty ledger"
    assert all(call.count >= 1 for call in ledger)
    # per-layer sites fire once per scanned layer: their counts carry the
    # n_layers multiplicity even though the scan body traces exactly once
    qkv = sum(c.count for c in ledger if c.site == "attn.qkv")
    assert qkv > 0 and qkv % cfg.n_layers == 0
    # the hook must be disarmed after discovery
    assert ftc._obs_record is None


def test_elems_on_coords_counts_protected_volume(rng):
    cfg = _f32(get_smoke_config("qwen1.5-0.5b"))
    params = init_params(jax.random.key(0), cfg)
    batch = _batch_for(cfg, 1, _seq_for(cfg), rng)
    ftc = build_ftcontext(_state(1, seed=1), _hyca("protected"))
    ledger = trace_site_calls(
        lambda c, p, b: forward(p, cfg, b, ftc=c), ftc, params, batch
    )
    assert elems_on_coords(ledger, set(), ROWS, COLS) == 0
    one = elems_on_coords(ledger, {(0, 0)}, ROWS, COLS)
    assert one > 0
    # the whole array covers every protected element of every call
    full = elems_on_coords(
        ledger, {(r, c) for r in range(ROWS) for c in range(COLS)}, ROWS, COLS
    )
    assert full == sum(c.m * c.n * c.count for c in ledger if c.protected)


# --------------------------------------------------------------------------- #
# the headline contract: counters-on == counters-off, zero retraces
# --------------------------------------------------------------------------- #
def _counted_pair(cfg, dispatch, rng):
    """(jitted decode, counter fold, args, decode trace list) for one arch:
    "counters on" runs the decode, then folds the counters from the
    ledger beside it (repro.obs.counters.fold_ledger); "counters off" runs
    the decode alone."""
    params = init_params(jax.random.key(0), cfg)
    batch = _batch_for(cfg, 1, _seq_for(cfg), rng)
    state = _state(3, seed=5, visible=True, pad_to=8)
    ftc = build_ftcontext(state, _hyca("protected"), dispatch=dispatch,
                          plan=identity_plan(ROWS, COLS))
    ledger = trace_site_calls(
        lambda c, p, b: forward(p, cfg, b, ftc=c), ftc, params, batch
    )
    traces = []

    @jax.jit
    def decode(fstate, plan, p, b):
        traces.append(1)
        logits, _ = forward(p, cfg, b, ftc=ftc.with_state(fstate).with_plan(plan))
        return logits

    def fold(counters, fstate, plan):
        return fold_ledger(ledger, counters, fstate, plan, ftc.hyca)

    return decode, fold, (params, batch, state), traces


def _assert_counters_bitexact(arch, dispatch, rng):
    cfg = _f32(get_smoke_config(arch))
    decode, fold, (params, batch, state), traces = _counted_pair(cfg, dispatch, rng)
    plan = identity_plan(ROWS, COLS)
    counters = Counters.zero()

    on1 = decode(state, plan, params, batch)
    counters = fold(counters, state, plan)
    folds = fold_ledger._cache_size()
    off1 = decode(state, plan, params, batch)
    np.testing.assert_array_equal(np.asarray(on1), np.asarray(off1))

    # leaf-only swaps: new fault table, new plan, accumulated counters —
    # all three at once must reuse both compiled programs
    state2 = _state(4, seed=9, visible=True, pad_to=8)
    plan2 = RepairPlan(
        jnp.asarray(np.random.default_rng(1).permutation(COLS), jnp.int32),
        jnp.zeros((ROWS, COLS), bool),
    )
    on2 = decode(state2, plan2, params, batch)
    counters = fold(counters, state2, plan2)
    off2 = decode(state2, plan2, params, batch)
    np.testing.assert_array_equal(np.asarray(on2), np.asarray(off2))
    assert len(traces) == 1, "state/plan swap retraced the step"
    assert fold_ledger._cache_size() == folds, "counter/state/plan swap retraced the fold"

    h = counters.to_host()
    assert h["steps"] == 2
    assert h["protected_calls"] > 0
    assert h["total_elems"] > 0
    assert h["fault_elems"] > 0  # 3-4 visible faults mapped somewhere
    return h


def test_counters_bitexact_and_no_retrace_fast(rng):
    h = _assert_counters_bitexact("qwen1.5-0.5b", "twopass", rng)
    # faults <= capacity and identity-permutation plans: everything faulty
    # is DPPU-recomputed, nothing corrupts, nothing is pruned
    assert h["recomputed_elems"] == h["fault_elems"]
    assert h["corrupted_elems"] == 0 and h["pruned_elems"] == 0


@pytest.mark.slow
@pytest.mark.parametrize("dispatch", ["twopass", "fused"])
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_all_families_counters_bitexact(arch, dispatch, rng):
    _assert_counters_bitexact(arch, dispatch, rng)


# --------------------------------------------------------------------------- #
# event log: roundtrip, schema, derivations
# --------------------------------------------------------------------------- #
def test_eventlog_roundtrip_and_schema(tmp_path):
    log = EventLog()
    log.emit("scan.bist", confirmed=2)        # before the loop: step None
    log.step = 3
    log.emit("fault.injected", row=1, col=2, bit=30, val=1)
    log.step = 7
    log.emit("fault.suspect", row=1, col=2)
    log.emit("fault.confirmed", row=1, col=2)
    log.emit("chaos.injected", n=1, step=5)   # explicit step override
    path = tmp_path / "ev.jsonl"
    log.to_jsonl(str(path))
    assert validate_jsonl(str(path)) == 5

    back = EventLog.from_jsonl(str(path))
    assert [e.kind for e in back.events] == [e.kind for e in log.events]
    assert back.events[0].step is None
    assert back.events[-1].step == 5

    det = detection_records(back)
    assert det == [{
        "row": 1, "col": 2, "injected_step": 3, "suspect_step": 7,
        "confirmed_step": 7, "suspect_latency": 4, "latency": 4,
    }]


def test_schema_rejects_malformed_events(tmp_path):
    validate_event({"ts": 1.0, "step": None, "kind": "scan.bist",
                    "data": {"confirmed": 0}})
    with pytest.raises(ValueError, match="unknown event kind"):
        validate_event({"ts": 1.0, "step": 0, "kind": "not.a.kind", "data": {}})
    with pytest.raises(ValueError, match="missing required data field"):
        validate_event({"ts": 1.0, "step": 0, "kind": "fault.injected",
                        "data": {"row": 1}})
    with pytest.raises(ValueError, match="must be int"):
        validate_event({"ts": 1.0, "step": 0, "kind": "chaos.injected",
                        "data": {"n": "three"}})
    with pytest.raises(ValueError, match="must be bool"):
        validate_event({"ts": 1.0, "step": 0, "kind": "repair.plan",
                        "data": {"mode": "remap", "n_remapped": 1,
                                 "remapped_cols": [1], "quality_fraction": 1.0,
                                 "retrained": 1}})
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"ts": 1.0, "step": 0, "kind": "nope", "data": {}}\n')
    with pytest.raises(ValueError, match="bad.jsonl:1"):
        validate_jsonl(str(bad))


def test_repair_records_pair_remap_with_next_plan():
    log = EventLog()
    log.emit("fault.remapped", row=0, col=1, step=4)
    log.emit("fault.remapped", row=2, col=3, step=6)
    log.emit("repair.plan", step=5, mode="remap", n_remapped=1,
             remapped_cols=[1], quality_fraction=0.9, retrained=False)
    log.emit("repair.plan", step=6, mode="remap", n_remapped=2,
             remapped_cols=[1, 3], quality_fraction=0.8, retrained=False)
    recs = repair_records(log)
    assert [(r["remapped_step"], r["plan_step"], r["latency"]) for r in recs] \
        == [(4, 5, 1), (6, 6, 0)]
    assert latency_summary([r["latency"] for r in recs], "x")["x_mean_steps"] == 0.5
    assert latency_summary([], "x")["x_p95_steps"] is None


# --------------------------------------------------------------------------- #
# exporter
# --------------------------------------------------------------------------- #
def test_prometheus_text_format():
    txt = prometheus_text(
        {"steps": 10, "nested": {"a": 1.5}, "skip_me": None, "name": "x",
         "flag": True},
        labels={"arch": "m1"},
    )
    assert '# TYPE hyca_steps gauge\nhyca_steps{arch="m1"} 10' in txt
    assert 'hyca_nested_a{arch="m1"} 1.5' in txt
    assert 'hyca_flag{arch="m1"} 1' in txt
    assert "skip_me" not in txt and "name" not in txt


def test_prometheus_label_values_escaped():
    # exposition-format escapes: backslash first, then quote and newline —
    # a pathological arch name must still yield a parseable sample line
    txt = prometheus_text(
        {"steps": 1},
        labels={"arch": 'q"1.5\\b\nx', "ok": "plain"},
    )
    assert 'arch="q\\"1.5\\\\b\\nx"' in txt
    assert 'ok="plain"' in txt
    # raw specials must not survive unescaped inside the braces
    line = [l for l in txt.splitlines() if l.startswith("hyca_steps{")][0]
    assert "\n" not in line and '\\"' in line


def test_prometheus_names_sanitized():
    # metric names and label names must match [a-zA-Z_][a-zA-Z0-9_]* — a
    # leading digit gets a "_" prefix, invalid chars become "_"
    txt = prometheus_text({"2xx": 5, "lat-ms": 1.0}, prefix="9p", labels={"0bad": "v"})
    for line in txt.splitlines():
        if not line.startswith("#"):
            assert not line[0].isdigit(), line
    assert '_9p_2xx{_0bad="v"} 5' in txt
    assert '_9p_lat_ms{_0bad="v"} 1' in txt
    assert '_0bad="v"' in txt and "{0bad=" not in txt


def test_prometheus_list_leaves_export_count():
    # a list leaf exports its LENGTH as <name>_total instead of vanishing
    txt = prometheus_text({"injection_steps": [3, 7, 9], "empty": []})
    assert "hyca_injection_steps_total 3" in txt
    assert "hyca_empty_total 0" in txt


def test_write_metrics_out_creates_pair(tmp_path):
    log = EventLog()
    log.emit("scan.bist", confirmed=0)
    out = tmp_path / "deep" / "dir" / "m.jsonl"  # parents created
    path, prom = write_metrics_out(str(out), {"steps": 3}, log)
    assert validate_jsonl(path) == 1
    assert "hyca_steps 3" in pathlib.Path(prom).read_text()


# --------------------------------------------------------------------------- #
# ServingMetrics edge cases (satellite)
# --------------------------------------------------------------------------- #
def _rec(step, toks=1, scan_ok=None):
    return StepRecord(step=step, active_slots=1, effective_slots=2,
                      queue_depth=0, tokens_generated=toks, confirmed_faults=0,
                      true_faults=0, surviving_cols=4, scan_ok=scan_ok,
                      completed=0)


def _done(rid, tokens, reason="done"):
    return CompletedRequest(rid=rid, tokens=np.asarray(tokens, np.int32),
                            prompt_len=2, arrival_step=0, admitted_step=0,
                            first_token_step=1, finish_step=3, reason=reason)


def test_summary_zero_completions():
    m = ServingMetrics(n_slots=2, rows=4, cols=4)
    m.finish()
    s = m.summary()
    assert s["steps"] == 0 and s["tokens"] == 0 and s["goodput_tokens"] == 0
    assert s["requests_completed"] == 0
    assert s["ttft_mean_steps"] is None and s["ttft_p95_steps"] is None
    assert s["wall_s"] == 0.0  # never started: no phantom compile-time wall
    assert s["surviving_cols_final"] == 4
    assert s["scan_coverage"] == 0.0


def test_summary_scan_free_run():
    m = ServingMetrics(n_slots=2, rows=4, cols=4, steps_per_sweep=4)
    for i in range(6):
        m.record_step(_rec(i, scan_ok=None), [])
    m.finish()
    s = m.summary()
    assert s["scan_steps"] == 0 and s["scan_sweeps"] == 0.0
    assert s["scan_coverage"] == 0.0


def test_summary_scan_coverage_caps_at_one():
    m = ServingMetrics(n_slots=2, rows=4, cols=4, steps_per_sweep=4)
    for i in range(10):
        m.record_step(_rec(i, scan_ok=True), [])
    s = m.summary()
    assert s["scan_sweeps"] == 2.5
    assert s["scan_coverage"] == 1.0


def test_summary_reference_mismatch_goodput():
    m = ServingMetrics(n_slots=2, rows=4, cols=4)
    m.record_step(_rec(0, toks=6), [_done(0, [1, 2, 3]), _done(1, [4, 5, 6])])
    m.finish()
    assert m.summary()["goodput_tokens"] == 6
    ref = {0: np.asarray([1, 2, 3], np.int32),      # match
           1: np.asarray([4, 5, 9], np.int32)}      # corrupted output
    s = m.summary(ref)
    assert s["goodput_tokens"] == 3
    assert s["tokens"] == 6                          # throughput unchanged
    # a request absent from the reference cannot be verified -> not goodput
    assert m.summary({0: np.asarray([1, 2, 3], np.int32)})["goodput_tokens"] == 3


def test_wall_clock_starts_at_first_step_not_construction():
    fake = iter([100.0, 107.0]).__next__
    m = ServingMetrics(n_slots=2, rows=4, cols=4)
    import time as _time
    orig = _time.perf_counter
    _time.perf_counter = fake
    try:
        m.record_step(_rec(0), [])   # t0 = 100 — construction time irrelevant
        m.finish()                   # wall = 107 - 100
    finally:
        _time.perf_counter = orig
    assert m.wall_s == 7.0


def test_summary_latency_fields_none_without_detections():
    log = EventLog()
    m = ServingMetrics(n_slots=2, rows=4, cols=4, log=log)
    m.record_step(_rec(0), [])
    m.finish()
    s = m.summary()
    assert s["detections"] == 0 and s["injection_steps"] == []
    assert s["detect_latency_p95_steps"] is None
    assert s["sweeps_completed"] == 0


# --------------------------------------------------------------------------- #
# server integration: measured detection latency under deterministic chaos
# --------------------------------------------------------------------------- #
SRV = ServerConfig(arch="qwen1.5-0.5b", n_slots=2, smax=24, mode="protected",
                   rows=4, cols=4, dppu_size=2, scan_block=4, confirm_hits=2,
                   seed=0)


def _srv_trace(n=2):
    rng = np.random.default_rng(7)
    return [{"step": 0, "prompt": rng.integers(0, 512, size=3),
             "max_new_tokens": 10} for _ in range(n)]


def test_server_detection_latency_matches_injection_steps():
    srv = FaultTolerantServer(SRV)
    inject_at = 2

    def chaos(s):
        if s.step_idx == inject_at:
            s.injector.inject_at(1, 1, bit=30, val=1)
            s.log.emit("chaos.injected", n=1)

    summary = srv.run(_srv_trace(), max_steps=48, on_step=chaos)
    assert summary["injection_steps"] == [inject_at]
    assert summary["detections"] == 1
    # scan_block=rows probes the whole array every step: first hit at the
    # injection step, confirm (2 hits) exactly one step later
    assert summary["detect_latency_p50_steps"] == pytest.approx(
        summary["detect_latency_p95_steps"])
    lat = summary["detect_latency_mean_steps"]
    assert lat is not None and np.isfinite(lat)
    det = detection_records(srv.log)
    assert det[0]["confirmed_step"] - det[0]["injected_step"] == lat
    assert det[0]["injected_step"] == inject_at
    # the fault.injected event came from the injector, stamped by the cursor
    assert [e.step for e in srv.log.of_kind("fault.injected")] == [inject_at]


def test_server_counters_summary_and_events(tmp_path):
    srv = FaultTolerantServer(dataclasses.replace(SRV, counters=True))
    summary = srv.run(_srv_trace(1), max_steps=32)
    c = summary["counters"]
    assert c["steps"] == summary["steps"]
    assert c["protected_calls"] > 0
    assert c["fault_elems"] == 0          # no faults injected
    # the emitted log validates against the schema end to end
    p = tmp_path / "srv.jsonl"
    srv.log.to_jsonl(str(p))
    assert validate_jsonl(str(p)) == len(srv.log)
    kinds = {e.kind for e in srv.log.events}
    assert "server.start" in kinds and "scan.sweep" in kinds


def _lowered_steps(cfg):
    b = ModelBundle(cfg)
    cache = b.fresh_cache()
    return [b._step.lower(b.params, cache, feed, b.empty_state,
                          b.identity_plan).as_text()
            for feed in b.feed_shapes()]


def test_server_step_is_one_program_with_counters_on_or_off():
    """The counters fold beside the step, so turning them on changes
    nothing the step compiles: the same HLO for the token-only feed and
    the chunk feed."""
    cfg = dataclasses.replace(SRV, prefill_chunk=4)
    on = _lowered_steps(dataclasses.replace(cfg, counters=True))
    off = _lowered_steps(cfg)
    assert len(on) == len(off) == 2
    for a, b in zip(on, off):
        assert "jit__step" in a
        assert a == b


def test_server_counters_match_a_hand_fold_of_each_steps_ledger():
    """A chunked run's counters are, step for step, ledger_stats over the
    ledger of the feed that ran, with that step's fault state and plan."""
    srv = FaultTolerantServer(dataclasses.replace(SRV, prefill_chunk=2, counters=True))
    step_fn, seen = srv.bundle.step_fn, []

    def spy(params, cache, feed, fstate, plan):
        seen.append((isinstance(feed, dict), fstate, plan))
        return step_fn(params, cache, feed, fstate, plan)

    def chaos(s):
        if s.step_idx == 2:     # a fault the scan has yet to confirm
            s.injector.inject_at(1, 1, bit=30, val=1)

    srv.bundle.step_fn = spy
    summary = srv.run(_srv_trace(), max_steps=32, on_step=chaos)
    assert {chunked for chunked, _, _ in seen} == {True, False}
    tok_ledger, chunk_ledger = srv.bundle.ledgers
    assert tok_ledger != chunk_ledger
    want = Counters.zero()
    for chunked, fstate, plan in seen:
        ledger = chunk_ledger if chunked else tok_ledger
        want = ledger_stats(ledger, want, fstate, plan, srv.bundle.hyca)
    want = want.to_host()
    assert want["steps"] == summary["steps"] == len(seen)
    assert want["fault_elems"] > 0
    assert summary["counters"] == want == srv.counters_host()


def test_repair_events_view_over_log():
    cfg = dataclasses.replace(SRV, repair="remap", dppu_size=1,
                              max_remap_fraction=1.0)
    srv = FaultTolerantServer(cfg)

    def chaos(s):
        if s.step_idx == 1:
            for col in range(3):      # 3 faults > capacity 1 -> remap
                s.injector.inject_at(2, col, bit=30, val=1)
            s.log.emit("chaos.injected", n=3)

    srv.run(_srv_trace(), max_steps=48, on_step=chaos)
    evs = srv.repair_events
    assert evs and evs[0]["mode"] == "remap"
    assert evs[0]["step"] is not None
    assert set(evs[0]) >= {"step", "mode", "n_remapped", "remapped_cols",
                           "quality_fraction", "retrained"}
    assert len(repair_records(srv.log)) >= 1


# --------------------------------------------------------------------------- #
# fleet telemetry (satellite)
# --------------------------------------------------------------------------- #
@pytest.mark.slow
def test_fleet_summary_surfaces_obs_telemetry():
    from repro.core.campaign import ChaosSpec
    from repro.serving.fleet import FleetConfig, run_fleet

    cfg = FleetConfig(
        n_replicas=2, n_spares=1, steps=20, request_rate=0.3,
        chaos=ChaosSpec(per=0.08, at_step=3, seed=5),
        server=dataclasses.replace(SRV, repair="remap", dppu_size=1,
                                   max_remap_fraction=1.0),
    )
    out = run_fleet(cfg)
    assert out["chaos_injected"] > 0
    assert out["detections"] >= 1
    assert out["detect_latency_p50_steps"] is not None
    assert out["scan_sweeps_total"] > 0
    for ev in out["repair_event_log"]:
        assert ev["replica"] in (0, 1) and ev["mode"] == "remap"
    for rs in out["replica_summaries"]:
        assert rs["scan_steps"] > 0 and rs["events"] > 0
        assert rs["scan_sweeps"] == rs["scan_steps"]  # scan_block == rows


# --------------------------------------------------------------------------- #
# benchmark regression gate
# --------------------------------------------------------------------------- #
def _load_regress():
    path = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "regress.py"
    spec = importlib.util.spec_from_file_location("_obs_test_regress", path)
    mod = importlib.util.module_from_spec(spec)
    # dataclasses resolves string annotations through sys.modules
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_regress_passes_on_committed_baseline():
    regress = _load_regress()
    base = str(pathlib.Path(__file__).resolve().parents[1] / "experiments" / "bench")
    out = regress.diff_benchmarks(base, base)
    assert out["ok"]
    assert out["rows"], "no budgeted metrics found in committed baselines"
    assert all(r["ratio"] == 1.0 for r in out["rows"])


def test_regress_flags_synthetic_2x_regression(tmp_path):
    regress = _load_regress()
    base = pathlib.Path(__file__).resolve().parents[1] / "experiments" / "bench"
    d = json.loads((base / "ft_overhead.json").read_text())
    for rec in d["results"]:
        rec["twopass_overhead_x"] *= 2.0
    (tmp_path / "ft_overhead.json").write_text(json.dumps(d))
    out = regress.diff_benchmarks(str(base), str(tmp_path))
    assert not out["ok"]
    bad = [r for r in out["rows"] if not r["ok"]]
    assert bad and all(r["metric"] == "twopass_overhead_x" for r in bad)
    assert all(r["ratio"] == pytest.approx(2.0) for r in bad)
    # scan_latency absent from the current run is a note, not a failure
    assert any("scan_latency" in n for n in out["notes"])
    # CLI contract: exit 1, and 0 under --warn-only
    assert regress.main(["--baseline", str(base), "--current", str(tmp_path)]) == 1
    assert regress.main(["--baseline", str(base), "--current", str(tmp_path),
                         "--warn-only"]) == 0
