"""PR-10 observability acceptance tests — spans, series, replay, consumers.

The contract (ISSUE 10):

  * **lifecycle spans** correlate the event log by entity: request traces
    (enqueue -> queue -> prefill -> decode -> completion) and fault traces
    (inject -> undetected -> suspect -> repair), with deterministic
    content-addressed ids, schema validation, and latency attributes that
    agree EXACTLY with ``ServingMetrics.summary()`` — both reuse
    ``detection_records`` / ``repair_records``;
  * **series**: the :class:`SeriesBuffer` ring rides the jitted vfleet
    program with zero host sync on the write path — series-on is BIT-EXACT
    with series-off on every shared report key, retrace-free across
    fault-rate / chaos swaps, and the vfleet per-tick rows match the legacy
    engine's host-side StepRecords on the pinned parity configs; the
    server's series is read from its own StepRecords, and harvesting it
    changes nothing the server reports;
  * **consumers**: the replay CLI joins events + series into an incident
    timeline whose latencies equal the summary's; Prometheus histograms
    carry cumulative buckets; the stdlib /metrics endpoint scrapes live;
  * **satellites**: metric-name collision dedupe, the
    ``slo_attainment_defined`` companion gauge, and TTFT's full
    ``latency_summary`` treatment.
"""
import dataclasses
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.obs.events import EventLog, latency_summary
from repro.obs.export import (
    histogram_text,
    histograms_text,
    prometheus_text,
    write_metrics_out,
)
from repro.obs.httpd import MetricsServer
from repro.obs.replay import build_timeline, render_text
from repro.obs.replay import main as replay_main
from repro.obs.schema import validate_jsonl
from repro.obs.series import SeriesBuffer, load_series, save_series
from repro.obs.trace import (
    build_traces,
    fault_traces,
    request_traces,
    span_id,
    trace_id,
    validate_span,
    validate_spans_jsonl,
    write_spans,
)
from repro.obs.trace import main as trace_main
from repro.serving.metrics import ServingMetrics
from repro.serving.server import FaultTolerantServer, ServerConfig
from repro.serving.vfleet import _TRACES, run_vfleet

from test_vfleet import PARITY_POOL, PARITY_REGION


# --------------------------------------------------------------------------- #
# span derivation over a synthetic log
# --------------------------------------------------------------------------- #
def _request_log(complete=True, reason="done", admit=True):
    log = EventLog()
    log.emit("request.enqueue", step=2, rid=7, prompt_len=5)
    if admit:
        log.emit("request.admit", step=4, rid=7, slot=1)
        log.emit("request.first_token", step=6, rid=7)
    if complete:
        log.emit("request.complete", step=11, rid=7, reason=reason, tokens=5)
    return log


def test_request_trace_structure():
    (tr,) = request_traces(_request_log())
    assert tr.entity == "request:7"
    assert [s.name for s in tr.spans] == ["request", "queue", "prefill", "decode"]
    root, queue, prefill, decode = tr.spans
    assert root.parent_span_id is None
    assert all(s.parent_span_id == root.span_id for s in tr.spans[1:])
    assert (root.start_step, root.end_step) == (2, 11)
    assert (queue.start_step, queue.end_step) == (2, 4)
    assert (prefill.start_step, prefill.end_step) == (4, 6)
    assert (decode.start_step, decode.end_step) == (6, 11)
    assert root.status == "ok"
    assert root.attributes["ttft_steps"] == 4
    assert root.attributes["tokens"] == 5
    assert prefill.attributes["slot"] == 1
    assert decode.duration_steps == 5


def test_request_trace_statuses():
    (expired,) = request_traces(_request_log(reason="expired", admit=False))
    assert expired.root.status == "error"
    assert [s.name for s in expired.spans] == ["request", "queue"]
    # queue span inherits the death: the request died waiting
    assert expired.spans[1].status == "error"
    assert expired.spans[1].end_step == 11
    (open_tr,) = request_traces(_request_log(complete=False))
    assert open_tr.root.status == "open"
    assert open_tr.root.end_step is None


def test_span_ids_deterministic_and_distinct():
    a = request_traces(_request_log())[0]
    b = request_traces(_request_log())[0]
    assert a.trace_id == b.trace_id == trace_id("request:7")
    assert {s.span_id for s in a.spans} == {s.span_id for s in b.spans}
    assert len({s.span_id for s in a.spans}) == len(a.spans)
    assert a.root.span_id == span_id(a.trace_id, "request")
    assert trace_id("request:8") != a.trace_id


def _fault_log():
    log = EventLog()
    log.emit("fault.injected", step=3, row=1, col=2, bit=30, val=1)
    log.emit("fault.suspect", step=5, row=1, col=2)
    log.emit("fault.confirmed", step=6, row=1, col=2)
    log.emit("fault.remapped", step=6, row=1, col=2)
    log.emit("repair.plan", step=8, mode="remap", n_remapped=1,
             remapped_cols=[2], quality_fraction=0.9, retrained=False)
    return log


def test_fault_trace_latencies_match_event_records():
    (tr,) = fault_traces(_fault_log())
    assert tr.entity == "fault:1:2"
    assert [s.name for s in tr.spans] == ["fault", "undetected", "suspect", "repair"]
    assert tr.root.attributes["detect_latency"] == 3      # 6 - 3
    assert tr.root.attributes["suspect_latency"] == 2     # 5 - 3
    assert tr.root.attributes["repair_latency"] == 2      # 8 - 6
    undet = tr.spans[1]
    assert (undet.start_step, undet.end_step) == (3, 5)
    repair = tr.spans[3]
    assert (repair.start_step, repair.end_step) == (6, 8)
    assert tr.root.end_step == 8


def test_validate_span_rejects_malformed():
    (tr,) = request_traces(_request_log())
    good = tr.root.to_json()
    validate_span(good)
    for mutate, match in [
        ({"trace_id": "xyz"}, "32 lowercase hex"),
        ({"span_id": good["span_id"][:-1]}, "16 lowercase hex"),
        ({"status": "weird"}, "status"),
        ({"start_step": 99}, "end_step"),
        ({"attributes": []}, "attributes"),
        ({"name": ""}, "name"),
    ]:
        with pytest.raises(ValueError, match=match):
            validate_span({**good, **mutate})


def test_span_jsonl_roundtrip_and_cli(tmp_path, capsys):
    log = _request_log()
    log.events.extend(_fault_log().events)
    events = tmp_path / "ev.jsonl"
    log.to_jsonl(str(events))
    assert validate_jsonl(str(events)) == len(log.events)

    spans = tmp_path / "spans.jsonl"
    n = write_spans(str(spans), build_traces(log))
    assert n == 8 and validate_spans_jsonl(str(spans)) == 8
    # CLI: derive then check
    out2 = tmp_path / "cli.spans.jsonl"
    assert trace_main([str(events), "-o", str(out2)]) == 0
    assert out2.read_text() == spans.read_text()
    assert trace_main(["--check", str(out2)]) == 0
    # a corrupted line fails --check
    out2.write_text(out2.read_text().replace('"ok"', '"weird"', 1))
    assert trace_main(["--check", str(out2)]) == 1
    assert "FAIL" in capsys.readouterr().err


# --------------------------------------------------------------------------- #
# SeriesBuffer ring semantics
# --------------------------------------------------------------------------- #
def test_series_ring_wrap_and_harvest():
    buf = SeriesBuffer.create(4, {"x": ((), np.int32)})
    for i in range(6):
        buf = buf.record({"x": i})
    assert buf.written == 6 and buf.capacity == 4
    got = buf.harvest(start=2)
    np.testing.assert_array_equal(got["x"], [2, 3, 4, 5])
    with pytest.raises(ValueError, match="capacity"):
        buf.harvest(start=0)          # rows 0-1 overwritten
    with pytest.raises(ValueError, match="past cursor"):
        buf.harvest(start=9)


def test_series_channel_mismatch_is_an_error():
    buf = SeriesBuffer.create(2, {"x": ((), np.int32)})
    with pytest.raises(ValueError, match="channels mismatch"):
        buf.record({"y": np.int32(1)})


def test_series_save_load_roundtrip(tmp_path):
    buf = SeriesBuffer.create(8, {"x": ((3,), np.float32)})
    for i in range(5):
        buf = buf.record({"x": np.full(3, i, np.float32)})
    path = save_series(str(tmp_path / "s"), buf.harvest(),
                       meta={"start_step": 2})
    assert path.endswith(".npz")
    series, meta = load_series(path)
    assert meta["start_step"] == 2 and meta["length"] == 5
    assert meta["channels"] == ["x"]
    np.testing.assert_array_equal(series["x"], np.asarray(buf.harvest()["x"]))


# --------------------------------------------------------------------------- #
# vfleet series: retrace-free, bit-exact, StepRecord parity
# --------------------------------------------------------------------------- #
def test_vfleet_series_bitexact_and_no_retrace():
    cfg_on = dataclasses.replace(PARITY_POOL, series=True)
    rep_off = run_vfleet(PARITY_POOL)
    rep_on = run_vfleet(cfg_on)
    # telemetry must not perturb the simulation: every shared key bit-exact
    diffs = {k: (rep_off[k], rep_on[k]) for k in rep_off
             if k != "sim_wall_s" and rep_off[k] != rep_on[k]}
    assert not diffs, f"series-on diverged: {diffs}"
    s = rep_on["series"]
    assert s["tokens"].shape == (PARITY_POOL.steps, PARITY_POOL.n_replicas)
    assert int(s["tokens"].sum()) == rep_on["goodput_tokens"]
    # fault-rate / chaos swaps are traced leaves: zero new traces with the
    # series carried (the test_ftcontext _TRACES idiom)
    n0 = len(_TRACES)
    for i, rate in enumerate((0.01, 0.05)):
        run_vfleet(dataclasses.replace(cfg_on, fault_rate=rate, seed=i))
    run_vfleet(dataclasses.replace(
        cfg_on, chaos=dataclasses.replace(cfg_on.chaos, per=0.6, at_step=4)))
    assert len(_TRACES) == n0, "series-on sweep retraced the chunk program"


# per-tick channel -> legacy StepRecord field; both capture post-admission,
# pre-retirement state each step
_CHANNEL_TO_RECORD = {
    "tokens": "tokens_generated",
    "queue_depth": "queue_depth",
    "active": "active_slots",
    "confirmed": "confirmed_faults",
    "effective_slots": "effective_slots",
    "true_faults": "true_faults",
    "surviving_cols": "surviving_cols",
}


@pytest.mark.parametrize("cfg", [PARITY_POOL,
                                 pytest.param(PARITY_REGION, marks=pytest.mark.slow)],
                         ids=["pool-1class", "region-2class"])
def test_vfleet_series_matches_legacy_step_records(cfg):
    from repro.serving.fleet import run_fleet

    legacy = run_fleet(dataclasses.replace(cfg, record_steps=True))
    vec = run_vfleet(dataclasses.replace(cfg, series=True))
    series = vec["series"]
    mismatches = []
    for i, records in enumerate(legacy["step_records"]):
        for rec in records:
            for ch, field in _CHANNEL_TO_RECORD.items():
                got = int(series[ch][rec["step"], i])
                want = int(rec[field])
                if got != want:
                    mismatches.append((i, rec["step"], ch, got, want))
    n = sum(len(r) for r in legacy["step_records"]) * len(_CHANNEL_TO_RECORD)
    assert not mismatches, f"{len(mismatches)}/{n}: {mismatches[:8]}"
    assert n > 0


# --------------------------------------------------------------------------- #
# server series + replay timeline on a pinned chaos serve
# --------------------------------------------------------------------------- #
SRV = ServerConfig(arch="qwen1.5-0.5b", n_slots=2, smax=24, mode="protected",
                   rows=4, cols=4, dppu_size=1, scan_block=4, confirm_hits=2,
                   repair="remap", max_remap_fraction=1.0, seed=0)


def _chaos(s):
    if s.step_idx == 2:
        for col in range(3):          # 3 faults > DPPU capacity 1 -> remap
            s.injector.inject_at(1, col, bit=30, val=1)
        s.log.emit("chaos.injected", n=3)


def _trace(n=3):
    rng = np.random.default_rng(7)
    return [{"step": 0, "prompt": rng.integers(0, 512, size=3),
             "max_new_tokens": 8} for _ in range(n)]


def _run_traced():
    srv = FaultTolerantServer(SRV)
    summary = srv.run(_trace(), max_steps=40, on_step=_chaos)
    return srv, summary


def test_server_series_matches_step_records():
    srv, summary = _run_traced()
    series = srv.series_host()
    recs = srv.metrics.steps
    assert len(series["tokens"]) == len(recs) == summary["steps"]
    for ch, field in _CHANNEL_TO_RECORD.items():
        got = series[ch].tolist()
        want = [int(getattr(r, field)) for r in recs]
        assert got == want, f"channel {ch} diverges from StepRecords"


def test_server_series_ring_keeps_tail():
    srv = FaultTolerantServer(dataclasses.replace(SRV, series_capacity=8))
    srv.run(_trace(), max_steps=40, on_step=_chaos)
    n = len(srv.metrics.steps)
    start = srv.series_start_step()
    assert start == n - 8
    series = srv.series_host()
    want = [r.tokens_generated for r in srv.metrics.steps[start:]]
    assert series["tokens"].tolist() == want


def test_server_series_off_is_bitexact():
    """A run whose series is harvested every step gives the summary of one
    whose series is never read."""
    _, off = _run_traced()

    def chaos_and_harvest(s):
        _chaos(s)
        s.series_host()

    srv_on = FaultTolerantServer(SRV)
    on = srv_on.run(_trace(), max_steps=40, on_step=chaos_and_harvest)
    # wall-clock readings differ between any two runs
    skip = {"wall_s", "tokens_per_s", "queue_wait_s_p90", "ttft_s_p90"}
    diffs = {k: (off[k], on[k]) for k in off
             if k not in skip and off[k] != on[k]}
    assert not diffs, f"series-harvesting server diverged: {diffs}"


def test_replay_timeline_latencies_match_summary_exactly():
    srv, summary = _run_traced()
    tl = build_timeline(srv.log, srv.series_host(),
                        start_step=srv.series_start_step())
    # the acceptance criterion: replay latencies == event-derived summary
    for k in ("detect_latency_mean_steps", "detect_latency_p50_steps",
              "detect_latency_p95_steps", "suspect_latency_mean_steps",
              "repair_latency_mean_steps", "repair_latency_p50_steps"):
        assert tl[k] == summary[k], k
    assert tl["detections"] == summary["detections"] >= 1
    (inc,) = tl["incidents"]
    assert inc["injected_step"] == 2 and inc["n_injected"] == 3
    assert inc["first_confirmed_step"] is not None
    assert inc["detect_latency_mean_steps"] == summary["detect_latency_mean_steps"]
    assert inc["repair_plan_step"] is not None
    # capacity trajectory joined from the series
    assert inc["capacity_pre"] is not None
    assert inc["capacity_trough"] <= inc["capacity_pre"]
    assert inc["quality_trough"] is not None
    text = render_text(tl)
    assert "incident @ step 2" in text and "repair" in text


def test_replay_cli_joins_artifacts(tmp_path, capsys):
    srv, _ = _run_traced()
    events = tmp_path / "ev.jsonl"
    srv.log.to_jsonl(str(events))
    assert validate_jsonl(str(events)) == len(srv.log.events)
    npz = save_series(str(tmp_path / "series"), srv.series_host(),
                      meta={"start_step": srv.series_start_step()})
    out = tmp_path / "tl.json"
    assert replay_main([str(events), "--series", npz, "-o", str(out)]) == 0
    stdout = capsys.readouterr().out
    assert "incident @ step 2" in stdout and "3 injected" in stdout
    tl = json.loads(out.read_text())
    assert tl["incidents"][0]["injected_step"] == 2
    assert tl["series_rows"] > 0
    assert replay_main([str(tmp_path / "missing.jsonl")]) == 1


def test_replay_fleet_series_replica_aggregation():
    rep = run_vfleet(dataclasses.replace(PARITY_POOL, series=True))
    # no EventLog in the vectorized engine: an empty log still yields the
    # series-side view (sum over replicas for counts)
    tl = build_timeline(EventLog(), rep["series"])
    assert tl["series_rows"] == PARITY_POOL.steps
    tl_one = build_timeline(EventLog(), rep["series"], replica=0)
    assert tl_one["series_rows"] == PARITY_POOL.steps
    assert tl["incidents"] == []


# --------------------------------------------------------------------------- #
# consumers: histograms, collision dedupe, slo gauge, /metrics endpoint
# --------------------------------------------------------------------------- #
def test_histogram_text_cumulative_buckets():
    text = histogram_text("lat", [1, 3, 100], buckets=(2.0, 64.0))
    lines = text.strip().splitlines()
    assert lines[0] == "# TYPE hyca_lat histogram"
    assert lines[1] == 'hyca_lat_bucket{le="2"} 1'
    assert lines[2] == 'hyca_lat_bucket{le="64"} 2'
    assert lines[3] == 'hyca_lat_bucket{le="+Inf"} 3'
    assert lines[4] == "hyca_lat_sum 104"
    assert lines[5] == "hyca_lat_count 3"
    empty = histogram_text("lat", [], buckets=(2.0,))
    assert 'le="2"} 0' in empty and "hyca_lat_count 0" in empty


def test_histograms_text_sorted_and_labelled():
    text = histograms_text({"b": [1], "a": [2]}, labels={"arch": "q"})
    assert text.index("hyca_a_") < text.index("hyca_b_")
    assert 'hyca_a_bucket{arch="q",le="1"} 0' in text


def test_prometheus_collision_dedupe():
    text = prometheus_text({"a": {"b": 1.0}, "a_b": 2.0})
    assert "# TYPE hyca_a_b gauge" in text
    assert "# TYPE hyca_a_b_2 gauge" in text
    assert "hyca_a_b 1" in text and "hyca_a_b_2 2" in text
    names = [l.split()[0] for l in text.splitlines() if not l.startswith("#")]
    assert len(names) == len(set(names))
    # deterministic across renders
    assert text == prometheus_text({"a": {"b": 1.0}, "a_b": 2.0})


def test_slo_attainment_defined_companion_gauge():
    m = ServingMetrics(n_slots=2, rows=4, cols=4)
    summary = m.summary()
    assert summary["slo_attainment"] is None
    assert summary["slo_attainment_defined"] is False
    text = prometheus_text(summary)
    assert "hyca_slo_attainment " not in text      # None has no gauge
    assert "hyca_slo_attainment_defined 0" in text


def test_ttft_gets_full_latency_summary():
    srv, summary = _run_traced()
    ttft = srv.metrics.ttft_steps()
    assert ttft
    assert summary["ttft_mean_steps"] == float(np.mean(ttft))
    assert summary["ttft_p50_steps"] == float(np.percentile(ttft, 50))
    assert summary["ttft_p95_steps"] == float(np.percentile(ttft, 95))
    assert summary == {**summary, **latency_summary(ttft, "ttft")}
    lists = srv.metrics.latency_lists()
    assert lists["ttft_steps"] == ttft
    assert lists["detect_latency_steps"]
    assert lists["repair_latency_steps"]


def test_write_metrics_out_appends_histograms(tmp_path):
    srv, summary = _run_traced()
    path, prom = write_metrics_out(
        str(tmp_path / "m.jsonl"), summary, srv.log,
        histograms=srv.metrics.latency_lists())
    text = (tmp_path / "m.jsonl.prom").read_text()
    assert "hyca_ttft_steps_bucket" in text
    assert "hyca_detect_latency_steps_count" in text
    assert "hyca_slo_attainment_defined" in text


def test_metrics_httpd_scrape():
    state = {"text": "hyca_x 1\n", "boom": False}

    def supplier():
        if state["boom"]:
            raise RuntimeError("exporter broke")
        return state["text"]

    with MetricsServer(supplier) as srv:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        resp = urllib.request.urlopen(url, timeout=5)
        assert resp.status == 200
        assert resp.read().decode() == "hyca_x 1\n"
        assert resp.headers["Content-Type"].startswith("text/plain")
        state["text"] = "hyca_x 2\n"      # live: re-rendered per scrape
        assert urllib.request.urlopen(url, timeout=5).read() == b"hyca_x 2\n"
        with pytest.raises(urllib.error.HTTPError) as e404:
            urllib.request.urlopen(url.replace("/metrics", "/nope"), timeout=5)
        assert e404.value.code == 404
        state["boom"] = True
        with pytest.raises(urllib.error.HTTPError) as e500:
            urllib.request.urlopen(url, timeout=5)
        assert e500.value.code == 500
        assert b"exporter broke" in e500.value.read()
    with pytest.raises(RuntimeError, match="not started"):
        MetricsServer(supplier).port


# --------------------------------------------------------------------------- #
# end-to-end: spans from a real serve agree with the summary
# --------------------------------------------------------------------------- #
def test_serve_spans_agree_with_summary(tmp_path):
    srv, summary = _run_traced()
    traces = build_traces(srv.log)
    req = [t for t in traces if t.entity.startswith("request:")]
    flt = [t for t in traces if t.entity.startswith("fault:")]
    assert req and flt
    # span-side TTFT equals the metrics-side list (same requests)
    span_ttft = sorted(t.root.attributes["ttft_steps"] for t in req
                       if "ttft_steps" in t.root.attributes)
    assert span_ttft == sorted(srv.metrics.ttft_steps())
    # span-side detect latencies reproduce the summary mean exactly
    lats = [t.root.attributes["detect_latency"] for t in flt
            if t.root.attributes["detect_latency"] is not None]
    assert float(np.mean(lats)) == summary["detect_latency_mean_steps"]
    path = tmp_path / "spans.jsonl"
    assert write_spans(str(path), traces) == validate_spans_jsonl(str(path))
