"""The harness's arithmetic on synthetic step times and requests, the
schedule's determinism, and discovery of cells and metrics by name."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import arrivals, harness, layer_metrics  # noqa: E402
from bench.harness import Record, ReqRec  # noqa: E402


def _record(reqs, *, step_s=0.1, n=20, k_open=4, k_close=14, follow_s=1.0, tokens=None):
    """Steps end every ``step_s`` from t = 0.1; the window is (0.5, 1.5]."""
    ends = step_s * (1 + np.arange(n))
    return Record(t_start=0.0, step_end=ends,
                  step_tokens=np.asarray(tokens if tokens is not None else np.ones(n, int)),
                  step_queue=np.zeros(n, int),
                  k_open=k_open, k_close=k_close, reqs={r.rid: r for r in reqs},
                  follow_s=follow_s)


def _req(rid, due, first=None, last=None, reason="done", n_tok=None):
    toks = None if n_tok is None else np.zeros(n_tok, np.int32)
    return ReqRec(rid, due, np.zeros(4, np.int32), 8, first, last, toks, reason)


def test_ttft_runs_from_the_due_time():
    rec = _record([_req(0, 0.52, first=7, last=9), _req(1, 0.3, first=6, last=9),
                   _req(2, 1.49, first=19, last=19)])
    ttft, missed = harness.ttft_values(rec)
    # request 1 was due before the window opened and is not counted
    np.testing.assert_allclose(sorted(ttft), [0.8 - 0.52, 2.0 - 1.49])
    assert missed == 0


def test_failed_and_late_requests_are_misses_at_the_cap():
    rec = _record([_req(0, 0.6, first=None, last=None, reason=None),
                   _req(1, 0.7, first=None, reason="dropped"),
                   _req(2, 0.8, first=9, last=9)], follow_s=0.3)
    ttft, missed = harness.ttft_values(rec)
    cap = 1.5 + 0.3
    np.testing.assert_allclose(sorted(ttft), sorted([cap - 0.6, cap - 0.7, 1.0 - 0.8]))
    assert missed == 2
    assert harness.percentile_nearest_rank(ttft, 90) == pytest.approx(cap - 0.6)


def test_inter_token_gaps_pooled_over_requests_inside_the_window():
    ends = [0.1, 0.2, 0.3, 0.4, 0.5, 0.65, 0.7, 0.9, 1.0, 1.05, 1.5, 1.6]
    rec = Record(0.0, np.asarray(ends), np.ones(12, int), np.zeros(12, int), 4, 10,
                 {0: _req(0, 0.0, first=2, last=6), 1: _req(1, 0.0, first=5, last=11),
                  2: _req(2, 0.0, first=None, reason=None)})
    gaps = np.sort(harness.itl_gaps(rec))
    # request 0: steps 3..6, of which 5 and 6 end in the window (0.5, 1.5]
    # request 1: steps 6..11, of which 6..10 end in the window
    want = sorted([0.15, 0.05] + [0.05, 0.2, 0.1, 0.05, 0.45])
    np.testing.assert_allclose(gaps, want)
    assert harness.percentile_nearest_rank(gaps, 95) == pytest.approx(0.45)


def test_only_tokens_of_steps_ending_in_the_window_count():
    toks = np.arange(20)
    rec = _record([], tokens=toks)
    assert harness.window_tokens(rec) == int(toks[5:15].sum())
    m = harness.end_to_end(rec, setup_s=3.0)
    assert m["out_tok_s"]["value"] == pytest.approx(toks[5:15].sum() / 1.0)
    assert m["setup_s"] == {"value": 3.0, "unit": "s"}
    assert "ttft_p90_s" not in m and "itl_p95_ms" not in m


TRAFFIC = {"loop": "open", "rate_rps": 5.0, "order_seed": 7, "block": 16,
           "prompt": {"median": 40, "sigma": 0.8, "min": 8, "max": 160},
           "output": {"median": 30, "sigma": 0.6, "min": 8, "max": 96}}


def _take(traffic, seed, n=48):
    s = arrivals.Schedule(traffic, 1000, seed)
    return [s.next() for _ in range(n)]


def test_open_loop_schedule_is_fixed_by_the_mix_and_tokens_by_the_seed():
    a, b, c = _take(TRAFFIC, 5), _take(TRAFFIC, 5), _take(TRAFFIC, 2**33 + 1)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new_tokens) for x in c]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    # every block of 16 holds each stratified length once
    plens = sorted(len(x.prompt) for x in a[:16])
    assert plens == sorted(arrivals.lognormal_quantiles(40, 0.8, 8, 160, 16))
    dues = np.array([x.due_s for x in a])
    assert np.all(np.diff(dues) > 0)
    assert dues[15] == pytest.approx(arrivals.exponential_quantiles(5.0, 16).sum())


def test_backlog_schedule_has_no_arrival_times():
    reqs = _take(dict(TRAFFIC, loop="backlog"), 3, 20)
    assert all(r.due_s == 0.0 for r in reqs)


def _mini_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A checkout holding the repository's own BENCHMARK.json, bench data
    files and model families, to which a test adds new files."""
    (tmp_path / "bench").mkdir()
    for sub in ("configs", "traffic", "limits", "metrics", "families"):
        (tmp_path / "bench" / sub).mkdir()
        for f in (ROOT / "bench" / sub).glob("*"):
            if f.is_file():
                (tmp_path / "bench" / sub / f.name).write_bytes(f.read_bytes())
    (tmp_path / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    return tmp_path


def test_new_traffic_and_metric_files_are_found_by_name(tmp_path):
    root = _mini_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    bench = json.loads((root / "BENCHMARK.json").read_text())
    # a later PR adds a mix, a cell and a metric: new files and new entries only
    (root / "bench" / "traffic" / "decode_heavy.json").write_text(
        (root / "bench" / "traffic" / "batch.json").read_text())
    (root / "bench" / "limits" / "starcoder2-3b.decode_heavy.protected.json").write_text(
        (root / "bench" / "limits" / "starcoder2-3b.batch.protected.json").read_text())
    (root / "bench" / "metrics" / "steps_seen.decode.py").write_text(
        "def read(ctx):\n    return float(len(ctx.step_load))\n")
    bench["workloads"].append({"name": "starcoder2-3b.decode_heavy.protected",
                               "config": "starcoder2-3b", "traffic": "decode_heavy",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_seen.decode", "unit": "steps", "better": "higher",
                               "source": "program_counter", "layer": "server loop",
                               "moves": "out_tok_s",
                               "workloads": ["starcoder2-3b.decode_heavy.protected"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("starcoder2-3b.decode_heavy.protected", root=root)
    assert cell.traffic == json.loads((ROOT / "bench" / "traffic" / "batch.json").read_text())
    assert [m["name"] for m in cell.per_layer] == ["steps_seen.decode"]
    ctx = layer_metrics.Context(None, cell.config, None, [(1, 2)] * 3)
    assert layer_metrics.read("steps_seen.decode", ctx, root / "bench" / "metrics") == 3.0
    after = {p: p.read_bytes() for p in before}
    assert after == before                       # no existing file was edited


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.per_layer and cell.end_to_end
        for m in cell.per_layer:
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(cell.limits["compare"]) == {"max_gap"}
