"""Program spans and site scopes (repro.obs.host):

  * a smoke server traced with ``jax.profiler`` shows every ``hyca.*`` phase
    of a step inside ``hyca.server.step``, the children covering >= 95 % of
    it, and the root carrying the step's load;
  * the collector hook opens and closes one span per collection and its
    counters rise; the exporter renders them;
  * named scopes change metadata only: the compiled decode step holds the
    same instructions with and without them, is still ``jit__step``, and
    protected serving still equals the fault-free run under the fused
    dispatch;
  * the request lifecycle in wall-clock seconds: span ``start_ts`` /
    ``end_ts`` and the summary's ``queue_wait_s_p90`` / ``ttft_s_p90``.
"""
import contextlib
import dataclasses
import gc
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.obs.events import EventLog
from repro.obs.export import gc_text
from repro.obs.host import install_gc_hook
from repro.obs.trace import request_traces, validate_span
from repro.serving import FaultTolerantServer, ModelBundle, ServerConfig
from repro.serving.metrics import ServingMetrics

CFG = ServerConfig(arch="qwen1.5-0.5b", n_slots=4, smax=32, mode="protected",
                   rows=4, cols=4, dppu_size=2, seed=0)
CHILDREN = {"hyca.fault.inject", "hyca.fault.scan", "hyca.fault.scan.sync",
            "hyca.fault.scan.probe", "hyca.sched.admit", "hyca.cache.reset",
            "hyca.decode.feed", "hyca.decode.dispatch", "hyca.decode.sample",
            "hyca.sched.commit", "hyca.metrics.record"}


def _host_events(tmp_path):
    (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                        for e in line.events if e.name.startswith("hyca.")]
    return out


def _covered(intervals, lo, hi):
    total, t = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, t), min(e, hi)
        if e > s:
            total += e - s
            t = e
    return total


@pytest.fixture(scope="module")
def bundle():
    return ModelBundle(CFG)


def test_server_step_spans_cover_the_step(bundle, tmp_path):
    srv = FaultTolerantServer(dataclasses.replace(CFG, fault_rate=0.05), bundle=bundle)
    rng = np.random.default_rng(0)
    for n_prompt, n_new in [(1, 1)] * 4 + [(3, 6)] * 4:
        srv.submit(rng.integers(0, 64, n_prompt), n_new)
    srv.step()          # compiles outside the trace; the first four finish
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(4):
        srv.step()
    jax.profiler.stop_trace()

    events = _host_events(tmp_path)
    roots = [e for e in events if e[0] == "hyca.server.step"]
    assert [r[3]["step"] for r in roots] == [1, 2, 3, 4]
    for r in roots:
        assert {"active", "positions", "tokens", "queue"} <= set(r[3])
        assert r[3]["positions"] >= r[3]["active"] > 0
    children = [e for e in events if e[0] not in ("hyca.server.step", "hyca.python.gc")]
    assert {e[0] for e in children} == CHILDREN
    for name, s, e, _ in children:
        assert any(r[1] <= s and e <= r[2] for r in roots), name
    for _, lo, hi, _ in roots:
        inside = [(s, e) for n, s, e, _ in events if n != "hyca.server.step" and lo <= s and e <= hi]
        assert _covered(inside, lo, hi) >= 0.95 * (hi - lo)


def test_gc_hook_spans_are_balanced_and_counters_rise(tmp_path):
    hook = install_gc_hook()
    assert install_gc_hook() is hook and gc.callbacks.count(hook) == 1
    before = hook.counters()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        gc.collect()
    jax.profiler.stop_trace()
    after = hook.counters()
    assert after["gc_collections_total"][2] >= before["gc_collections_total"][2] + 3
    assert after["gc_seconds_total"][2] > before["gc_seconds_total"][2]
    assert hook._open is None
    spans = [e for e in _host_events(tmp_path) if e[0] == "hyca.python.gc"]
    # every traced collection is one closed span; collections outside the
    # trace's start and stop are counted and not traced
    assert 3 <= len(spans) <= sum(after["gc_collections_total"]) - sum(before["gc_collections_total"])
    assert sum(e[3]["generation"] == 2 for e in spans) >= 3
    assert all(e[2] >= e[1] for e in spans)
    text = gc_text(labels={"arch": "x"})
    assert "# TYPE hyca_python_gc_seconds_total counter" in text
    assert re.search(r'hyca_python_gc_collections_total\{arch="x",generation="2"\} \d+', text)


def _instructions(text):
    return [re.sub(r",? metadata=\{[^}]*\}", "", line.strip()) for line in text.splitlines()
            if re.match(r"\s*(ROOT )?%\S+ = ", line)]


def _compiled_step(b):
    tok = jnp.zeros((b.cfg.n_slots, 1), jnp.int32)
    return b.step_fn.lower(b.params, b.fresh_cache(), tok, b.empty_state,
                           b.identity_plan).compile().as_text()


@pytest.mark.parametrize("dispatch", ["twopass", "fused"])
def test_scopes_change_metadata_only(dispatch, monkeypatch):
    cfg = dataclasses.replace(CFG, dispatch=dispatch)
    scoped = _compiled_step(ModelBundle(cfg))
    assert scoped.startswith("HloModule jit__step,")
    names = {c for s in re.findall(r'op_name="([^"]*)"', scoped) for c in s.split("/")}
    assert {"attn.qkv", "attn.out", "ffn", "head", "weights.cast"} <= names
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _compiled_step(ModelBundle(cfg))
    assert bare.startswith("HloModule jit__step,")
    assert _instructions(scoped) == _instructions(bare)


def test_fused_protected_equals_off_with_faults_within_capacity():
    cfg = dataclasses.replace(CFG, dispatch="fused")
    b = ModelBundle(cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, 4) for _ in range(4)]
    out = {}
    for mode in ("off", "protected"):
        srv = FaultTolerantServer(dataclasses.replace(cfg, mode=mode, bist=False), bundle=b)
        srv.injector.inject_n(cfg.hyca().capacity)
        srv.manager.bist()
        for p in prompts:
            srv.submit(p, 5)
        srv.run(max_steps=40)
        out[mode] = srv.completions_by_rid()
    assert out["off"].keys() == out["protected"].keys() and len(out["off"]) == 4
    for rid in out["off"]:
        np.testing.assert_array_equal(out["off"][rid], out["protected"][rid])


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_request_spans_and_summary_in_wall_clock_seconds():
    clock = _Clock()
    log = EventLog(clock=clock)
    for rid, (enq, adm, first, done) in enumerate([(0.0, 0.5, 1.0, 3.0), (0.0, 2.0, 4.0, 5.0)]):
        for kind, t, extra in (("request.enqueue", enq, {"prompt_len": 3}),
                               ("request.admit", adm, {"slot": rid}),
                               ("request.first_token", first, {}),
                               ("request.complete", done, {"reason": "done", "tokens": 2})):
            clock.t = 100.0 + t
            log.emit(kind, step=int(t * 10), rid=rid, **extra)
    log.events.sort(key=lambda e: e.ts)
    tr = request_traces(log)[1]
    spans = {s.name: s for s in tr.spans}
    assert (spans["request"].start_ts, spans["request"].end_ts) == (100.0, 105.0)
    assert (spans["queue"].start_ts, spans["queue"].end_ts) == (100.0, 102.0)
    assert (spans["prefill"].start_ts, spans["prefill"].end_ts) == (102.0, 104.0)
    assert (spans["decode"].start_ts, spans["decode"].end_ts) == (104.0, 105.0)
    validate_span(tr.root.to_json())
    with pytest.raises(ValueError, match="end_ts"):
        validate_span({**tr.root.to_json(), "end_ts": 99.0})

    m = ServingMetrics(2, 4, 4, log=log)
    s = m.summary()
    assert s["queue_wait_s_p90"] == pytest.approx(np.percentile([0.5, 2.0], 90))
    assert s["ttft_s_p90"] == pytest.approx(np.percentile([1.0, 4.0], 90))
    bare = ServingMetrics(2, 4, 4).summary()
    assert bare["queue_wait_s_p90"] is None and bare["ttft_s_p90"] is None
