"""The readers of the program's own spans and scopes (``bench/program_trace.py``)
on a synthetic trace built here, and the search for the run's trace file."""
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from bench import layer_metrics, program_trace, trace_reduce  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402
from bench.program_trace import ProgramTrace, ScopedOp  # noqa: E402
from bench.trace_reduce import Event, Trace  # noqa: E402

MS = 1e-3
KERNEL = ('%ft_matmul.{} = f32[64,1024] custom-call(s32[1024] %m, f32[64,1024] %x, '
          'f32[1024,1024] %w), custom_call_target="tpu_custom_call"')
BODY = "jit(_step)/while/body/closed_call/"
READERS = ("unattributed_idle_ms_per_step.batch", "scan_sync_ms_per_step.batch",
           "gc_ms_per_step.batch", "ft_wrap_device_ms.batch", "weight_cast_device_ms.batch")


def _trace():
    """Two 9 ms server steps 10 ms apart.  Per step, on the host: the scan
    (0-1 ms, readbacks 0-0.4 and 0.8-1), feed, dispatch, sample (2-7.5),
    commit, nothing at 8-8.5, record; a 0.2 ms collection in the first.
    On the device: the decode step 2-6 ms (weight cast 1 ms, then a loop
    whose body holds an ffn convert 0.5 ms, the kernel 1.5 ms, an ffn slice
    0.25 ms and an unscoped add), and an argmax 7-7.5 ms."""
    bench, spans, ops, modules = [], [], [], []
    for i, t0 in enumerate((0.0, 10 * MS)):
        def at(a, b):
            return t0 + a * MS, (b - a) * MS
        bench.append(Event("bench.step", *at(0, 9)))
        spans += [Event(n, *at(a, b)) for n, a, b in (
            ("hyca.server.step", 0, 9), ("hyca.fault.scan", 0, 1),
            ("hyca.fault.scan.sync", 0, 0.4), ("hyca.fault.scan.probe", 0.4, 0.8),
            ("hyca.fault.scan.sync", 0.8, 1), ("hyca.decode.feed", 1, 1.5),
            ("hyca.decode.dispatch", 1.5, 2), ("hyca.decode.sample", 2, 7.5),
            ("hyca.sched.commit", 7.5, 8), ("hyca.metrics.record", 8.5, 9))]
        if i == 0:
            spans.append(Event("hyca.python.gc", *at(8.6, 8.8)))
        modules += [Event("jit__step(1)", *at(2, 6)), Event("jit__argmax(2)", *at(7, 7.5))]
        ops += [ScopedOp("%convert_element_type.1 = bf16[] convert()", *at(2, 3),
                         "jit(_step)/weights.cast/convert_element_type"),
                ScopedOp("%while.2 = () while()", *at(3, 6), "jit(_step)/while"),
                ScopedOp("%convert_element_type.3 = f32[] convert()", *at(3, 3.5),
                         BODY + "ffn/convert_element_type"),
                ScopedOp(KERNEL.format(i), *at(3.5, 5), BODY + "ffn/jit(ft_matmul)/ft_matmul/pallas_call"),
                ScopedOp("%slice.4 = f32[] slice()", *at(5, 5.25), BODY + "ffn/slice"),
                ScopedOp("%add.5 = f32[] add()", *at(5.25, 6), BODY + "add"),
                ScopedOp("%reduce.6 = s32[] reduce()", *at(7, 7.5), "jit(argmax)/argmax")]
    (window,) = trace_reduce.windows(Trace(ops={"/device:TPU:0": ops},
                                           modules={"/device:TPU:0": modules}, spans=bench))
    pt = ProgramTrace(first_step=bench[0].start, spans=sorted(spans, key=lambda e: e.start), ops=ops)
    return window, pt


@pytest.fixture
def tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(program_trace, "_cache", {})
    return tmp_path


def _register(tmp, pt, name="bench-trace-a"):
    path = tmp / name / "plugins" / "profile" / "t" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    program_trace._cache[str(path)] = pt
    return path


def _ctx(window):
    return layer_metrics.Context(window, {}, PEAKS["TPU v5 lite"], [])


def test_readers_on_a_synthetic_trace(tmp):
    window, pt = _trace()
    _register(tmp, pt)
    got = {name: layer_metrics.read(name, _ctx(window)) for name in READERS}
    # idle in a step: 0-2, 6-7 and 7.5-9 ms; under no child span only 8-8.5
    assert got["unattributed_idle_ms_per_step.batch"] == pytest.approx(0.5)
    assert got["scan_sync_ms_per_step.batch"] == pytest.approx(0.6)
    assert got["gc_ms_per_step.batch"] == pytest.approx(0.1)
    # the ffn convert and slice; not the kernel, not the unscoped add
    assert got["ft_wrap_device_ms.batch"] == pytest.approx(0.75)
    assert got["weight_cast_device_ms.batch"] == pytest.approx(1.0)


def test_readers_silent_on_a_program_without_spans_or_scopes(tmp):
    window, pt = _trace()
    bare = ProgramTrace(first_step=pt.first_step, spans=[],
                        ops=[ScopedOp(o.name, o.start, o.dur, "jit(_step)/while/body/dot_general")
                             for o in pt.ops])
    _register(tmp, bare)
    assert all(layer_metrics.read(name, _ctx(window)) is None for name in READERS)
    # spans present, scopes absent: the device readers alone are silent
    program_trace._cache.clear()
    pt_unscoped = ProgramTrace(first_step=pt.first_step, spans=pt.spans, ops=bare.ops)
    _register(tmp, pt_unscoped, "bench-trace-b")
    assert program_trace.ft_wrap_device_ms(_ctx(window)) is None
    assert program_trace.weight_cast_device_ms(_ctx(window)) is None
    assert program_trace.scan_sync_ms_per_step(_ctx(window)) == pytest.approx(0.6)


def test_trace_of_another_window_is_rejected(tmp):
    window, pt = _trace()
    shifted = ProgramTrace(first_step=pt.first_step + 1e-9, spans=pt.spans, ops=pt.ops)
    _register(tmp, shifted)
    assert program_trace.find(window) is None
    assert all(layer_metrics.read(name, _ctx(window)) is None for name in READERS)
    _register(tmp, pt, "bench-trace-b")
    assert program_trace.find(window) is pt


def test_real_trace_is_found_by_its_first_step(tmp):
    import jax

    trace_dir = tmp / "bench-trace-real"
    jax.profiler.start_trace(str(trace_dir))
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            with jax.profiler.TraceAnnotation("hyca.server.step", step=i):
                with jax.profiler.TraceAnnotation("hyca.fault.scan.sync"):
                    pass
    jax.profiler.stop_trace()
    (path,) = trace_dir.rglob("*.xplane.pb")
    steps = [s for s in trace_reduce.load(str(path)).spans if s.name == "bench.step"]
    window = trace_reduce.Window(steps[0].start, steps[-1].end, [], [], steps)
    pt = program_trace.find(window)
    assert pt is not None and pt.first_step == window.lo
    assert len(pt.spans_named(program_trace.ROOT_SPAN, window.lo, window.hi)) == 3
    assert program_trace.scan_sync_ms_per_step(_ctx(window)) >= 0.0
    later = trace_reduce.Window(steps[1].start, steps[-1].end, [], [], steps[1:])
    assert program_trace.find(later) is None


def _xspace_file(tmp, window, pt):
    """The synthetic trace written as an ``.xplane.pb``, each device op's
    scope in its event metadata's ``tf_op`` stat, as a TPU trace keeps it."""
    schema = program_trace._xplane_schema()
    space = schema.XSpace()
    ps = lambda t: int(round(t * 1e12))  # noqa: E731
    dev = space.planes.add(id=1, name="/device:TPU:0")
    dev.stat_metadata[1].id, dev.stat_metadata[1].name = 1, "tf_op"
    for line_id, (line_name, events) in enumerate(
            (("XLA Ops", pt.ops), ("XLA Modules", window.modules)), 1):
        line = dev.lines.add(id=line_id, name=line_name)
        for e in events:
            mid = len(dev.event_metadata) + 1
            dev.event_metadata[mid].id, dev.event_metadata[mid].name = mid, e.name
            if getattr(e, "scope", ""):
                dev.event_metadata[mid].stats.add(metadata_id=1, str_value=e.scope)
            line.events.add(metadata_id=mid, offset_ps=ps(e.start), duration_ps=ps(e.dur))
    host = space.planes.add(id=2, name="/host:CPU")
    line = host.lines.add(id=1, name="python")
    for e in sorted(window.spans + pt.spans, key=lambda e: e.start):
        mid = len(host.event_metadata) + 1
        host.event_metadata[mid].id, host.event_metadata[mid].name = mid, e.name
        line.events.add(metadata_id=mid, offset_ps=ps(e.start), duration_ps=ps(e.dur))
    path = tmp / "bench-trace-x" / "plugins" / "profile" / "t" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(space.SerializeToString())
    return path


def test_scopes_read_from_event_metadata_of_a_written_trace(tmp):
    window, pt = _trace()
    path = _xspace_file(tmp, window, pt)
    loaded = program_trace.load(str(path))
    assert [o.scope for o in loaded.ops] == [o.scope for o in pt.ops]
    assert len(loaded.spans) == len(pt.spans)
    steps = [s for s in trace_reduce.load(str(path)).spans if s.name == "bench.step"]
    (read_window,) = trace_reduce.windows(trace_reduce.load(str(path)))
    assert loaded.first_step == read_window.lo == steps[0].start
    ctx = _ctx(read_window)
    assert layer_metrics.read("ft_wrap_device_ms.batch", ctx) == pytest.approx(0.75, rel=1e-6)
    assert layer_metrics.read("weight_cast_device_ms.batch", ctx) == pytest.approx(1.0, rel=1e-6)
    assert layer_metrics.read("unattributed_idle_ms_per_step.batch", ctx) == pytest.approx(0.5, rel=1e-6)
