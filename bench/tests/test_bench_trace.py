"""The reduction from trace events to busy time, idle gaps and kernel time,
on a synthetic trace built here (no profiler, no chip)."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from bench import layer_metrics, trace_reduce, work  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402
from bench.trace_reduce import Event, Trace  # noqa: E402

MS = 1e-3
MATMUL = ('%ft_matmul.{} = f32[64,1024] custom-call(s32[1024] %m, f32[64,1024] %x, '
          'f32[1024,1024] %w), custom_call_target="tpu_custom_call"')


def _trace():
    """Two server steps of 10 ms.  Each holds a scan span (1 ms) and a
    decode module (4 ms) whose while loop contains two kernels and a copy,
    then an argmax module; the host then records for 1 ms."""
    ops, modules, spans = [], [], []
    for i, t0 in enumerate((0.0, 10 * MS)):
        spans += [Event("bench.step", t0, 9 * MS), Event("bench.scan_step", t0, 1 * MS),
                  Event("bench.step_fn", t0 + 1 * MS, 0.5 * MS),
                  Event("bench.record", t0 + 9 * MS, 1 * MS)]
        d0 = t0 + 2 * MS
        modules.append(Event("jit__step(123)", d0, 4 * MS))
        ops += [Event("%while.2 = (...) while(...)", d0, 4 * MS),
                Event(MATMUL.format(2 * i), d0, 1 * MS),
                Event(MATMUL.format(2 * i + 1), d0 + 1 * MS, 1.5 * MS),
                # overlaps the second kernel: counted once in the busy union
                Event("%copy.7 = bf16[64] copy(bf16[64] %a)", d0 + 2 * MS, 2 * MS)]
        modules.append(Event("jit__argmax(9)", t0 + 7 * MS, 1 * MS))
        ops.append(Event("%reduce.1 = s32[64] reduce(...)", t0 + 7 * MS, 1 * MS))
    return Trace(ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": modules},
                 spans=sorted(spans, key=lambda e: e.start))


def test_union_merges_overlapping_and_nested_ops():
    evs = [Event("a", 0.0, 4.0), Event("b", 1.0, 1.0), Event("c", 3.5, 2.0), Event("d", 7.0, 1.0)]
    assert trace_reduce.union(evs, 0.0, 10.0) == [(0.0, 5.5), (7.0, 8.0)]
    assert trace_reduce.union(evs, 2.0, 7.5) == [(2.0, 5.5), (7.0, 7.5)]
    assert trace_reduce.gaps([(0.0, 5.5), (7.0, 8.0)], 0.0, 10.0) == [(5.5, 7.0), (8.0, 10.0)]


def test_window_busy_and_idle_share():
    (w,) = trace_reduce.windows(_trace())
    assert (w.lo, w.hi) == (0.0, pytest.approx(19 * MS))
    # per step 4 ms of decode and 1 ms of argmax: 10 ms busy, 9 ms idle
    assert w.busy_s == pytest.approx(10 * MS)
    ctx = layer_metrics.Context(w, {}, PEAKS["TPU v5 lite"], [])
    assert layer_metrics.idle_share(ctx) == pytest.approx(100 * 9 / 19)


def test_idle_gaps_labelled_by_innermost_host_span():
    (w,) = trace_reduce.windows(_trace())
    idle = w.idle_by_span()
    # per step: 0-1 ms under scan_step, 1-1.5 step_fn, 1.5-2, 6-7 and 8-9 the
    # step itself, then 9-10 record (the window ends with the second step at 19 ms)
    assert idle["bench.scan_step"] == pytest.approx(2 * MS)
    assert idle["bench.step_fn"] == pytest.approx(1 * MS)
    assert idle["bench.step"] == pytest.approx(5 * MS)
    assert idle["bench.record"] == pytest.approx(1 * MS)
    assert sum(idle.values()) == pytest.approx(w.seconds - w.busy_s)


def test_breakdown_lists_leaf_ops_and_idle_labels():
    (w,) = trace_reduce.windows(_trace())
    b = w.breakdown(top=3)
    names = [n for n, _ in b["device_ops"]]
    assert "while.2" not in names                       # a loop is not an op's own time
    assert b["device_ops"][0] == ["copy.7", pytest.approx(4 * MS)]
    assert len(b["device_ops"]) == 3
    assert b["idle_gaps"][0] == ["bench.step", pytest.approx(5 * MS)]
    assert all(isinstance(v, float) for _, v in b["device_ops"] + b["idle_gaps"])


def test_kernel_time_and_roofline_from_named_events():
    (w,) = trace_reduce.windows(_trace())
    steps = w.modules_matching(layer_metrics.STEP_MODULE)
    kernels = w.ops_within(steps, layer_metrics.KERNEL)
    assert len(kernels) == 4
    assert sum(k.dur for k in kernels) == pytest.approx(5 * MS)
    ctx = layer_metrics.Context(w, {}, PEAKS["TPU v5 lite"], [])
    assert layer_metrics.decode_device_ms(ctx) == pytest.approx(4.0)
    assert layer_metrics.scan_ms_per_step(ctx) == pytest.approx(1.0)
    # each 9 ms step span holds 5 ms of device work
    assert layer_metrics.host_ms_per_step(ctx) == pytest.approx(4.0)


def test_roofline_silent_unless_one_kernel_per_model_call(monkeypatch):
    config = {"hidden_size": 1024, "num_attention_heads": 16, "num_key_value_heads": 16,
              "intermediate_size": 2816, "num_hidden_layers": 1, "vocab_size": 256,
              "hidden_act": "silu", "n_slots": 64}
    (w,) = trace_reduce.windows(_trace())
    ctx = layer_metrics.Context(w, config, PEAKS["TPU v5 lite"], [])
    assert layer_metrics.ft_matmul_roofline(ctx) is None   # 2 kernels a step, 8 calls
    calls = [work.Call("attn.out", 64, 1024, 1024, 2)]
    least = 2 * sum(c.least_s(PEAKS["TPU v5 lite"]) for c in calls)
    monkeypatch.setattr(ctx.family, "decode_calls", lambda config, n: calls)
    assert layer_metrics.ft_matmul_roofline(ctx) == pytest.approx(100 * least / (5 * MS))


def test_mfu_from_recorded_step_load():
    config = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
              "intermediate_size": 128, "num_hidden_layers": 2, "vocab_size": 512,
              "hidden_act": "silu"}
    (w,) = trace_reduce.windows(_trace())
    ctx = layer_metrics.Context(w, config, PEAKS["TPU v5 lite"], [(3, 30), (4, 44)])
    flops = work.step_model_flops(config, 3, 30) + work.step_model_flops(config, 4, 44)
    assert layer_metrics.decode_mfu(ctx) == pytest.approx(100 * flops / w.seconds / 197e12)
