"""One benchmark run of one cell: build, warm, measure, check.

The system under test is the served path as users call it:
``FaultTolerantServer.step()`` over a ``ModelBundle`` (``repro.serving``).
The harness submits the schedule's requests, calls ``step()`` and reads the
host clock after each step (``step()`` ends by copying the sampled tokens to
the host, so the device has finished that step).  Everything the end-to-end
metrics need is derived from those step end times and from which step gave
each request its first and its later tokens:

* token ``i`` of a request comes out of step ``first_step + i`` (a decoding
  slot emits one token every step);
* a time to first token runs from the request's due time to the end of the
  step that produced its first token;
* an inter-token gap is the time between the ends of two consecutive steps
  that both gave the request a token.

The window opens at the end of the warm phase's last step and closes at the
end of the first step that ends ``seconds`` or more after it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import pathlib
import time
import types
from typing import Callable

import numpy as np

from bench import families
from bench.arrivals import Schedule

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_LEAD_S = 1.0      # --trace 1: the profiler starts this long into the window
TRACE_S = 3.0           # and records this long


# --------------------------------------------------------------------------- #
# the cell, from BENCHMARK.json and the files it names
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int = 1
    end_to_end: list = dataclasses.field(default_factory=list)
    per_layer: list = dataclasses.field(default_factory=list)
    family: types.ModuleType | None = None    # bench.families; by the config's name if None

    def __post_init__(self):
        if self.family is None:
            self.family = families.load(self.config)


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell named ``workload``: its configuration, traffic mix, limits of
    the correctness comparison, the metrics it reports and the configuration's
    model family, each found by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    config_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    config = json.loads((root / config_file).read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m else m["moves"] in moved)]
    return Cell(
        name=workload,
        config=config,
        traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((root / "bench" / "limits" / f"{workload}.json").read_text()),
        chips=int(w["chips"]),
        end_to_end=e2e,
        per_layer=per_layer,
        family=families.load(config, root / "bench" / "families"),
    )


def lm_config(config: dict, family: types.ModuleType | None = None):
    """The program's ``LMConfig`` for a configuration file, by its family
    (looked up by the file's ``family`` key where none is given)."""
    return (family or families.load(config)).lm_config(config)


def build_server(cell: Cell, seed: int):
    """The bundle and server of the cell, faults injected from the seed and
    confirmed by BIST (the paper's power-on fault map)."""
    from repro.serving import FaultTolerantServer, ModelBundle, ServerConfig

    prot = cell.traffic["protection"]
    cfg = ServerConfig(
        arch=cell.config["arch"], smoke=False,
        n_slots=int(cell.config["n_slots"]), smax=int(cell.config["smax"]),
        mode=prot["mode"], rows=prot["rows"], cols=prot["cols"],
        dppu_size=prot["dppu_size"], dispatch=prot["dispatch"],
        scan_block=prot["scan_block"], fault_rate=prot["fault_rate"], seed=seed,
    )
    bundle = ModelBundle(cfg, lm=cell.family.lm_config(cell.config))
    server = FaultTolerantServer(cfg, bundle=bundle)
    if prot["faults_at_boot"]:
        server.injector.inject_n(int(prot["faults_at_boot"]))
        server.manager.bist()
        if server.manager.n_confirmed != int(prot["faults_at_boot"]):
            raise RuntimeError(f"BIST confirmed {server.manager.counts()}")
    return server


# --------------------------------------------------------------------------- #
# what one run records
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class ReqRec:
    rid: int
    due: float                     # host clock (s) at which it was due
    prompt: np.ndarray
    max_new_tokens: int
    first_step: int | None = None  # step that produced its first token
    last_step: int | None = None   # step that produced its latest token
    tokens: np.ndarray | None = None
    reason: str | None = None      # server's completion reason, None in flight


@dataclasses.dataclass
class Record:
    t_start: float                 # host clock at the schedule's start
    step_end: np.ndarray           # host clock at the end of each step
    step_tokens: np.ndarray        # tokens each step gave out
    step_queue: np.ndarray         # requests waiting after each step
    k_open: int                    # last step of the warm phase
    k_close: int                   # step whose end closes the window
    reqs: dict                     # rid -> ReqRec
    follow_s: float = 0.0
    step_load: list = dataclasses.field(default_factory=list)  # (active, sum ctx), --trace 1
    trace_first_step: int = 0      # first step the profiler recorded whole
    compiles_in_window: int = 0

    @property
    def t_open(self) -> float:
        return float(self.step_end[self.k_open])

    @property
    def t_close(self) -> float:
        return float(self.step_end[self.k_close])

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t) -> np.ndarray:
        t = np.asarray(t)
        return (t > self.t_open) & (t <= self.t_close)


# --------------------------------------------------------------------------- #
# end-to-end arithmetic
# --------------------------------------------------------------------------- #
def percentile_nearest_rank(values, q: float) -> float:
    """The smallest value with at least ``q`` percent of the values at or below it."""
    v = np.sort(np.asarray(values, float))
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(v[max(0, math.ceil(q / 100.0 * v.size) - 1)])


def window_tokens(rec: Record) -> int:
    """Output tokens given out by the steps that end inside the window."""
    return int(rec.step_tokens[rec.in_window(rec.step_end)].sum())


def due_in_window(rec: Record) -> list[ReqRec]:
    return [r for r in rec.reqs.values() if rec.t_open <= r.due < rec.t_close]


def ttft_values(rec: Record) -> tuple[np.ndarray, int]:
    """Times to first token (s) of every request due in the window, and how
    many of them missed: a request that failed, or that had no first token
    ``follow_s`` after the window closed, counts at that cap."""
    cap = rec.t_close + rec.follow_s
    out, missed = [], 0
    for r in due_in_window(rec):
        ok = r.first_step is not None and r.reason in (None, "done", "eos")
        t = float(rec.step_end[r.first_step]) if r.first_step is not None else math.inf
        if not ok or t > cap:
            missed += 1
            t = cap
        out.append(t - r.due)
    return np.asarray(out, float), missed


def itl_gaps(rec: Record) -> np.ndarray:
    """Every inter-token gap (s) of every request whose later token came out
    of a step that ended inside the window."""
    d = np.diff(rec.step_end, prepend=np.nan)
    inside = rec.in_window(rec.step_end)
    parts = []
    for r in rec.reqs.values():
        if r.first_step is None or r.last_step is None or r.last_step <= r.first_step:
            continue
        k = np.arange(r.first_step + 1, r.last_step + 1)
        parts.append(d[k[inside[k]]])
    return np.concatenate(parts) if parts else np.zeros(0)


def end_to_end(rec: Record, setup_s: float) -> dict:
    m = {"setup_s": {"value": setup_s, "unit": "s"},
         "out_tok_s": {"value": window_tokens(rec) / rec.window_s, "unit": "tokens/s"}}
    ttft, _ = ttft_values(rec)
    if ttft.size:
        m["ttft_p90_s"] = {"value": percentile_nearest_rank(ttft, 90), "unit": "s"}
    gaps = itl_gaps(rec)
    if gaps.size:
        m["itl_p95_ms"] = {"value": 1e3 * percentile_nearest_rank(gaps, 95), "unit": "ms"}
    return m


# --------------------------------------------------------------------------- #
# the loop
# --------------------------------------------------------------------------- #
class Spans:
    """Host spans written into the profiler's trace around the calls into
    each layer (``jax.profiler.TraceAnnotation``); names start ``bench.``."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        if enabled:
            import jax

            self._annotation = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        return self._annotation("bench." + name) if self.enabled else contextlib.nullcontext()

    def wrap(self, obj, attr: str) -> None:
        """Put a span named after the method around every call of it."""
        if not self.enabled:
            return
        fn = getattr(obj, attr)
        label = "bench." + attr
        ann = self._annotation

        def wrapped(*a, **k):
            with ann(label):
                return fn(*a, **k)

        setattr(obj, attr, wrapped)


def _load_recorder(server, rec_list: list) -> None:
    """Record (active slots, summed context) of every step's feed."""
    sched = server.scheduler
    plan_feed = sched.plan_feed

    def wrapped():
        n, ctx = 0, 0
        for s in sched.slots:
            if s.request is None:
                continue
            n += 1
            ctx += s.pos + 1 if s.phase == "prefill" else s.request.prompt_len + len(s.generated)
        rec_list.append((n, ctx))
        return plan_feed()

    sched.plan_feed = wrapped


class CompileCounter:
    """Counts XLA compilations (JAX's backend-compile events) while armed."""

    def __init__(self):
        import jax

        self.n = 0
        self.armed = False

        def count(event: str) -> None:
            # a program compiled, or loaded from the persistent cache
            if self.armed and (event.endswith("backend_compile_duration")
                               or event == "/jax/compilation_cache/cache_hits"):
                self.n += 1

        jax.monitoring.register_event_listener(lambda event, **kw: count(event))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, duration, **kw: count(event))


def drive(server, schedule: Schedule, traffic: dict, seconds: float, *,
          spans: Spans | None = None, tracer=None, compiles: CompileCounter | None = None,
          clock: Callable[[], float] = time.perf_counter) -> Record:
    """Warm phase, measured window, and (open loop) the tail in which the
    requests due in the window are followed to their first token."""
    spans = spans or Spans(False)
    loop, n_slots = traffic["loop"], server.cfg.n_slots
    k_open = int(traffic["warm_steps"]) - 1
    follow_s = float(traffic.get("follow_s", 0.0))
    step_load: list = []
    if spans.enabled:
        spans.wrap(server.manager, "scan_step")
        spans.wrap(server.bundle, "step_fn")
        spans.wrap(server.bundle, "reset_fn")
        spans.wrap(server.scheduler, "admit")
        spans.wrap(server.scheduler, "commit")
        _load_recorder(server, step_load)

    reqs: dict[int, ReqRec] = {}
    ends: list[float] = []
    toks: list[int] = []
    queue: list[int] = []
    slots = server.scheduler.slots
    pending = schedule.next()
    t_start = clock()
    t_open = t_close = None
    k_close = None
    trace_on = trace_off = None
    trace_first = 0

    def submit(req, due):
        rid = server.submit(req.prompt, req.max_new_tokens)
        reqs[rid] = ReqRec(rid, due, req.prompt, req.max_new_tokens)

    while True:
        with spans("arrivals"):
            now = clock()
            if loop == "open":
                while t_start + pending.due_s <= now:
                    submit(pending, t_start + pending.due_s)
                    pending = schedule.next()
            else:
                while server.queue.depth() < n_slots:
                    submit(pending, now)
                    pending = schedule.next()
        with spans("step"):
            done = server.step()
        t = clock()
        with spans("record"):
            k = len(ends)
            ends.append(t)
            toks.append(server.scheduler.last_step_tokens)
            queue.append(server.queue.depth())
            for s in slots:
                if s.request is not None and s.first_token_step == k:
                    r = reqs[s.request.rid]
                    r.first_step = r.last_step = k
                elif s.request is not None and s.first_token_step is not None:
                    reqs[s.request.rid].last_step = k
            for c in done:
                r = reqs[c.rid]
                r.reason, r.tokens = c.reason, c.tokens
                if c.first_token_step is not None:
                    r.first_step = c.first_token_step
                    r.last_step = c.first_token_step + len(c.tokens) - 1

        if k == k_open:
            t_open = t
            if compiles is not None:
                compiles.armed = True
        if t_open is not None and k_close is None:
            if tracer is not None:
                if trace_on is None and t >= t_open + TRACE_LEAD_S:
                    tracer.start()
                    trace_on, trace_first = t, k + 1
                elif trace_on is not None and trace_off is None and t >= trace_on + TRACE_S:
                    tracer.stop()
                    trace_off = t
            if t >= t_open + seconds:
                k_close, t_close = k, t
                if compiles is not None:
                    compiles.armed = False
                if tracer is not None and trace_on is not None and trace_off is None:
                    tracer.stop()
        if k_close is not None:
            if loop != "open" or t >= t_close + follow_s:
                break
            if all(r.first_step is not None or r.reason is not None
                   for r in reqs.values() if t_open <= r.due < t_close):
                break

    return Record(
        t_start=t_start, step_end=np.asarray(ends), step_tokens=np.asarray(toks),
        step_queue=np.asarray(queue),
        k_open=k_open, k_close=k_close, reqs=reqs, follow_s=follow_s,
        step_load=step_load, trace_first_step=trace_first,
        compiles_in_window=compiles.n if compiles else 0,
    )


# --------------------------------------------------------------------------- #
# one whole run
# --------------------------------------------------------------------------- #
class Tracer:
    """The JAX profiler over part of the window, into a temporary directory."""

    def __init__(self):
        import tempfile

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # no per-function Python events
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def path(self) -> str | None:
        found = sorted(pathlib.Path(self.dir).rglob("*.xplane.pb"))
        return str(found[-1]) if found else None

    def close(self) -> None:
        import shutil

        shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def free_device_memory() -> None:
    """Drop every array still on the devices (the served program's state)."""
    import gc

    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def per_layer(cell: Cell, rec: Record, trace_path: str, peaks) -> tuple[dict, dict, dict]:
    """(metrics, device fields, breakdown) of the traced part of the window."""
    from bench import layer_metrics, trace_reduce

    wins = trace_reduce.windows(trace_reduce.load(trace_path))
    if not wins:
        raise RuntimeError("the trace holds no complete bench.step span")
    n_steps = len(wins[0].spans_named("step"))
    ctx = layer_metrics.Context(
        window=wins[0], config=cell.config, peaks=peaks,
        step_load=rec.step_load[rec.trace_first_step:][:n_steps], family=cell.family,
    )
    metrics = {}
    for m in cell.per_layer:
        v = layer_metrics.read(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"busy_s": sum(w.busy_s for w in wins) / len(wins), "window_s": wins[0].seconds}
    return metrics, device, wins[0].breakdown()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
             device: dict, peaks=None) -> dict:
    """Build, warm, measure and check one cell; the result line's object.

    ``t_start`` is the host clock (``time.perf_counter``) at process start;
    set-up runs from there to the window's opening."""
    from bench import check

    compiles = CompileCounter()
    server = build_server(cell, seed)
    schedule = Schedule(cell.traffic, int(cell.config["vocab_size"]), seed)
    tracer = Tracer() if trace else None
    try:
        rec = drive(server, schedule, cell.traffic, seconds, spans=Spans(trace),
                    tracer=tracer, compiles=compiles)
        setup_s = rec.t_open - t_start
        e2e = end_to_end(rec, setup_s)
        mem = memory_peak_bytes()
        del server
        free_device_memory()
        sample = check.sample_finished(rec.reqs.values(), seed, int(cell.traffic["check_requests"]))
        readings = (check.served_gaps(cell.config, seed, sample, int(cell.config["smax"]),
                                      family=cell.family) if sample else None)
        correct, checks = check.judge(readings, cell.limits)
        due = due_in_window(rec)
        if cell.traffic["loop"] == "open":
            _, failed = ttft_values(rec)          # failures and first tokens past the cap
        else:
            failed = sum(r.reason not in (None, "done", "eos") for r in due)
        out = {"correct": correct, "attempted": len(due), "failed": failed}
        if trace:
            metrics, dev, breakdown = per_layer(cell, rec, tracer.path(), peaks)
        else:
            names = {m["name"] for m in cell.end_to_end}
            metrics, dev, breakdown = {k: v for k, v in e2e.items() if k in names}, {}, None
        out["metrics"] = metrics
        out["device"] = dict(device, memory_peak_bytes=mem, **dev)
        if breakdown is not None:
            out["breakdown"] = breakdown
        out["compiles_in_window"] = rec.compiles_in_window
        out["served_tokens_checked"] = None if readings is None else readings["served_tokens"]
        out["checks"] = checks
        return out
    finally:
        if tracer is not None:
            tracer.close()
