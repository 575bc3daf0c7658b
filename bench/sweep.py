"""Find the knee of an open-loop cell on the chip: the highest arrival rate
at which the queue does not grow through the window.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --fractions 0.7,0.85,1.0,1.1

One process: it builds the cell once, measures the decode step at full
occupancy, estimates the knee as ``n_slots / (mean service steps x step
time)``, then serves the cell's mix at each fraction of that estimate on a
fresh server (same compiled bundle) for a warm phase and ``--seconds``.
For each rate it prints the queue at the window's opening and close, the
completions per second and the cell's end-to-end numbers.  The knee is the
highest rate, at or below the estimate, whose queue, and that of every
lower rate swept, does not grow through the window: no more requests wait
at its close than at its opening, give or take ``QUEUE_SLACK``.  A rate
above the estimate is beyond the capacity that full occupancy allows and is
never the knee, whatever its queue did in one window.  The JSON lands in ``chiprun_out/sweep_<cell>.json`` when that
directory exists.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
QUEUE_SLACK = 2


def holds(row: dict) -> bool:
    """Whether a swept rate is sustained: at or below the full-occupancy
    estimate, and its queue did not grow through the window."""
    return row["fraction"] <= 1.0 and row["queue_close"] <= row["queue_open"] + QUEUE_SLACK


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fractions", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import arrivals, harness
    from bench.run import device_check
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import FaultTolerantServer

    cell = harness.load_cell(args.workload)
    device_check(cell.chips)
    enable_compile_cache()
    first = harness.build_server(cell, args.seed)
    bundle, n_slots = first.bundle, first.cfg.n_slots
    full = dict(cell.traffic, loop="backlog", warm_steps=20, follow_s=0.0)
    sched = arrivals.Schedule(full, int(cell.config["vocab_size"]), args.seed)
    rec = harness.drive(first, sched, full, 10.0)
    step_s = float(np.median(np.diff(rec.step_end[rec.k_open:rec.k_close + 1])))
    service = arrivals.mean_service_steps(cell.traffic)
    knee = n_slots / (service * step_s)
    out = {"workload": cell.name, "step_s": step_s, "service_steps": service,
           "knee_estimate_rps": knee, "rates": []}
    print(json.dumps(out), flush=True)
    del first, rec
    gc.collect()
    prot = cell.traffic["protection"]
    failed = False      # the knee lies below the lowest rate that was not sustained
    for f in sorted(float(x) for x in args.fractions.split(",")):
        rate = f * knee
        traffic = dict(cell.traffic, rate_rps=rate, follow_s=0.0)
        server = FaultTolerantServer(bundle.cfg, bundle=bundle)
        server.injector.inject_n(int(prot["faults_at_boot"]))
        server.manager.bist()
        rec = harness.drive(server, arrivals.Schedule(traffic, int(cell.config["vocab_size"]),
                                                      args.seed), traffic, args.seconds)
        done = [r for r in rec.reqs.values() if r.reason is not None
                and r.first_step is not None and rec.t_open < rec.step_end[r.last_step] <= rec.t_close]
        row = {"fraction": f, "rate_rps": rate,
               "queue_open": int(rec.step_queue[rec.k_open]),
               "queue_close": int(rec.step_queue[rec.k_close]),
               "queue_max": int(rec.step_queue[rec.k_open:rec.k_close + 1].max()),
               "completed_per_s": len(done) / rec.window_s,
               "step_ms_median": 1e3 * float(np.median(np.diff(rec.step_end[rec.k_open:rec.k_close + 1]))),
               **{k: v["value"] for k, v in harness.end_to_end(rec, 0.0).items() if k != "setup_s"}}
        out["rates"].append(row)
        print(json.dumps(row), flush=True)
        if holds(row) and not failed:
            out["knee_rps"] = rate
        failed = failed or not holds(row)
        del server, rec
        gc.collect()
    dest = ROOT / "chiprun_out"
    if dest.is_dir():
        (dest / f"sweep_{cell.name}.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
