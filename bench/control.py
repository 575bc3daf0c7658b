"""Both readings behind a cell's correctness limit, on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process: build the cell, serve its mix through the
warm phase and a short window at the cell's own load, then compare the same
sample the benchmark would against the float32 reference (the program's
reading, ``max_gap``) and against the float8 reference put in the program's
place (the control's reading, ``control_gap``).  Each reading goes through
the benchmark's own judgement (``check.judge``) against the limit in
``bench/limits/<cell>.json``: the program's has to come out correct and the
control's not.  One JSON line per seed.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float) -> dict:
    from bench import arrivals, check, harness

    server = harness.build_server(cell, seed)
    rec = harness.drive(server, arrivals.Schedule(cell.traffic, int(cell.config["vocab_size"]), seed),
                        cell.traffic, seconds)
    del server
    harness.free_device_memory()
    sample = check.sample_finished(rec.reqs.values(), seed, int(cell.traffic["check_requests"]))
    return check.served_gaps(cell.config, seed, sample, int(cell.config["smax"]), control=True,
                             family=cell.family)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench.harness import load_cell
    from bench.run import device_check
    from repro.launch.compile_cache import enable_compile_cache

    cell = load_cell(args.workload)
    device_check(cell.chips)
    enable_compile_cache()
    from bench.check import judge

    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(cell, seed, args.seconds)
        program, _ = judge(r, cell.limits)
        control, checks = judge(dict(r, max_gap=r["control_gap"]), cell.limits)
        print(json.dumps({"workload": cell.name, "seed": seed, **r,
                          "program_correct": program, "control_correct": control,
                          "control_checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
