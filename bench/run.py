"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell's configuration, traffic mix, limits
and metrics are found by the names in ``BENCHMARK.json``.  One process holds
the chip: without a TPU, with fewer chips than the cell asks for, or with a
chip whose ``device_kind`` is not in ``bench/peaks.py``, it exits non-zero
and prints no result.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number compared
beside its limit, which also end standard error).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def device_check(chips: int):
    """The device fields of the result and the chip's peaks; exits without a chip."""
    import jax

    from bench.peaks import PEAKS

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"[bench] no TPU found (platform {d.platform!r}); the benchmark runs only on the chip")
    if len(devs) < chips:
        sys.exit(f"[bench] the cell needs {chips} chips, found {len(devs)}")
    if d.device_kind not in PEAKS:
        sys.exit(f"[bench] no published peaks for {d.device_kind!r} in bench/peaks.py")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}, PEAKS[d.device_kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import load_cell, run_cell

    cell = load_cell(args.workload)
    device, peaks = device_check(cell.chips)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()   # JAX_COMPILATION_CACHE_DIR, else a fixed path in the checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, device=device, peaks=peaks)
    for name, c in out["checks"].items():
        print(f"[check] {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(f"[check] correct {out['correct']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
