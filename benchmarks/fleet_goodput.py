"""Fleet goodput under chaos — the vectorized-engine headline benchmark.

``run_vfleet`` advances the whole fleet as one jitted program per chunk, so
production-scale campaigns (1000 replicas x 10k steps of trace-driven
traffic, Poisson wearout, a mid-run chaos event, spare-pool replacement)
run in minutes on CPU — the legacy per-server ``run_fleet`` loop is
O(replicas*steps) host iterations with a real decode each, ~1e4x more wall
per replica-step.

Records (keyed ``fleet`` for the regress.py budgets):

  * quick-size sweep — three scenarios on identical geometry so they share
    ONE compiled chunk program: ``baseline`` (no faults), ``chaos-pool``
    and ``chaos-region`` (same wearout + chaos event, pool vs region spare
    policy).  Always emitted, in quick and full mode — these are the rows
    the regression gate compares (goodput floor + sim-wall ceiling).
  * ``headline-1000x10k`` — full mode only: the production-scale campaign,
    with its wall time in the JSON.

Claims: cross-engine parity on the pinned small-fleet config, zero
recompilations across scenarios and fault-rate points, pooled spares beat
region-locked spares, chaos costs goodput vs baseline, and the headline
completes in minutes.
"""
from __future__ import annotations

import dataclasses

from benchmarks.common import Claims
from repro.serving import ChaosSpec, FleetConfig, ServerConfig, TrafficSpec
from repro.serving.fleet import run_fleet
from repro.serving.vfleet import _TRACES, run_vfleet

# prefill_chunk=1: every prefilling slot feeds one prompt token a step, the
# feed the vectorized engine's decode proxy models (run_vfleet refuses more)
_SERVER = ServerConfig(
    n_slots=4, smax=64, mode="protected", scan_block=2,
    rows=8, cols=8, dppu_size=4, prefill_chunk=1,
)
_TRAFFIC = TrafficSpec(
    request_rate=0.3, sla_steps=64, seed=2, n_classes=2, tail=0.4,
    burst_rate=0.05, burst_size=4.0,
    diurnal_amplitude=0.4, diurnal_period=2000,
)


N_REGIONS = 4


def _sweep_cfg(n_replicas: int, steps: int, policy: str, *,
               chaos: bool) -> FleetConfig:
    # the chaos event is a *localized* failure domain: every target sits in
    # region 0 (replica index ≡ 0 mod N_REGIONS), each hit hard enough
    # (per=0.15 on an 8x8 array ≈ 9.6 faults >> DPPU capacity 4) to retire.
    # A shared pool can spend every spare on the stricken region; region-
    # locked spares can only spend region 0's quarter — the goodput gap
    # between the two scenarios is the paper's pooled-redundancy argument
    # at fleet scale.
    targets = tuple(range(0, n_replicas, N_REGIONS))
    return FleetConfig(
        n_replicas=n_replicas, n_spares=max(2, n_replicas // 5),
        spare_policy=policy, n_regions=N_REGIONS if policy == "region" else 1,
        steps=steps, retire_fraction=0.25, seed=0, chunk_steps=250,
        fault_rate=3e-4 if chaos else 0.0,
        chaos=ChaosSpec(per=0.15, at_step=steps // 5, seed=1,
                        replicas=targets) if chaos else None,
        traffic=dataclasses.replace(
            _TRAFFIC, diurnal_period=max(steps // 5, 1)),
        server=_SERVER,
    )


def _record(fleet: str, cfg: FleetConfig, report: dict) -> dict:
    return {
        "fleet": fleet,
        "n_replicas": cfg.n_replicas,
        "steps": cfg.steps,
        "fault_rate": cfg.fault_rate,
        "spare_policy": cfg.spare_policy,
        "goodput_tokens": report["goodput_tokens"],
        "goodput_per_step": report["goodput_per_step"],
        "requests_completed": report["requests_completed"],
        "requests_expired": report["requests_expired"],
        "slo_attainment": report["slo_attainment"],
        "retirements": report["retirements"],
        "replacements": report["replacements"],
        "alive_final": report["alive_final"],
        "alive_mean": report["alive_mean"],
        "latency_e2e_p50": report["latency_e2e_p50"],
        "latency_e2e_p99": report["latency_e2e_p99"],
        "sim_wall_s": report["sim_wall_s"],
    }


def run(quick: bool = False) -> dict:
    c = Claims("fleet_goodput")
    results: list[dict] = []

    # ---- quick-size sweep: three scenarios, one compiled program -------- #
    n_replicas, steps = 64, 600
    scenarios = [
        ("baseline", _sweep_cfg(n_replicas, steps, "pool", chaos=False)),
        ("chaos-pool", _sweep_cfg(n_replicas, steps, "pool", chaos=True)),
        ("chaos-region", _sweep_cfg(n_replicas, steps, "region", chaos=True)),
    ]
    reports = {}
    traces_after = {}
    for name, cfg in scenarios:
        reports[name] = run_vfleet(cfg)
        traces_after[name] = len(_TRACES)
        results.append(_record(name, cfg, reports[name]))
    c.check(
        "chaos scenario reuses the baseline's compiled chunk program "
        "(the chaos map / rate are traced leaves, not statics)",
        traces_after["chaos-pool"] == traces_after["baseline"],
        f"new traces: {traces_after['chaos-pool'] - traces_after['baseline']}",
    )
    n1 = len(_TRACES)
    for i, rate in enumerate((1e-4, 1e-3)):
        run_vfleet(dataclasses.replace(
            scenarios[1][1], fault_rate=rate, seed=i + 1))
    c.check(
        "zero recompilations across fault-rate sweep points",
        len(_TRACES) == n1,
        f"retraces: {len(_TRACES) - n1}",
    )
    c.check(
        "chaos + wearout cost goodput vs the fault-free baseline",
        reports["baseline"]["goodput_tokens"] > reports["chaos-pool"]["goodput_tokens"],
        f"baseline={reports['baseline']['goodput_tokens']} "
        f"chaos={reports['chaos-pool']['goodput_tokens']}",
    )
    c.check(
        "pooled spares serve at least as much as region-locked spares",
        reports["chaos-pool"]["goodput_tokens"] >= reports["chaos-region"]["goodput_tokens"],
        f"pool={reports['chaos-pool']['goodput_tokens']} "
        f"region={reports['chaos-region']['goodput_tokens']}",
    )

    # ---- cross-engine parity on the pinned small fleet ------------------ #
    parity_cfg = FleetConfig(
        n_replicas=3, n_spares=2, spare_policy="pool", n_regions=1, steps=48,
        fault_rate=0.0, retire_fraction=0.25, seed=0,
        chaos=ChaosSpec(per=0.3, at_step=10, seed=3),
        traffic=TrafficSpec(request_rate=0.8, sla_steps=12, seed=5),
        server=ServerConfig(n_slots=2, smax=32, mode="protected",
                            scan_block=2, rows=4, cols=4, dppu_size=2,
                            prefill_chunk=1),
    )
    legacy = run_fleet(parity_cfg)
    vec = run_vfleet(parity_cfg)
    parity_keys = (
        "goodput_tokens", "requests_completed", "requests_expired",
        "requests_lost", "retirements", "replacements", "spares_remaining",
        "chaos_injected", "slo_requests", "slo_met", "slo_misses",
    )
    diffs = {k: (legacy[k], vec[k]) for k in parity_keys if legacy[k] != vec[k]}
    c.check(
        "vectorized engine matches the legacy fleet loop key-for-key "
        "on the pinned config",
        not diffs, f"diffs={diffs}" if diffs else f"{len(parity_keys)} keys equal",
    )
    parity = {"legacy": {k: legacy[k] for k in parity_keys},
              "vfleet": {k: vec[k] for k in parity_keys}}

    # ---- the headline: 1000 replicas x 10k steps (full mode only) ------- #
    headline = None
    if not quick:
        cfg = _sweep_cfg(1000, 10_000, "pool", chaos=True)
        report = run_vfleet(cfg)
        headline = _record("headline-1000x10k", cfg, report)
        results.append(headline)
        c.check(
            "1000 replicas x 10k steps of goodput-under-chaos completes "
            "in minutes on CPU",
            report["sim_wall_s"] < 900,
            f"sim_wall_s={report['sim_wall_s']:.1f}",
        )
        c.check(
            "the spare pool keeps the chaos-hit fleet serving "
            "(goodput never collapses to zero after the event)",
            report["goodput_tokens"] > 0 and report["alive_final"] > 0,
            f"alive_final={report['alive_final']} "
            f"goodput={report['goodput_tokens']}",
        )

    return {
        "results": results,
        "parity": parity,
        "headline": headline,
        "claims": c.items,
        "all_ok": c.all_ok,
    }


def main(argv=None) -> int:
    import argparse
    import time

    from benchmarks.common import save_result

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="skip the 1000x10k headline (CI smoke)")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    out = run(quick=args.quick)
    out["elapsed_s"] = round(time.perf_counter() - t0, 2)
    path = save_result("fleet_goodput", out)
    for r in out["results"]:
        print(
            f"[fleet_goodput] {r['fleet']:>17}: {r['n_replicas']:>4} replicas"
            f" x {r['steps']:>5} steps  goodput {r['goodput_tokens']:>9}"
            f"  slo {r['slo_attainment']:.3f}"
            f"  retire {r['retirements']:>4}  wall {r['sim_wall_s']:7.2f}s"
        )
    print(f"[fleet_goodput] wrote {path} ({out['elapsed_s']}s)")
    return 0 if out["all_ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
