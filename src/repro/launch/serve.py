"""Serving steps: prefill (forward, last-position logits) and one-token
decode against a sharded KV cache, plus a CPU-scale batched-request driver.

Cache shardings come from dist.sharding.cache_specs: KV heads over the model
axis when they divide it, otherwise the KV *length* is sharded
(flash-decoding layout) so 500k-token caches stay shardable for low-kv archs.
"""
from __future__ import annotations

import argparse
import time
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.ftcontext import FTContext
from repro.dist.sharding import cache_specs, named, param_specs, resolve_spec
from repro.models.lm import LMConfig, decode_step, forward


def make_prefill(cfg: LMConfig, mesh: Mesh, params_shapes: Any, batch_shapes: Any,
                 *, ftc: FTContext | None = None):
    pspec = param_specs(params_shapes, mesh)
    bspec = jax.tree.map(
        lambda v: resolve_spec(["batch"] + [None] * (len(v.shape) - 1), v.shape, mesh),
        batch_shapes,
    )

    def prefill(params, batch):
        logits, _ = forward(params, cfg, batch, last_only=True, ftc=ftc)
        return logits

    fn = jax.jit(
        prefill,
        in_shardings=(named(mesh, pspec), named(mesh, bspec)),
        out_shardings=named(mesh, resolve_spec(["batch", None, "vocab"], (1, 1, cfg.padded_vocab), mesh)),
    )
    return fn, (pspec, bspec)


def make_decode(cfg: LMConfig, mesh: Mesh, params_shapes: Any, cache_shapes: Any, *,
                batch: int | None = None, ftc: FTContext | None = None):
    pspec = param_specs(params_shapes, mesh)
    cspec = cache_specs(cache_shapes, mesh)
    if batch is None:  # infer the request batch from any batch-major cache leaf
        idx = jax.tree.leaves({k: v for k, v in cache_shapes.items() if k != "enc"})
        batch = idx[0].shape[1] if idx else 8
    tok_spec = resolve_spec(["batch", None], (batch, 1), mesh)

    def step(params, cache, batch):
        return decode_step(params, cfg, cache, batch, ftc=ftc)

    fn = jax.jit(
        step,
        in_shardings=(named(mesh, pspec), named(mesh, cspec), named(mesh, {"token": tok_spec})),
        out_shardings=(None, named(mesh, cspec)),
        donate_argnums=(1,),
    )
    return fn, (pspec, cspec)


# --------------------------------------------------------------------------- #
# CLI — thin front-end over repro.serving (the fault-aware runtime)
# --------------------------------------------------------------------------- #
def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import FaultTolerantServer, ServerConfig

    ap = argparse.ArgumentParser(
        description="Fault-aware continuous-batching inference server."
    )
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU); default: the published widths")
    ap.add_argument("--slots", type=int, default=4, help="decode slots (max batch)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16, help="max new tokens per request")
    ap.add_argument("--mode", default="protected", choices=["off", "protected", "unprotected"])
    ap.add_argument("--faults", type=int, default=0, help="faults injected at power-on")
    ap.add_argument("--fault-rate", type=float, default=0.0, help="Poisson new faults/step")
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--cols", type=int, default=8)
    ap.add_argument("--dppu", type=int, default=4)
    ap.add_argument("--protect-fraction", type=float, default=1.0)
    ap.add_argument("--dispatch", default="twopass", choices=["twopass", "fused"],
                    help="FTContext kernel dispatch for protected matmuls")
    ap.add_argument("--repair", default="none", choices=["none", "remap", "retrain"],
                    help="model-side remediation past DPPU capacity "
                         "(repro.repair): remap prunes least-salient channels "
                         "onto broken columns; retrain also fine-tunes the "
                         "replica's params on a budget")
    ap.add_argument("--retrain-steps", type=int, default=4,
                    help="fine-tune budget when --repair retrain")
    ap.add_argument("--scan-block", type=int, default=1,
                    help="PE-grid rows probed per scan step (must divide --rows; "
                         "p = scan_block*cols DPPU groups scan in parallel)")
    ap.add_argument("--dppu-groups", type=int, default=0,
                    help="report the Section IV-D cycle model at this grouping "
                         "(0 = the grouping --scan-block implies)")
    ap.add_argument("--sla", type=int, default=0, help="deadline in steps (0 = none)")
    ap.add_argument("--max-steps", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos-per", type=float, default=0.0,
                    help="chaos experiment: inject a campaign-sampled fault map "
                         "at this PER into the running server (0 = off)")
    ap.add_argument("--chaos-at", type=int, default=0,
                    help="server step at which the chaos map is injected")
    ap.add_argument("--chaos-model", default="random", choices=["random", "clustered"],
                    help="fault distribution of the chaos map")
    ap.add_argument("--counters", action="store_true",
                    help="fold the repro.obs device-side Counters beside each "
                         "compiled step (exact fault/recompute accounting; "
                         "the step is the same program with counters off)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the event log as JSONL to PATH and a "
                         "Prometheus-style rendering of the summary (gauges "
                         "+ latency histograms) to PATH.prom "
                         "(docs/observability.md)")
    ap.add_argument("--series-out", default=None, metavar="PATH",
                    help="write the per-step telemetry series to PATH.npz; "
                         "feed to python -m repro.obs.replay")
    ap.add_argument("--spans-out", default=None, metavar="PATH",
                    help="derive repro.obs.trace lifecycle spans from the "
                         "event log and write them as JSONL to PATH")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve a stdlib-only HTTP /metrics endpoint on "
                         "127.0.0.1:PORT during the run (0 = pick a free "
                         "port); the scrape returns the same Prometheus "
                         "text --metrics-out writes")
    ap.add_argument("--metrics-hold", type=float, default=0.0, metavar="SEC",
                    help="keep the /metrics endpoint up SEC seconds after "
                         "the run finishes (lets an external scraper catch "
                         "the final state — the CI obs-smoke lane does)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = ServerConfig(
        arch=args.arch, smoke=args.smoke, n_slots=args.slots,
        smax=args.prompt_len + args.gen + 2,
        mode=args.mode, rows=args.rows, cols=args.cols, dppu_size=args.dppu,
        protect_fraction=args.protect_fraction, dispatch=args.dispatch,
        scan_block=args.scan_block, fault_rate=args.fault_rate, seed=args.seed,
        repair=args.repair, retrain_steps=args.retrain_steps,
        counters=args.counters,
    )
    server = FaultTolerantServer(cfg)
    if args.faults:
        server.injector.inject_n(args.faults)
        if args.mode == "protected":
            server.manager.bist()

    lm = server.lm
    rng = np.random.default_rng(args.seed)
    trace = [
        {
            "step": int(rng.integers(0, max(args.requests // 2, 1))),
            "prompt": rng.integers(0, lm.vocab, size=args.prompt_len),
            "max_new_tokens": args.gen,
            **({"deadline_step": int(rng.integers(0, args.requests)) + args.sla} if args.sla else {}),
        }
        for _ in range(args.requests)
    ]
    on_step = None
    chaos_state = {"injected": None}
    if args.chaos_per > 0:
        from repro.core.campaign import ChaosSpec, apply_chaos, chaos_maps

        chaos = ChaosSpec(per=args.chaos_per, fault_model=args.chaos_model,
                          at_step=args.chaos_at, seed=args.seed + 99)
        cmap = chaos_maps(chaos, 1, args.rows, args.cols)[0]

        def on_step(srv):
            if srv.step_idx == chaos.at_step and chaos_state["injected"] is None:
                n = apply_chaos(srv.injector, cmap)
                chaos_state["injected"] = n
                srv.log.emit("chaos.injected", n=n)

    httpd = None
    if args.metrics_port is not None:
        from repro.obs.export import gc_text, histograms_text, prometheus_text
        from repro.obs.httpd import MetricsServer

        def _render_prom():
            labels = {"arch": lm.name, "mode": args.mode}
            return (
                prometheus_text(server.metrics.summary(
                    counters=server.counters_host()), labels=labels)
                + histograms_text(server.metrics.latency_lists(), labels=labels)
                + gc_text(labels=labels)
            )

        httpd = MetricsServer(_render_prom, port=args.metrics_port)
        # flush: scrapers (CI) tail the redirected log for the bound port
        print(f"[serve] /metrics live on "
              f"http://127.0.0.1:{httpd.start()}/metrics", flush=True)

    t0 = time.perf_counter()
    summary = server.run(trace, max_steps=args.max_steps, on_step=on_step)
    dt = time.perf_counter() - t0
    from repro.core.detection import detection_cycles

    groups = args.dppu_groups or args.scan_block * args.cols
    print(f"[serve] arch={lm.name} mode={args.mode} slots={args.slots} "
          f"faults={server.injector.n_faults} confirmed={server.manager.n_confirmed} "
          f"surviving_cols={server.manager.surviving_cols}/{args.cols}")
    if args.repair != "none":
        print(f"[serve] repair={args.repair}: remapped={server.manager.n_remapped} "
              f"quality_fraction={server.manager.quality_fraction:.2f} "
              f"events={len(server.repair_events)}")
    if args.chaos_per > 0:
        print(f"[serve] chaos: {chaos_state['injected'] or 0} faults injected "
              f"at step {args.chaos_at} (PER {args.chaos_per}, {args.chaos_model}); "
              f"detection is the ScanEngine's job")
    print(f"[serve] scan: block={args.scan_block} rows/step "
          f"({server.manager.steps_per_sweep} steps/sweep); cycle model "
          f"p={groups}: {detection_cycles(args.rows, args.cols, dppu_groups=groups)} "
          f"cycles/sweep (p=1: {detection_cycles(args.rows, args.cols)})")
    if summary.get("detections"):
        print(f"[serve] detection latency (steps, measured): "
              f"mean={summary['detect_latency_mean_steps']:.1f} "
              f"p50={summary['detect_latency_p50_steps']:.1f} "
              f"p95={summary['detect_latency_p95_steps']:.1f} "
              f"over {summary['detections']} confirmations "
              f"(injected at steps {summary['injection_steps']})")
    if args.counters:
        c = summary["counters"]
        print(f"[serve] counters: steps={c['steps']} "
              f"protected_calls={c['protected_calls']} plain={c['plain_calls']} "
              f"fault={c['fault_fraction']:.2e} corrupted={c['corrupted_fraction']:.2e} "
              f"pruned={c['pruned_fraction']:.2e}")
    for k in ("steps", "tokens", "tokens_per_step", "goodput_tokens",
              "requests_completed", "requests_failed", "ttft_mean_steps",
              "queue_depth_mean", "scan_sweeps", "effective_slots_final"):
        print(f"    {k:>22} = {summary[k]}")
    print(f"    {'wall_s':>22} = {dt:.2f}")
    if args.metrics_out:
        from repro.obs.export import write_metrics_out

        path, prom = write_metrics_out(
            args.metrics_out, summary, server.log,
            labels={"arch": lm.name, "mode": args.mode},
            histograms=server.metrics.latency_lists(),
        )
        print(f"[serve] metrics: events -> {path}  summary -> {prom}")
    if args.series_out:
        from repro.obs.series import save_series

        written = save_series(args.series_out, server.series_host(), meta={
            "arch": lm.name, "mode": args.mode,
            "start_step": server.series_start_step(),
        })
        print(f"[serve] series: {len(server.metrics.steps)} steps -> {written}")
    if args.spans_out:
        from repro.obs.trace import build_traces, write_spans

        n = write_spans(args.spans_out, build_traces(server.log))
        print(f"[serve] spans: {n} -> {args.spans_out}")
    if httpd is not None:
        if args.metrics_hold > 0:
            print(f"[serve] holding /metrics for {args.metrics_hold:g}s",
                  flush=True)
            time.sleep(args.metrics_hold)
        httpd.stop()
    return summary


if __name__ == "__main__":
    main()
