#!/usr/bin/env python3
"""Chip smoke test: the fault-aware server and trainer on a TPU at the
published widths of qwen1.5-0.5b (24 layers, d_model 1024, 16 heads, d_ff
2816, vocab 151936) on the paper's 32x32 PE array with a DPPU of 32.

    python chip_smoke.py             # one chip: kernel, serve, train
    python chip_smoke.py --chips 4   # four chips: train on a (data=2,
                                     # model=2) mesh vs one device, nothing else;
                                     # per-step losses and gradient norms agree
                                     # within LOSS_RTOL and GNORM_RTOL

Everything runs in this one process (a chip belongs to one process).  Phases
on one chip, each through the entry points a user calls:

  kernel  the Pallas ``ft_matmul`` behind ``FTContext(dispatch="fused")``,
          over-capacity faults and a RepairPlan, against the tile-granular
          jnp oracle ``kernels.ref.ft_matmul_ref`` on integer-valued
          operands (exact f32 sums): bit-equal, some tiles corrupted;
  serve   ``ModelBundle`` + ``FaultTolerantServer``, 8 requests (prompt 64,
          gen 32, 8 slots), ``dispatch="fused"``, once ``mode="off"`` and
          once ``mode="protected"`` with a few BIST-confirmed power-on faults
          (at most DPPU capacity): every request completes, the protected
          tokens equal the off tokens, no site fell back from the kernel, and
          the compiled decode step holds ``tpu_custom_call``;
  train   ``launch.train.make_train_step`` on a one-device mesh, batch 8,
          seq 128, ``hyca_mode="protected"``: every loss finite.  Building a
          ``hyca_dispatch="fused"`` step must raise (the Pallas kernel has no
          JVP or transpose rule).

Seconds printed on the way are host wall time, informational only, not
benchmark metrics.  The last line of stdout is one JSON object with the
device as JAX reports it.  Without a TPU the script exits non-zero at the
device check; there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ARCH = "qwen1.5-0.5b"
SEED = 0
# one-device vs (data=2, model=2), per step: bf16 compute under a different
# partitioning regroups reductions, so losses and gradient norms agree only
# to a few bf16 ulps of the summed terms, not bit for bit.  The limits sit
# between sound runs (TPU v5e, full width: loss 9.0e-5, grad norm 1.9e-3;
# CPU, smoke size: 1.2e-5, 1.8e-4) and a gradient that drops one data
# shard's rows (TPU: loss 2.0e-2, grad norm 0.96; CPU: loss 3.7e-4 to
# 9.9e-4, grad norm 3.1e-2 to 1.4e-1); the grad norm checks every step's
# reduction, the loss every update before it.
LOSS_RTOL = 2e-4
GNORM_RTOL = 5e-3


def device_check(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} count={len(devs)}", flush=True)
    if d.platform != "tpu":
        sys.exit("[device] no TPU found; chip_smoke runs only on the chip")
    if len(devs) < chips:
        sys.exit(f"[device] --chips {chips} needs {chips} devices, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _check(ok: bool, what) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _wall(label: str, t0: float) -> None:
    print(f"[info] {label}: {time.perf_counter() - t0:.3f} s wall (informational)", flush=True)


# --------------------------------------------------------------------------- #
# kernel
# --------------------------------------------------------------------------- #
def phase_kernel() -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.hyca_dla import dla_config
    from repro.core.engine import RepairPlan, fault_state_from_map
    from repro.core.ftcontext import build_ftcontext
    from repro.kernels import ref
    from repro.kernels.ops import fault_grids

    hyca = dla_config()
    rng = np.random.default_rng(SEED)
    fmap = np.zeros((hyca.rows, hyca.cols), bool)
    fmap.reshape(-1)[rng.choice(fmap.size, 3 * hyca.capacity // 2, replace=False)] = True
    state = fault_state_from_map(fmap, rng=rng)
    cm = np.roll(np.arange(hyca.cols), 5).astype(np.int32)
    prune = np.zeros((hyca.rows, hyca.cols), bool)
    prune.reshape(-1)[rng.choice(prune.size, 16, replace=False)] = True
    plan = RepairPlan(jnp.asarray(cm), jnp.asarray(prune))
    ctx = build_ftcontext(state, hyca, dispatch="fused", fused_block=(8, 128, 128), plan=plan)

    # 32 row tiles x 32 column tiles: every PE owns at least one tile
    x = jnp.asarray(rng.integers(-3, 4, (256, 1024)), jnp.float32)
    w = jnp.asarray(rng.integers(-3, 4, (1024, 4096)), jnp.float32)
    got = np.asarray(ctx.matmul(x, w, site="ffn"))
    bit, val, faulty, repaired = fault_grids(state, hyca.rows, hyca.cols, hyca.capacity)
    want = np.asarray(ref.ft_matmul_ref(
        x, w, bit[:, cm], val[:, cm], faulty[:, cm], repaired[:, cm],
        bm=8, bn=128, pe_prune=jnp.asarray(prune[:, cm]),
    ))
    clean = np.asarray(x) @ np.asarray(w)
    _check(np.array_equal(got.view(np.uint32), want.view(np.uint32)), "kernel != oracle")
    n_bad = int((got != clean).sum())
    _check(n_bad > 0, "no output was corrupted: the fault mux did not run")
    print(f"[kernel] ft_matmul (256x1024 @ 1024x4096, block 8x128x128) bit-equal to the "
          f"oracle; {n_bad} outputs corrupted or pruned", flush=True)


# --------------------------------------------------------------------------- #
# serve
# --------------------------------------------------------------------------- #
def phase_serve(*, n_requests=8, prompt_len=64, gen=32, slots=8, n_faults=6) -> None:
    """``n_faults`` power-on faults, at most the DPPU's 32."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro.obs import reset_site_fallbacks, site_fallback_total
    from repro.serving import FaultTolerantServer, ModelBundle, ServerConfig

    cfg = ServerConfig(
        arch=ARCH, smoke=False, n_slots=slots, smax=prompt_len + gen + 2,
        mode="off", rows=32, cols=32, dppu_size=32, dispatch="fused", seed=SEED,
    )
    reset_site_fallbacks()
    t0 = time.perf_counter()
    bundle = ModelBundle(cfg)
    _wall("serve: bundle build", t0)
    lm = bundle.lm
    print(f"[serve] {lm.name}: {lm.n_layers} layers, d_model {lm.d_model}, "
          f"{lm.n_heads} heads, d_ff {lm.d_ff}, vocab {lm.vocab}; "
          f"array {cfg.rows}x{cfg.cols}, DPPU {cfg.dppu_size}", flush=True)
    rng = np.random.default_rng(SEED)
    trace = [{"step": 0, "prompt": rng.integers(0, lm.vocab, prompt_len), "max_new_tokens": gen}
             for _ in range(n_requests)]

    tokens = {}
    for mode in ("off", "protected"):
        server = FaultTolerantServer(dataclasses.replace(cfg, mode=mode), bundle=bundle)
        if mode == "protected":
            server.injector.inject_n(n_faults)
            server.manager.bist()
            _check(server.manager.n_confirmed == n_faults, server.manager.counts())
        t0 = time.perf_counter()
        summary = server.run(trace, max_steps=4 * (prompt_len + gen))
        dt = time.perf_counter() - t0
        done = server.completions_by_rid()
        _check(len(done) == n_requests and all(len(t) == gen for t in done.values()),
               (mode, summary["requests_completed"], summary["requests_failed"]))
        tokens[mode] = done
        print(f"[serve] mode={mode}: {summary['requests_completed']} requests, "
              f"{summary['tokens']} tokens in {summary['steps']} steps; "
              f"faults={server.injector.n_faults} confirmed={server.manager.n_confirmed}",
              flush=True)
        print(f"[info] serve {mode}: {dt:.3f} s wall, {dt / summary['steps'] * 1e3:.3f} ms/step "
              f"incl. first-step compile (informational)", flush=True)

    same = all(np.array_equal(tokens["off"][r], tokens["protected"][r]) for r in tokens["off"])
    _check(same, "protected tokens differ from off tokens")
    _check(site_fallback_total() == {}, site_fallback_total())
    hlo = bundle.step_fn.lower(
        server.params, server.cache, jnp.zeros((slots, 1), jnp.int32),
        bundle.empty_state, bundle.identity_plan,
    ).compile().as_text()
    _check("tpu_custom_call" in hlo, "no Pallas kernel in the compiled decode step")
    print("[serve] protected tokens == off tokens; site fallbacks 0; "
          "decode step holds tpu_custom_call", flush=True)


# --------------------------------------------------------------------------- #
# train
# --------------------------------------------------------------------------- #
def _train_curves(devices, *, model: int, steps: int, batch=8,
                  seq=128) -> tuple[list[float], list[float]]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.configs.hyca_dla import dla_config
    from repro.core.engine import fault_state_from_map
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.dist.sharding import named, use_mesh
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import TrainConfig, init_state, make_train_step

    lm = get_config(ARCH)
    tc = TrainConfig(n_micro=2, hyca_mode="protected", warmup=0, total_steps=steps)
    hyca = dla_config()
    rng = np.random.default_rng(SEED)
    fmap = np.zeros((hyca.rows, hyca.cols), bool)
    fmap.reshape(-1)[rng.choice(fmap.size, 8, replace=False)] = True
    fstate = fault_state_from_map(fmap, max_faults=hyca.capacity, rng=rng)
    mesh = make_host_mesh(model=model, devices=devices)
    data = SyntheticLM(DataConfig(seed=SEED, batch=batch, seq_len=seq), lm)
    init = functools.partial(init_state, jax.random.key(SEED), lm, tc)
    batch0 = jax.tree.map(jnp.asarray, data.batch(0))
    step_fn, (sspec, _), _ = make_train_step(
        lm, tc, mesh, jax.eval_shape(init), jax.eval_shape(lambda: batch0), hyca=hyca)
    # made in place on the step's shardings (else step 1 compiles again)
    state = jax.jit(init, out_shardings=named(mesh, sspec))()
    losses, gnorms = [], []
    with use_mesh(mesh):
        for i in range(steps):
            t0 = time.perf_counter()
            state, metrics = step_fn(state, jax.tree.map(jnp.asarray, data.batch(i)), fstate)
            losses.append(float(metrics["loss"]))
            gnorms.append(float(metrics["gnorm"]))
            _wall(f"train {dict(mesh.shape)} step {i} (step 0 incl. compile)", t0)
    return losses, gnorms


def phase_train(steps: int = 3) -> None:
    import math

    import jax

    from repro.configs import get_config
    from repro.configs.hyca_dla import dla_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import TrainConfig, make_train_step

    fused = TrainConfig(hyca_mode="protected", hyca_dispatch="fused")
    try:
        make_train_step(get_config(ARCH), fused, make_host_mesh(devices=jax.devices()[:1]),
                        None, None, hyca=dla_config())
    except ValueError as e:
        print(f"[train] hyca_dispatch=fused raises at build: {str(e).splitlines()[0]}", flush=True)
    else:
        _check(False, "a fused train step built on the chip; it cannot differentiate")
    losses, _ = _train_curves(jax.devices()[:1], model=1, steps=steps)
    _check(all(math.isfinite(v) for v in losses), losses)
    print(f"[train] {ARCH} protected (twopass), batch 8 x seq 128, one device: "
          f"losses {losses}", flush=True)


def phase_mesh(steps: int = 3) -> None:
    import jax

    four = _train_curves(jax.devices()[:4], model=2, steps=steps)
    one = _train_curves(jax.devices()[:1], model=1, steps=steps)
    worst = {}
    for name, i, rtol in (("losses", 0, LOSS_RTOL), ("grad norms", 1, GNORM_RTOL)):
        print(f"[mesh] (data=2, model=2) {name} {four[i]}", flush=True)
        print(f"[mesh] one-device {name}       {one[i]}", flush=True)
        worst[name] = max(abs(a - b) / abs(b) for a, b in zip(four[i], one[i]))
        print(f"[mesh] {name}: max relative difference {worst[name]:.3e} "
              f"(tolerance {rtol:.0e})", flush=True)
    _check(worst["losses"] <= LOSS_RTOL and worst["grad norms"] <= GNORM_RTOL, worst)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the (data=2, model=2) mesh phase and its "
                         "one-device comparison")
    args = ap.parse_args(argv)

    device = device_check(args.chips)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[cache] compilation cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh()
    else:
        phase_kernel()
        phase_serve()
        phase_train()
    _wall("all phases", t0)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
