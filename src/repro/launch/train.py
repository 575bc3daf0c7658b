"""Distributed training step + CLI driver.

``make_train_step`` assembles the production pjit train step for any
(arch config × mesh):

  * microbatch gradient accumulation via ``lax.scan`` (bounds activation
    memory and keeps the HLO one-body small);
  * Megatron tensor-parallel param shardings (dist.sharding.param_specs),
    batch over ("pod","data");
  * ZeRO-1 optimizer-moment sharding over the data axes;
  * optional top-k gradient compression with error feedback;
  * optional HyCA protection: a core.ftcontext.FTContext routes every weight
    matmul (attention/FFN/expert/SSM projections + LM head) through the
    paper's fault-tolerant engine with the FaultState a traced input — fault
    tables update without recompiles.

Run ``PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b
--smoke`` for a CPU-scale training run with checkpoint/restart.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import FaultState, HyCAConfig
from repro.core.ftcontext import FTContext, ProtectPolicy, build_ftcontext, fused_backend
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist.sharding import (DEFAULT_RULES, DP_RULES, EP_RULES, named,
    param_specs, resolve_spec, use_mesh, use_rules, zero1_specs)
from repro.models.lm import LMConfig, init_params, loss_fn
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.compression import compress, ef_init
from repro.optim.schedules import cosine_warmup


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    n_micro: int = 8
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    warmup: int = 100
    total_steps: int = 1000
    grad_compress_ratio: float = 0.0   # 0 = off
    hyca_mode: str = "off"             # off | protected | unprotected
    hyca_dispatch: str = "twopass"     # plain | twopass | fused (FTContext)
    protect_fraction: float = 1.0      # fraction of main-stack layers protected
    aux_weight: float = 0.01
    # §Perf optimization: cast fp32 master params to bf16 ONCE per step
    # instead of inside every microbatch (the baseline re-reads + re-casts the
    # whole parameter set n_micro times — pure HBM traffic)
    cast_once: bool = False
    # roofline probes: unroll the microbatch loop so cost_analysis counts
    # every microbatch (XLA tallies a while body once) — production uses scan
    unroll_micro: bool = False


def make_ftc(
    tc: TrainConfig,
    hyca: HyCAConfig | None,
    state: FaultState | None,
    plan=None,
) -> FTContext | None:
    """Build the training FTContext from config (None = protection off).
    ``plan``: optional repro.repair RepairPlan (or per-site dict) — the
    fault-aware retraining path runs the forward with it active."""
    if hyca is None or tc.hyca_mode == "off" or state is None:
        return None
    hcfg = dataclasses.replace(hyca, mode=tc.hyca_mode)
    return build_ftcontext(
        state, hcfg,
        policy=ProtectPolicy(layer_fraction=tc.protect_fraction),
        dispatch=tc.hyca_dispatch,
        plan=plan,
    )


def init_state(key, cfg: LMConfig, tc: TrainConfig) -> dict:
    params = init_params(key, cfg)
    state = {"params": params, "opt": adamw_init(params)}
    if tc.grad_compress_ratio:
        state["ef"] = ef_init(params)
    return state


def state_specs(state_shapes: Any, mesh: Mesh, profile: str = "tp") -> Any:
    """Sharding specs for the full train state (profile: tp | dp)."""
    specs = {
        "params": param_specs(state_shapes["params"], mesh, profile),
        "opt": {
            "m": zero1_specs(state_shapes["opt"]["m"], mesh, profile=profile),
            "v": zero1_specs(state_shapes["opt"]["v"], mesh, profile=profile),
            "step": P(),
            "gnorm": P(),
        },
    }
    if "ef" in state_shapes:
        specs["ef"] = zero1_specs(state_shapes["ef"], mesh, profile=profile)
    return specs


def batch_specs(batch_shapes: Any, mesh: Mesh, profile: str = "tp") -> Any:
    rules = {"dp": DP_RULES, "ep": EP_RULES}.get(profile, DEFAULT_RULES)
    return jax.tree.map(
        lambda v: resolve_spec(
            ["batch"] + [None] * (len(v.shape) - 1), v.shape, mesh, rules
        ),
        batch_shapes,
    )


def _split_micro(batch: dict, n_micro: int) -> dict:
    def f(x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])

    return jax.tree.map(f, batch)


def make_train_step(
    cfg: LMConfig,
    tc: TrainConfig,
    mesh: Mesh,
    state_shapes: Any,
    batch_shapes: Any,
    *,
    hyca: HyCAConfig | None = None,
    profile: str = "tp",
    plan=None,
    grad_mask=None,
):
    """Returns (jitted_fn, in_shardings, out_shardings).

    jitted_fn(state, batch[, fault_state]) -> (state, metrics)
    ``profile``: "tp" (Megatron layout) or "dp" (replicated params, batch
    over every mesh axis — the small-arch §Perf profile).

    Repair-aware retraining hooks (repro.repair.retrain):
    ``plan`` — a RepairPlan (or per-site dict) the protected forward applies
    (closed over: fixed for this step function; the serving runtime is where
    plans swap as traced data).  ``grad_mask`` — a pytree of broadcastable
    multipliers matching ``params``; gradients are masked before the
    optimizer so frozen parameter groups stay bit-identical.

    ``hyca_dispatch="fused"`` on a TPU raises here: its Pallas kernel
    (``pallas_call``) has no JVP or transpose rule, so ``jax.grad`` cannot
    differentiate the protected forward.  Off the TPU the fused dispatch is
    the jnp formulation and trains.
    """
    if hyca is not None and tc.hyca_mode != "off" and tc.hyca_dispatch == "fused" \
            and fused_backend() == "pallas":
        raise ValueError(
            "hyca_dispatch='fused' cannot train on a TPU: the Pallas kernel "
            "has no JVP or transpose rule, so jax.grad cannot differentiate "
            "the protected forward; train with hyca_dispatch='twopass'"
        )
    rules = {"dp": DP_RULES, "ep": EP_RULES}.get(profile, DEFAULT_RULES)
    sspec = state_specs(state_shapes, mesh, profile)
    bspec = batch_specs(batch_shapes, mesh, profile)

    def _train_step(state, batch, fault_state=None):
        params = state["params"]
        if tc.cast_once:
            # one fp32->bf16 sweep per step; the model's per-stage casts
            # become no-ops, so each microbatch reads bf16 weights directly
            fwd_params = jax.tree.map(
                lambda a: a.astype(cfg.dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a,
                params,
            )
        else:
            fwd_params = params
        micro = _split_micro(batch, tc.n_micro)
        ftc = make_ftc(tc, hyca, fault_state, plan)

        def micro_step(carry, mb):
            gacc, lacc, aacc = carry
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p, cfg, mb, aux_weight=tc.aux_weight, ftc=ftc),
                has_aux=True,
            )(fwd_params)
            gacc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), gacc, grads)
            return (gacc, lacc + metrics["loss"], aacc + metrics["aux"]), None

        gzero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        init = (gzero, jnp.zeros(()), jnp.zeros(()))
        if tc.unroll_micro:
            carry = init
            for i in range(tc.n_micro):
                carry, _ = micro_step(carry, jax.tree.map(lambda a: a[i], micro))
            gsum, lsum, asum = carry
        else:
            (gsum, lsum, asum), _ = jax.lax.scan(micro_step, init, micro)
        grads = jax.tree.map(lambda g: g / tc.n_micro, gsum)
        if grad_mask is not None:
            grads = jax.tree.map(lambda g, m: g * m, grads, grad_mask)

        new_state = dict(state)
        if tc.grad_compress_ratio:
            grads, new_ef, kept = compress(grads, state["ef"], tc.grad_compress_ratio)
            new_state["ef"] = new_ef

        lr = cosine_warmup(
            state["opt"]["step"], peak_lr=tc.opt.lr, warmup=tc.warmup, total=tc.total_steps
        )
        new_params, new_opt = adamw_update(grads, state["opt"], params, tc.opt, lr)
        if grad_mask is not None:
            # zeroed grads alone don't freeze a leaf — AdamW's decoupled
            # weight decay still shifts it; gate the update so frozen
            # parameter groups stay bit-identical
            new_params = jax.tree.map(
                lambda new, old, m: jnp.where(m > 0, new, old),
                new_params, params, grad_mask,
            )
        new_state["params"] = new_params
        new_state["opt"] = new_opt
        metrics = {
            "loss": lsum / tc.n_micro,
            "aux": asum / tc.n_micro,
            "lr": lr,
            "gnorm": new_opt["gnorm"],
        }
        return new_state, metrics

    def train_step(state, batch, fault_state=None):
        with use_rules(rules):  # active at trace time -> model shard() calls
            return _train_step(state, batch, fault_state)

    in_sh = (named(mesh, sspec), named(mesh, bspec))
    out_sh = (named(mesh, sspec), None)
    fn = jax.jit(
        train_step,
        in_shardings=in_sh + (None,),
        out_shardings=out_sh,
        donate_argnums=(0,),
    )
    return fn, (sspec, bspec), sspec


# --------------------------------------------------------------------------- #
# CLI driver (CPU-scale)
# --------------------------------------------------------------------------- #
def main(argv=None):
    from repro.checkpoint.store import CheckpointManager
    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--compress", type=float, default=0.0)
    ap.add_argument("--hyca-mode", default="off", choices=["off", "protected", "unprotected"])
    ap.add_argument("--hyca-dispatch", default="twopass", choices=["plain", "twopass", "fused"])
    ap.add_argument("--protect-fraction", type=float, default=1.0)
    ap.add_argument("--hyca-faults", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write per-step train.step events as JSONL to PATH "
                         "and a final-summary gauge file to PATH.prom "
                         "(docs/observability.md)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tc = TrainConfig(
        n_micro=args.n_micro,
        opt=AdamWConfig(lr=args.lr),
        total_steps=args.steps,
        warmup=max(1, args.steps // 10),
        grad_compress_ratio=args.compress,
        hyca_mode=args.hyca_mode,
        hyca_dispatch=args.hyca_dispatch,
        protect_fraction=args.protect_fraction,
    )
    mesh = make_host_mesh()
    key = jax.random.key(args.seed)
    state = init_state(key, cfg, tc)
    data = SyntheticLM(DataConfig(seed=args.seed, batch=args.batch, seq_len=args.seq), cfg)
    batch0 = jax.tree.map(jnp.asarray, data.batch(0))
    state_shapes = jax.eval_shape(lambda: state)
    batch_shapes = jax.eval_shape(lambda: batch0)

    hyca_cfg = fault_state = None
    if args.hyca_mode != "off":
        from repro.core.fault_models import random_fault_maps
        from repro.core.engine import fault_state_from_map
        hyca_cfg = HyCAConfig(rows=32, cols=32, mode=args.hyca_mode)
        fmap = np.zeros((32, 32), bool)
        rng = np.random.default_rng(args.seed)
        idx = rng.choice(32 * 32, size=args.hyca_faults, replace=False)
        fmap.reshape(-1)[idx] = True
        fault_state = fault_state_from_map(fmap, max_faults=max(args.hyca_faults, 1))

    step_fn, _, _ = make_train_step(cfg, tc, mesh, state_shapes, batch_shapes, hyca=hyca_cfg)

    mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every) if args.ckpt_dir else None
    start = 0
    if mgr is not None:
        resumed = mgr.resume(state_shapes)
        if resumed is not None:
            start, state = resumed
            print(f"[train] resumed from step {start}")

    log = None
    if args.metrics_out:
        from repro.obs.events import EventLog

        log = EventLog()

    last_loss = last_gnorm = None
    with use_mesh(mesh):
        for step in range(start, args.steps):
            batch = jax.tree.map(jnp.asarray, data.batch(step))
            t0 = time.perf_counter()
            state, metrics = step_fn(state, batch, fault_state)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            last_loss, last_gnorm = loss, float(metrics["gnorm"])
            if log is not None:
                log.step = step
                log.emit("train.step", loss=loss, lr=float(metrics["lr"]),
                         gnorm=last_gnorm, ms=dt * 1e3)
            if step % max(1, args.steps // 20) == 0 or step == args.steps - 1:
                print(f"[train] step {step:5d} loss {loss:8.4f} lr {float(metrics['lr']):.2e} gnorm {float(metrics['gnorm']):7.3f} {dt*1e3:7.1f} ms")
            if mgr is not None:
                mgr.maybe_save(step + 1, state, {"arch": cfg.name})
    if log is not None:
        from repro.obs.export import write_metrics_out

        times = [e.data["ms"] for e in log.of_kind("train.step")]
        summary = {
            "steps": len(times),
            "loss_final": last_loss,
            "gnorm_final": last_gnorm,
            "step_ms_mean": sum(times) / len(times) if times else None,
        }
        path, prom = write_metrics_out(
            args.metrics_out, summary, log,
            labels={"arch": cfg.name, "hyca_mode": args.hyca_mode},
        )
        print(f"[train] metrics: events -> {path}  summary -> {prom}")
    return state


if __name__ == "__main__":
    main()
