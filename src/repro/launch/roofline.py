"""§Roofline: three-term analysis per (arch × shape) on the single-pod mesh.

    compute term    = HLO_FLOPs_corrected / peaks.bf16_flops     [s]
    memory term     = HLO_bytes_corrected / peaks.hbm_bw         [s]
    collective term = collective_wire_bytes / peaks.ici_bw       [s]

with ``peaks`` the published table entry (launch.hw) of the chip the
single-pod roofline models, TPU v5e.

All three use *per-device* quantities from the trip-count-corrected probes
(launch.probes; cost_analysis counts a while body once, so production scans
are linearly reconstructed from unrolled reduced-depth probes).  MODEL_FLOPS
is the analytic ideal (6·N_active·D dense-train convention + exact attention
terms); MODEL/HLO quantifies remat + redundancy waste.

Usage: PYTHONPATH=src python -m repro.launch.roofline [--probes-dir ...]
Writes experiments/roofline.json and prints the §Roofline markdown table.
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, ShapeCell, applicable
from repro.launch.hw import peaks
from repro.models.lm import LMConfig

N_DEV = 256  # single-pod roofline (16 x 16)
DEVICE_KIND = "TPU v5 lite"  # the chip of that pod (JAX's name for TPU v5e)


def _attn_flops_fwd(cfg: LMConfig, tokens: int, seq: int, causal: bool = True) -> float:
    """Score+AV matmul FLOPs for full attention over ``seq`` per token batch."""
    if cfg.family == "ssm":
        return 0.0  # linear mixer; its state ops are counted separately
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    if cfg.attn_kind == "mla":
        qk = cfg.mla.d_nope + cfg.mla.d_rope
        per_tok = 2 * cfg.n_heads * (qk + cfg.mla.d_v) * seq
    else:
        per_tok = 2 * cfg.n_heads * 2 * hd * seq
    f = per_tok * tokens
    if causal:
        f *= 0.5
    # attention applications: every layer for transformers, only the shared
    # blocks for the hybrid arch, none for pure SSMs
    n_apps = len(_hybrid_apps(cfg)) if cfg.family == "hybrid" else cfg.n_layers
    if cfg.family == "encdec":
        n_apps = cfg.n_layers + cfg.n_enc_layers  # + cross-attn ~ self-attn cost
    return f * n_apps


def _hybrid_apps(cfg: LMConfig):
    ae = cfg.attn_every or cfg.n_layers
    return list(range(0, cfg.n_layers, ae))


def model_flops(cfg: LMConfig, cell: ShapeCell) -> float:
    """Analytic ideal FLOPs per step (global), 6ND convention for train."""
    n_active = cfg.n_active_params()
    if cell.kind == "train":
        d_tokens = cell.global_batch * cell.seq_len
        lin = 6.0 * n_active * d_tokens
        attn = 3.0 * _attn_flops_fwd(cfg, d_tokens, cell.seq_len)
        return lin + attn
    if cell.kind == "prefill":
        d_tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_active * d_tokens + _attn_flops_fwd(cfg, d_tokens, cell.seq_len)
    # decode: one token against a seq-long cache
    d_tokens = cell.global_batch
    return 2.0 * n_active * d_tokens + _attn_flops_fwd(cfg, d_tokens, cell.seq_len, causal=False)


def _advice(dominant: str, rec: dict, cfg: LMConfig, cell: ShapeCell) -> str:
    if dominant == "compute":
        return ("compute-bound: cut HLO/model-FLOP waste (remat policy, fused loss head) "
                "or it is already near the hardware ceiling")
    if dominant == "memory":
        if cell.kind == "decode":
            return ("HBM-bound on weight+KV reads: larger decode batch amortises weight "
                    "reads; quantised KV / MLA-style latent cache shrinks cache traffic")
        return ("HBM-bound: raise arithmetic intensity — bigger microbatch, fused "
                "attention (no score materialisation), bf16 activation residency")
    return ("ICI-bound: re-shard to cut per-layer collectives (sequence-parallel "
            "norms, 1-hot expert dispatch), overlap grad all-reduce with bwd, "
            "compress DP gradients")


def analyse(probes_dir: str) -> list[dict]:
    hw = peaks(DEVICE_KIND)
    rows = []
    for path in sorted(glob.glob(os.path.join(probes_dir, "*.json"))):
        rec = json.load(open(path))
        if rec.get("status") != "ok":
            rows.append(rec)
            continue
        arch, shape = rec["arch"], rec["shape"]
        cfg, cell = get_config(arch), SHAPES[shape]
        t = rec["total"]
        terms = {
            "compute": max(t["flops"], 0.0) / hw.bf16_flops,
            "memory": max(t["bytes"], 0.0) / hw.hbm_bw,
            "collective": max(t["wire_bytes"], 0.0) / hw.ici_bw,
        }
        dominant = max(terms, key=terms.get)
        bound = max(terms.values())
        mf = model_flops(cfg, cell)
        mf_dev = mf / N_DEV
        ideal = mf_dev / hw.bf16_flops
        rows.append({
            "arch": arch, "shape": shape, "status": "ok",
            "compute_s": terms["compute"], "memory_s": terms["memory"],
            "collective_s": terms["collective"], "dominant": dominant,
            "bound_s": bound,
            "model_flops_global": mf,
            "model_flops_per_dev": mf_dev,
            "hlo_flops_per_dev": t["flops"],
            "model_over_hlo": mf_dev / t["flops"] if t["flops"] else 0.0,
            "roofline_fraction": ideal / bound if bound else 0.0,
            "advice": _advice(dominant, rec, cfg, cell),
        })
    return rows


def to_markdown(rows: list[dict]) -> str:
    hdr = ("| arch | shape | compute (ms) | memory (ms) | collective (ms) | dominant | "
           "MODEL/HLO | roofline frac |\n|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        if r.get("status") != "ok":
            lines.append(f"| {r.get('arch','?')} | {r.get('shape','?')} | FAILED | | | | | |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']*1e3:.2f} | "
            f"{r['memory_s']*1e3:.2f} | {r['collective_s']*1e3:.2f} | "
            f"**{r['dominant']}** | {r['model_over_hlo']:.2f} | {r['roofline_fraction']:.1%} |"
        )
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--probes-dir", default="experiments/probes")
    ap.add_argument("--out", default="experiments/roofline.json")
    args = ap.parse_args(argv)
    rows = analyse(args.probes_dir)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(to_markdown(rows))
    ok = [r for r in rows if r.get("status") == "ok"]
    if ok:
        worst = min(ok, key=lambda r: r["roofline_fraction"])
        coll = max(ok, key=lambda r: r["collective_s"] / max(r["bound_s"], 1e-12))
        print(f"\nworst roofline fraction: {worst['arch']}/{worst['shape']} "
              f"({worst['roofline_fraction']:.1%})")
        print(f"most collective-bound:   {coll['arch']}/{coll['shape']}")


if __name__ == "__main__":
    main()
