"""Roofline analysis from the dry-run artifacts (deliverable g).

Per (arch x shape x mesh): the three roofline terms from the while-aware
HLO analysis of the compiled SPMD program, the dominant bottleneck, analytic
MODEL_FLOPS and the useful-compute ratio.

    compute_s    = HLO_FLOPs_per_device / 197 TF/s   (bf16 peak, v5e)
    memory_s     = HLO_bytes_per_device / 819 GB/s
    collective_s = wire_bytes_per_device / 50 GB/s   (ICI per link)

Roofline fraction = compute_s / max(terms): the share of the (perfectly
overlapped) step occupied by useful math — this is the score §Perf pushes.

CPU-backend caveat (documented in EXPERIMENTS.md): XLA-CPU emulates bf16
dots in f32, so byte-based terms are inflated ~2x vs a TPU lowering; the
analysis is self-consistent across iterations (same lowering rules), which
is what the hillclimb needs.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, Optional

from repro.configs.base import ArchConfig, ShapeConfig, get_shape
from repro.configs.registry import get_config

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """Analytic MODEL_FLOPS for the *global* step.

    Train: 6 * N_active * tokens (fwd+bwd matmuls, no remat) + causal
    attention term 12 * L_attn * H * hd * S/2 per token (x3 for bwd).
    Decode: 2 * N_active per token + 4 * L_attn * H * hd * S_cache.
    """
    n_active = cfg.active_param_count() if cfg.moe else cfg.param_count()
    hd = cfg.resolved_head_dim
    l_attn = sum(1 for k in cfg.layer_pattern
                 if k == "attn") * cfg.n_periods
    if cfg.encoder_decoder:
        l_attn += cfg.encoder_layers
    s, b = shape.seq_len, shape.global_batch

    if shape.kind == "train":
        tokens = b * s
        matmul = 6.0 * n_active * tokens
        attn = 3.0 * (4.0 * cfg.n_heads * hd * (s / 2)) * l_attn * tokens
        return matmul + attn
    if shape.kind == "prefill":
        tokens = b * s
        return 2.0 * n_active * tokens + \
            (4.0 * cfg.n_heads * hd * (s / 2)) * l_attn * tokens
    # decode: one token, cache length s
    tokens = b * 1
    return 2.0 * n_active * tokens + \
        (4.0 * cfg.n_heads * hd * s) * l_attn * tokens


def load_records(results_dir: str = RESULTS, tag: str = ""):
    recs = []
    for path in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        base = os.path.basename(path)[:-5]
        parts = base.split("__")
        if tag and (len(parts) < 4 or parts[3] != tag):
            continue
        if not tag and len(parts) > 3:
            continue
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def roofline_row(rec: Dict) -> Optional[Dict]:
    if not rec.get("ok") or "hlo" not in rec:
        return {"arch": rec.get("arch"), "shape": rec.get("shape"),
                "mesh": rec.get("mesh"), "ok": False,
                "error": rec.get("error", "?")[:100]}
    cfg = get_config(rec["arch"])
    shape = get_shape(rec["shape"])
    chips = rec["chips"]
    h = rec["hlo"]
    compute_s = h["flops"] / PEAK_FLOPS
    memory_s = h["hbm_bytes"] / HBM_BW
    coll_s = h["collective_bytes"] / LINK_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": coll_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    useful = mf / chips / max(h["flops"], 1.0)
    frac = compute_s / max(max(terms.values()), 1e-30)
    row = {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "ok": True, "chips": chips,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dominant,
        "model_flops": mf, "hlo_flops_dev": h["flops"],
        "useful_ratio": useful, "roofline_frac": frac,
        "temp_gb": rec.get("memory", {}).get("temp_size_in_bytes", 0) / 1e9,
        "fits_hbm": rec.get("memory", {}).get("temp_size_in_bytes", 0)
        + rec.get("memory", {}).get("argument_size_in_bytes", 0) < 16e9,
    }
    return row


def table(rows, mesh: str = "single"):
    lines = [
        "| arch | shape | compute_s | memory_s | collective_s | dominant |"
        " useful | frac | temp GB |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if not r.get("ok"):
            lines.append(f"| {r['arch']} | {r['shape']} | FAIL "
                         f"{r.get('error','')} | | | | | | |")
            continue
        if r["mesh"] != mesh:
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3f} | "
            f"{r['memory_s']:.3f} | {r['collective_s']:.3f} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_frac']:.3f} | {r['temp_gb']:.1f} |")
    return "\n".join(lines)


def main(out_csv: bool = True):
    rows = [roofline_row(r) for r in load_records()]
    rows = [r for r in rows if r]
    print("name,us_per_call,derived")
    for r in rows:
        if not r.get("ok"):
            print(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']},nan,FAIL")
            continue
        step_s = max(r["compute_s"], r["memory_s"], r["collective_s"])
        print(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']},"
              f"{step_s*1e6:.0f},"
              f"frac={r['roofline_frac']:.3f};dom={r['dominant']};"
              f"useful={r['useful_ratio']:.2f}")
    md = table(rows, "single")
    out = os.path.join(os.path.dirname(__file__), "..", "results",
                       "roofline_table.md")
    with open(out, "w") as f:
        f.write(md + "\n")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
