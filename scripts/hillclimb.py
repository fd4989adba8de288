import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Perf hillclimb driver (EXPERIMENTS.md §Perf phase 1).

Runs tagged dry-run variants of the three chosen cells and prints
before/after roofline terms. Each variant is one hypothesis from the log.

    PYTHONPATH=src python scripts/hillclimb.py [--only rwkv,qwen,dsv2]
"""
import argparse     # noqa: E402
import json         # noqa: E402

import jax          # noqa: E402

from repro.launch.dryrun import RESULTS_DIR, run_cell   # noqa: E402

PEAK, HBM, LINK = 197e12, 819e9, 50e9


def terms(rec):
    h = rec.get("hlo", {})
    return (h.get("flops", 0) / PEAK, h.get("hbm_bytes", 0) / HBM,
            h.get("collective_bytes", 0) / LINK)


def report(name, base_rec, var_rec):
    bc, bm, bl = terms(base_rec)
    vc, vm, vl = terms(var_rec)
    def frac(c, m, l):
        mx = max(c, m, l, 1e-30)
        return c / mx
    print(f"--- {name}")
    print(f"  base: compute {bc:9.3f}s memory {bm:9.3f}s coll {bl:8.3f}s "
          f"frac {frac(bc,bm,bl):.3f}")
    print(f"  var : compute {vc:9.3f}s memory {vm:9.3f}s coll {vl:8.3f}s "
          f"frac {frac(vc,vm,vl):.3f}")
    dom_b = max((bm, 'memory'), (bc, 'compute'), (bl, 'collective'))
    dom = {"memory": (bm, vm), "compute": (bc, vc),
           "collective": (bl, vl)}[dom_b[1]]
    if dom[0] > 0:
        print(f"  dominant({dom_b[1]}): {dom[0]:.3f} -> {dom[1]:.3f} "
              f"({100*(1-dom[1]/dom[0]):+.1f}% reduction)")


def load(arch, shape, tag=""):
    nm = f"{arch}__{shape}__single" + (f"__{tag}" if tag else "")
    with open(os.path.join(RESULTS_DIR, nm + ".json")) as f:
        return json.load(f)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--variants", default="")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else None
    vwant = set(args.variants.split(",")) if args.variants else None

    def go(key, arch, shape, tag, **kw):
        if want and key not in want:
            return
        if vwant and tag not in vwant:
            return
        rec = run_cell(arch, shape, False, tag=tag, **kw)
        jax.clear_caches()
        status = "OK" if rec.get("ok") else f"FAIL {rec.get('error')}"
        print(f"[{status}] {arch} {shape} {tag}")
        if rec.get("ok"):
            report(f"{arch}/{shape} [{tag}]", load(arch, shape), rec)

    # --- cell 1: rwkv6-3b train_4k (worst roofline fraction; memory) ----
    # hypothesis: 4096 sequential WKV state updates round-trip the state
    # through HBM each step; chunked-parallel form (C=32) cuts sequential
    # depth 128x and turns the work MXU-shaped.
    go("rwkv", "rwkv6-3b", "train_4k", "wkv32",
       cfg_patch={"rwkv.chunk": 32})
    go("rwkv", "rwkv6-3b", "prefill_32k", "wkv32",
       cfg_patch={"rwkv.chunk": 32})

    # --- cell 2: qwen2-0.5b train_4k (most collective-bound) ------------
    # hypothesis: TP=16 over-shards a 0.5B model (per-layer TP all-reduces
    # dominate); retasking the "model" axis as a second DP/ZeRO axis
    # removes TP collectives entirely (grads RS only) at replicated-weight
    # memory cost that a 0.5B model easily affords.
    go("qwen", "qwen2-0.5b", "train_4k", "dp_all", layout="dp_all")

    # --- cell 3: deepseek-v2-236b train_4k (paper-representative MoE) ---
    # hypothesis A: full remat recomputes the MoE dispatch in bwd;
    # policy "dots" saves matmul outputs, trading HBM for flops.
    go("dsv2", "deepseek-v2-236b", "train_4k", "remat_dots",
       tc_kw={"remat_policy": "dots"})


if __name__ == "__main__":
    main()
