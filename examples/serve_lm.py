"""Continuous-batching serving example on the paged KV/SSM cache.

A mixed-length request stream flows through a fixed pool of decode slots
and a paged cache (repro.serve): requests admit when a slot + pages free
up, decode as one ragged batch, and retire slot-by-slot — no
pad-to-max_len cache, no head-of-batch stragglers. Works for every
registry arch family (attention, MLA, SSM/RWKV, hybrid, MoE).

    PYTHONPATH=src python examples/serve_lm.py --arch rwkv6-3b --requests 6
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.models.model import init_model
from repro.serve import PagedCacheConfig, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--num-pages", type=int, default=33)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    params = init_model(jax.random.PRNGKey(args.seed), cfg, max_pos=256)
    rng = np.random.default_rng(args.seed)

    ccfg = PagedCacheConfig(
        num_slots=args.slots, page_size=args.page_size,
        num_pages=args.num_pages,
        max_pages_per_seq=-(-(args.max_prompt + args.max_new)
                            // args.page_size))
    engine = ServeEngine(params, cfg, ccfg)

    reqs = []
    for _ in range(args.requests):
        s0 = int(rng.integers(4, args.max_prompt + 1))
        new = int(rng.integers(2, args.max_new + 1))
        prompt = rng.integers(0, cfg.vocab_size, s0).astype(np.int32)
        reqs.append((engine.submit(prompt, new), s0, new))

    t0 = time.time()
    out = engine.run()
    dt = time.time() - t0

    total_new = sum(new for _, _, new in reqs)
    print(f"arch={args.arch} served {args.requests} requests "
          f"({total_new} tokens) through {args.slots} slots in {dt:.2f}s "
          f"({total_new / max(dt, 1e-9):.1f} tok/s)")
    print(f"engine stats: {engine.stats}; peak slots in use: "
          f"{engine.sched.peak_active}; pages free at end: "
          f"{engine.kv.alloc.n_free}/{ccfg.num_pages - 1}")
    for rid, s0, new in reqs:
        print(f"  req {rid}: prompt {s0:3d} tokens -> {out[rid].tolist()}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
