"""Serving tail latency vs redundancy r + engine throughput (DESIGN.md §9/§12).

Two measurements:

1. ``serve/dispatch_r{r}`` — the paper's first-(n-r) waiting rule applied
   to replicated inference, simulated with the §5 heavy-tail LatencyModel
   (3 stragglers x10): p50/p99 round latency vs the wait-for-all baseline
   and whether the answered tokens match it (they must — honest replicas
   are deterministic copies).
2. ``serve/engine`` — real tokens/s of the paged continuous-batching
   engine on reduced registry archs (CPU-scale smoke of the actual decode
   path), sweeping the decode-superstep length K. The workload is run
   once as a *warmup* on the same engine before the timed run, so jit
   compile time never folds into the first measurement; ``--record``
   writes the K x arch sweep to BENCH_serve.json (the serving analogue of
   BENCH_agg.json), including the host_syncs-per-token figure and a
   token-parity check of every K against the K=1 conformance path.
3. ``serve/prefix`` — the DESIGN.md §13 prefix cache under a flash-crowd
   workload: a burst of requests sharing one long system-prompt prefix
   (``prefix_mix_requests``) drained once on the FIFO/no-cache baseline
   and once with ``prefix_cache="on"`` + the SLA policy. Reported per
   share mix (0%, 50%, 90%): p99 TTFT (wall seconds submit -> first
   token, queueing included) for both engines, the speedup, cached tok/s
   and the cache hit rate — with a token-parity check of every cached
   stream against the baseline. The cache is reset before each timed
   pass so the measurement always starts cold.

    PYTHONPATH=src python benchmarks/serve_latency.py \
        [--smoke] [--superstep-k K] [--prefix-share S] [--record]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

from repro.core.async_engine import default_latency
from repro.serve.dispatch import (DispatchConfig, RedundantDispatcher,
                                  honest_tokens, tail_latency)

N_REPLICAS = 10

RECORD_ARCHS = ("qwen2-0.5b", "deepseek-v2-236b")
RECORD_KS = (1, 4, 8, 16)
PREFIX_SHARES = (0.0, 0.5, 0.9)
BENCH_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_serve.json"


def _replica_fn(j, request):
    return honest_tokens(request, length=16)


def run_dispatch(n_requests: int = 2000, seed: int = 0,
                 n_replicas: int = N_REPLICAS):
    """Stand-in replica p50/p99 vs r. ``n_replicas`` is overridable so
    benchmarks/e2e_load.py can record this curve at the real fleet's
    size next to the real-engine one."""
    rng = np.random.default_rng(seed)
    reqs = [rng.integers(0, 256, 8).astype(np.int32)
            for _ in range(n_requests)]
    rows = []
    for r in (0, 1, 2, 3):
        lat = default_latency(n_replicas, n_stragglers=3, factor=10.0,
                              seed=3)
        d = RedundantDispatcher(
            _replica_fn, DispatchConfig(n_replicas=n_replicas, r=r),
            latency=lat)
        t0 = time.time()
        toks, lats = d.serve(reqs)
        wall = time.time() - t0
        d.reseed()
        toks_all, lats_all = d.serve(reqs, wait_for_all=True)
        match = all(np.array_equal(a, b) for a, b in zip(toks, toks_all))
        rows.append(dict(
            r=r, n_replicas=n_replicas, p50=tail_latency(lats, 50),
            p99=tail_latency(lats, 99),
            p99_all=tail_latency(lats_all, 99), match=match, wall_s=wall))
    return rows


def _requests(cfg, n_requests: int, seed: int):
    """Mixed-length prompts with budgets big enough that the scheduler's
    budget-bounded K actually reaches the cap (DESIGN.md §12)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n_requests):
        s0 = int(rng.integers(4, 17))
        new = int(rng.integers(24, 33))
        reqs.append((rng.integers(0, cfg.vocab_size, s0).astype(np.int32),
                     new))
    return reqs


def run_engine(n_requests: int = 8, seed: int = 0, arch: str = "qwen2-0.5b",
               superstep_k: int = 8, warmup: bool = True,
               repeats: int = 1, tp: int = 1):
    """Timed drain of a mixed-length workload at one superstep length.

    The identical workload is submitted and drained once first on the
    same engine (same prefill shape buckets, same K sequence), so the
    timed pass measures steady-state tok/s, not XLA compilation; the
    drain is repeated ``repeats`` times and the best wall time reported
    (a single drain is ~0.1 s at reduced scale — too noisy to compare
    K values on a shared machine).
    """
    import jax
    from repro.configs.registry import get_config
    from repro.models.model import init_model
    from repro.serve import PagedCacheConfig, ServeEngine

    cfg = get_config(arch).reduced()
    params = init_model(jax.random.PRNGKey(seed), cfg, max_pos=128)
    mesh = None
    if tp > 1:                        # TP-meshed engine (DESIGN.md §14)
        if jax.device_count() % tp:
            raise ValueError(f"tp={tp} does not divide "
                             f"{jax.device_count()} devices")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((jax.device_count() // tp, tp), ("data", "model"))
    engine = ServeEngine(params, cfg, PagedCacheConfig(
        num_slots=2, page_size=8, num_pages=16, max_pages_per_seq=6),
        superstep_k=superstep_k, mesh=mesh)
    reqs = _requests(cfg, n_requests, seed)
    total = sum(n for _, n in reqs)
    if warmup:                       # compile prefill buckets + every K
        for p, n in reqs:
            engine.submit(p, n)
        engine.run()
    wall = float("inf")
    for _ in range(max(repeats, 1)):
        base = dict(engine.stats)    # timed pass reports deltas only
        rids = [engine.submit(p, n) for p, n in reqs]
        t0 = time.time()
        out = engine.run()
        wall = min(wall, time.time() - t0)
    syncs = engine.stats["host_syncs"] - base["host_syncs"]
    return dict(arch=arch, superstep_k=superstep_k, tokens=total,
                devices=jax.device_count(), tp=tp,
                mesh=dict(mesh.shape) if mesh is not None else None,
                wall_s=wall, tok_s=total / max(wall, 1e-9),
                host_syncs=syncs, syncs_per_token=syncs / total,
                supersteps=engine.stats["supersteps"] - base["supersteps"],
                decode_steps=engine.stats["decode_steps"]
                - base["decode_steps"],
                prefill_calls=engine.stats["prefill_calls"]
                - base["prefill_calls"],
                n_requests=n_requests,
                generated={rid: out[rid].tolist() for rid in rids})


def run_engine_sweep(n_requests: int = 8, seed: int = 0,
                     repeats: int = 5):
    """K x arch sweep with a token-parity check of every K against the
    K=1 host-loop conformance reference (identical streams required)."""
    rows = []
    for arch in RECORD_ARCHS:
        base = None
        for k in RECORD_KS:
            row = run_engine(n_requests=n_requests, seed=seed, arch=arch,
                             superstep_k=k, repeats=repeats)
            if k == 1:
                base = row
                row["match"] = True
                row["speedup_vs_k1"] = 1.0
            else:
                row["match"] = row["generated"] == base["generated"]
                row["speedup_vs_k1"] = row["tok_s"] / base["tok_s"]
            rows.append(row)
    return rows


def _drain_ttft(engine, reqs, new_tokens: int):
    """Submit a burst, drain it, and report per-request TTFT.

    Every request is submitted before the drain starts, so TTFT folds in
    the queueing delay behind slower admissions — exactly the tail the
    prefix cache is supposed to cut."""
    base = dict(engine.stats)
    rids = [engine.submit(p, new_tokens) for p in reqs]
    t0 = time.time()
    out = engine.run()
    wall = time.time() - t0
    ttfts = np.asarray([engine.sched.finished[r].ttft for r in rids])
    return out, rids, wall, ttfts, base


def run_prefix(share: float, n_requests: int = 16, seed: int = 0,
               arch: str = "qwen2-0.5b", prefix_len: int = 152,
               suffix_len: int = 4, new_tokens: int = 6,
               repeats: int = 2):
    """Flash-crowd comparison at one prefix-share mix: FIFO/no-cache
    baseline vs prefix_cache="on" + SLA policy over the identical
    ``prefix_mix_requests`` burst. Both engines are warmed on the same
    workload first; the cached engine's index is reset before every
    timed pass so hits are earned inside the measurement, not inherited
    from warmup. Streams must match token-for-token."""
    import jax
    from repro.configs.registry import get_config
    from repro.models.model import init_model
    from repro.serve import PagedCacheConfig, ServeEngine
    from repro.serve.dispatch import prefix_mix_requests

    cfg = get_config(arch).reduced()
    total = prefix_len + suffix_len + new_tokens
    params = init_model(jax.random.PRNGKey(seed), cfg, max_pos=2 * total)
    ccfg = PagedCacheConfig(
        num_slots=2, page_size=8,
        num_pages=96, max_pages_per_seq=(total + 7) // 8 + 1)
    reqs = prefix_mix_requests(n_requests, share, prefix_len=prefix_len,
                               suffix_len=suffix_len, vocab=cfg.vocab_size,
                               seed=seed)

    base_eng = ServeEngine(params, cfg, ccfg, superstep_k=8)
    hit_eng = ServeEngine(params, cfg, ccfg, superstep_k=8,
                          prefix_cache="on", policy="sla")
    for eng in (base_eng, hit_eng):         # compile prefill buckets + K
        _drain_ttft(eng, reqs, new_tokens)

    best = {}
    for eng, tag in ((base_eng, "base"), (hit_eng, "cached")):
        for _ in range(max(repeats, 1)):
            if tag == "cached":
                eng.reset_prefix_cache()     # timed pass starts cold
            out, rids, wall, ttfts, stats0 = _drain_ttft(
                eng, reqs, new_tokens)
            p99 = tail_latency(ttfts, 99)
            if tag not in best or p99 < best[tag]["p99_ttft"]:
                best[tag] = dict(
                    p99_ttft=p99, p50_ttft=tail_latency(ttfts, 50),
                    wall_s=wall,
                    tok_s=n_requests * new_tokens / max(wall, 1e-9),
                    out=[out[r] for r in rids], stats0=stats0, eng=eng)

    b, c = best["base"], best["cached"]
    eng, stats0 = c.pop("eng"), c.pop("stats0")
    b.pop("eng"), b.pop("stats0")
    hit = eng.stats["cache_hit_tokens"] - stats0["cache_hit_tokens"]
    miss = eng.stats["cache_miss_tokens"] - stats0["cache_miss_tokens"]
    match = all(np.array_equal(x, y)
                for x, y in zip(b.pop("out"), c.pop("out")))
    return dict(
        share=share, arch=arch, n_requests=n_requests,
        prefix_len=prefix_len, suffix_len=suffix_len,
        new_tokens=new_tokens, base=b, cached=c,
        speedup_p99_ttft=b["p99_ttft"] / max(c["p99_ttft"], 1e-9),
        hit_rate=hit / max(hit + miss, 1), match=match)


def run_prefix_sweep(n_requests: int = 16, seed: int = 0,
                     repeats: int = 2):
    return [run_prefix(s, n_requests=n_requests, seed=seed,
                       repeats=repeats) for s in PREFIX_SHARES]


def _print_prefix(row) -> None:
    print(f"serve/prefix_share{int(row['share'] * 100)},"
          f"{row['cached']['wall_s'] * 1e6:.0f},"
          f"p99_ttft_base={row['base']['p99_ttft'] * 1e3:.1f}ms;"
          f"p99_ttft_cached={row['cached']['p99_ttft'] * 1e3:.1f}ms;"
          f"x_p99_ttft={row['speedup_p99_ttft']:.2f};"
          f"cached_tok_s={row['cached']['tok_s']:.1f};"
          f"hit_rate={row['hit_rate']:.2f};match={int(row['match'])}")


def record(rows_dispatch, rows_engine, rows_prefix, engine_requests: int,
           smoke: bool) -> None:
    import jax
    payload = {
        "meta": {
            "backend": jax.default_backend(),
            "devices": jax.device_count(),
            "archs": list(RECORD_ARCHS),
            "superstep_ks": list(RECORD_KS),
            "engine_requests": engine_requests,
            "smoke": smoke,      # a reduced sweep must be visibly reduced
            "prefix_shares": list(PREFIX_SHARES),
            "note": "reduced() registry archs; warmed jit; tok/s is a "
                    "drained mixed-length workload (DESIGN.md §12); "
                    "prefix rows are cold-cache flash-crowd TTFT "
                    "(DESIGN.md §13)",
        },
        "dispatch": [{k: v for k, v in r.items()} for r in rows_dispatch],
        "engine": [{k: v for k, v in r.items() if k != "generated"}
                   for r in rows_engine],
        "prefix": rows_prefix,
    }
    # a reduced sweep must never clobber the committed full baseline
    path = BENCH_PATH.with_suffix(".smoke.json") if smoke else BENCH_PATH
    path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {path}")


def main(n_requests: int = 2000, engine_requests: int = 8,
         superstep_k: int = 8, do_record: bool = False,
         smoke: bool = False, prefix_share: float | None = None,
         tp: int = 1):
    if tp > 1 and not do_record:
        # sharded engine smoke (CI stage 9): the TP-meshed engine must be
        # token-identical to the replicated one on the same workload
        ref = run_engine(engine_requests, superstep_k=superstep_k)
        row = run_engine(engine_requests, superstep_k=superstep_k, tp=tp)
        match = row["generated"] == ref["generated"]
        print(f"serve/engine_tp{tp}_{row['arch']}_k{row['superstep_k']},"
              f"{row['wall_s'] * 1e6:.0f},"
              f"tok_s={row['tok_s']:.1f};mesh={row['mesh']};"
              f"match={int(match)}")
        assert match, "tp engine streams diverged from replicated"
        return
    if prefix_share is not None and not do_record:
        # the §13 comparison alone (CI stage 8 runs this under --smoke)
        row = run_prefix(prefix_share,
                         n_requests=6 if smoke else 16,
                         repeats=1 if smoke else 2)
        _print_prefix(row)
        assert row["match"], "cached streams diverged from baseline"
        return
    rows_dispatch = run_dispatch(n_requests)
    for row in rows_dispatch:
        print(f"serve/dispatch_r{row['r']},{row['wall_s'] * 1e6:.0f},"
              f"p50={row['p50']:.3f};p99={row['p99']:.3f};"
              f"p99_all={row['p99_all']:.3f};match={int(row['match'])}")
    if do_record:
        rows_engine = run_engine_sweep(engine_requests)
        for row in rows_engine:
            print(f"serve/engine_{row['arch']}_k{row['superstep_k']},"
                  f"{row['wall_s'] * 1e6:.0f},"
                  f"tok_s={row['tok_s']:.1f};"
                  f"x_vs_k1={row['speedup_vs_k1']:.2f};"
                  f"syncs_per_tok={row['syncs_per_token']:.3f};"
                  f"match={int(row['match'])}")
        rows_prefix = run_prefix_sweep(n_requests=6 if smoke else 16,
                                       repeats=1 if smoke else 2)
        for row in rows_prefix:
            _print_prefix(row)
        record(rows_dispatch, rows_engine, rows_prefix, engine_requests,
               smoke)
        return
    row = run_engine(engine_requests, superstep_k=superstep_k)
    print(f"serve/engine_{row['arch']}_k{row['superstep_k']},"
          f"{row['wall_s'] * 1e6:.0f},"
          f"tok_s={row['tok_s']:.1f};"
          f"syncs_per_tok={row['syncs_per_token']:.3f};"
          f"decodes={row['decode_steps']};"
          f"prefills={row['prefill_calls']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes for CI")
    ap.add_argument("--superstep-k", type=int, default=8,
                    help="decode superstep length for the engine run")
    ap.add_argument("--record", action="store_true",
                    help="run the K x arch sweep and commit "
                         "BENCH_serve.json")
    ap.add_argument("--prefix-share", type=float, default=None,
                    help="run only the §13 prefix-cache comparison at "
                         "this share mix (e.g. 0.9)")
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the TP-meshed engine parity smoke at "
                         "this tensor-parallel degree (needs "
                         "device_count %% tp == 0)")
    args = ap.parse_args()
    if args.smoke:
        main(n_requests=200, engine_requests=3,
             superstep_k=args.superstep_k, do_record=args.record,
             smoke=True, prefix_share=args.prefix_share, tp=args.tp)
    else:
        main(superstep_k=args.superstep_k, do_record=args.record,
             prefix_share=args.prefix_share, tp=args.tp)
