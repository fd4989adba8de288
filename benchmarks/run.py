"""Benchmark harness — one module per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV. Module map:
  comm_time            Figures 2/3/4 (LeNet D-SGD, comm time vs r)
  staleness            §3.2 / Theorem 4 (tau sweep)
  byzantine            §4 / Theorems 5-6 (attack x rule grid)
  redundancy_tradeoff  Definition 1 (overlap -> eps -> error)
  roofline             §Roofline terms from the dry-run artifacts
  serve_latency        first-(n-r) dispatch p99 vs r + paged-engine tok/s
  agg_throughput       GradAgg host-vs-fused-device iteration (BENCH_agg)
  e2e_load             every named scenario vs real replicated engines
                       (BENCH_e2e: goodput/p99 vs r under injected faults)
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: comm_time,staleness,byzantine,"
                         "redundancy,roofline,serve,agg,e2e")
    ap.add_argument("--fast", action="store_true",
                    help="reduced iteration counts")
    ap.add_argument("--record", action="store_true",
                    help="write BENCH_serve.json (superstep K x arch "
                         "sweep) and BENCH_agg.json, each row stamped "
                         "with device count and mesh shape")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else None
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    failed = []

    def go(name, fn):
        if want and name not in want:
            return
        try:
            fn()
        except Exception as e:  # keep the harness going, fail at the end
            traceback.print_exc()
            print(f"{name},nan,ERROR:{type(e).__name__}", flush=True)
            failed.append(name)

    print("name,us_per_call,derived")

    from benchmarks import roofline
    go("roofline", roofline.main)

    from benchmarks import staleness
    go("staleness", (lambda: staleness.run(500)) if args.fast
       else staleness.main)

    from benchmarks import byzantine
    go("byzantine", (lambda: byzantine.run(400)) if args.fast
       else byzantine.main)

    from benchmarks import redundancy_tradeoff
    go("redundancy", redundancy_tradeoff.main)

    from benchmarks import comm_time
    go("comm_time", (lambda: comm_time.run(iters=30)) if args.fast
       else comm_time.main)

    from benchmarks import serve_latency
    go("serve", (lambda: serve_latency.main(200, 3, do_record=args.record,
                                            smoke=True))
       if args.fast
       else (lambda: serve_latency.main(do_record=args.record)))

    from benchmarks import agg_throughput
    # --record stamps every row with device count + mesh shape (None for
    # the replicated path); a smoke --record writes BENCH_agg.smoke.json
    # so a reduced sweep never clobbers the committed full baseline
    go("agg", (lambda: agg_throughput.main(smoke=True,
                                           record=args.record))
       if args.fast
       else (lambda: agg_throughput.main(record=args.record)))

    from benchmarks import e2e_load
    # every scenario vs real replicated engines; a --fast --record run
    # writes BENCH_e2e.smoke.json, never the committed full baseline
    go("e2e", (lambda: e2e_load.main(smoke=True, do_record=args.record))
       if args.fast
       else (lambda: e2e_load.main(do_record=args.record)))
    if failed:
        print(f"failed phases: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
