"""Paper §5 reproduction: LeNet (431,080 params) D-SGD with n=20 agents,
r in {0,1,3,5,10,15} — accuracy parity + cumulative-communication-time
reduction (Figures 2/3/4 trends).

MNIST is not shipped in this container; a documented distributional
stand-in (same shapes/protocol) is used — see EXPERIMENTS.md.

    PYTHONPATH=src python examples/async_mnist.py [--iters 120]
"""
import argparse

from benchmarks.comm_time import run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=120)
    ap.add_argument("--r", type=int, nargs="*",
                    default=[0, 1, 3, 5, 10, 15])
    args = ap.parse_args()
    rows = run(iters=args.iters, r_values=tuple(args.r))
    base = rows[0]["cum_comm"]
    print(f"\n{'r':>3} {'accuracy':>9} {'cum comm (s)':>13} {'speedup':>8}")
    for row in rows:
        print(f"{row['r']:>3} {row['acc']:>9.3f} {row['cum_comm']:>13.1f} "
              f"{base / row['cum_comm']:>7.2f}x")
    print("\npaper's claim: accuracy comparable across r; comm time drops "
          "fastest for the first few r (few very slow stragglers).")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
