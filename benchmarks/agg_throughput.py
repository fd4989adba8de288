"""GradAgg server-iteration throughput: host f64 reference pipeline vs
the device-resident fused path (DESIGN.md §11) — the repo's first
tracked perf baseline.

Per (rule, n_agents, P) cell, two measurements of one *server iteration*
(aggregate -> step-size scale -> project_ball):

- ``host``  exactly what ``AsyncEngine`` does with ``agg_backend="host"``:
  re-stack the (n, P) f64 matrix, run the eager-mode reference rule,
  apply + project on the host iterate.
- ``fused`` the ``agg_backend="device"`` path: the gradient stack is
  already resident in a ``GradLedger`` and the whole iteration is one
  jitted ``make_aggregate_apply`` dispatch. The incremental ledger
  scatter (the per-round upload the resident buffer still pays) is
  timed separately as ``upload``.

P sweeps the flat model sizes from LeNet (the paper's 431k-param model)
up to qwen2-1.5b; flat sizes above ``--max-elems / n`` are benchmarked
at the capped P with the nominal size recorded (a (n, 1.5e9) f64 host
stack plus eager temporaries does not fit a CPU host — the cap is
explicit in the row, never silent).

    PYTHONPATH=src python benchmarks/agg_throughput.py [--smoke] \
        [--out BENCH_agg.json]

Wired into ``benchmarks/run.py`` and CI stage 6 (``--smoke``).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

RULES = (("sum", 0), ("mean", 0), ("cge", 1), ("trimmed_mean", 1),
         ("quantized", 0))
# (label, nominal flat size): LeNet exact; LMs from configs (eval_shape)
SIZES = (("lenet", 431_080),
         ("qwen2-0.5b", 494_032_768),
         ("qwen2-1.5b", 1_543_714_304))
N_AGENTS = (8, 20)                   # paper experiments use n=20
GAMMA = 1e6
ETA = 0.01


def _stack(n: int, p: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    # block-fill: full-size normal() at 1.5e9/32M scale dominates the
    # benchmark setup otherwise
    base = rng.normal(size=(n, min(p, 1 << 20))).astype(np.float32)
    reps = -(-p // base.shape[1])
    return np.tile(base, (1, reps))[:, :p]


def _time(fn, repeats: int) -> float:
    fn()                                       # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats


def bench_cell(rule: str, f: int, n: int, p: int, repeats: int) -> dict:
    import jax.numpy as jnp

    from repro.core import gradagg
    from repro.core.ledger import GradLedger, make_aggregate_apply

    g_src = _stack(n, p)
    received = np.ones(n, bool)
    received[-1] = False                       # one straggler dropped
    idx = np.nonzero(received)[0]
    x0 = np.zeros(p, np.float64)

    # -- host reference pipeline (AsyncEngine agg_backend="host") -------
    host_rule = gradagg.make_gradagg(rule, f=f)

    def host_iter():
        g = np.zeros((n, p))
        g[idx] = g_src[idx]
        agg = host_rule(np.asarray(g, np.float64), received)
        return np.asarray(gradagg.project_ball(
            np.asarray(x0 - ETA * np.asarray(agg)), GAMMA))

    host_s = _time(host_iter, repeats)

    # -- fused device path (agg_backend="device") -----------------------
    led = GradLedger(n, p)
    led.upload(np.arange(n), g_src)
    step = make_aggregate_apply(rule, f, GAMMA)
    rx = jnp.asarray(received)
    # chain the iterate (the fused step donates x on accelerators —
    # reusing one buffer across calls would read a deleted array there)
    state = {"x": jnp.asarray(x0, jnp.float32)}

    def fused_iter():
        state["x"] = step(state["x"], led.data, rx, ETA)
        state["x"].block_until_ready()

    fused_s = _time(fused_iter, repeats)

    def upload_iter():
        led.upload(idx, g_src[idx])
        led.data.block_until_ready()

    upload_s = _time(upload_iter, repeats)

    return dict(rule=rule, f=f, n=n, P=p,
                host_us=round(host_s * 1e6, 1),
                fused_us=round(fused_s * 1e6, 1),
                upload_us=round(upload_s * 1e6, 1),
                speedup=round(host_s / fused_s, 2))


def run(sizes=SIZES, n_agents=N_AGENTS, repeats: int = 3,
        max_elems: int = 640_000_000, out: str | None = "BENCH_agg.json"):
    import jax

    rows = []
    memo = {}                # dedupe capped cells landing on the same P
    for label, nominal in sizes:
        for n in n_agents:
            p = min(nominal, max_elems // n)
            for rule, f in RULES:
                key = (rule, n, p)
                if key not in memo:
                    memo[key] = bench_cell(rule, f, n, p, repeats)
                cell = dict(memo[key])
                cell.update(model=label, P_nominal=nominal,
                            capped=p < nominal,
                            devices=jax.device_count(), mesh=None)
                rows.append(cell)
                print(f"agg/{rule}_n{n}_{label},{cell['fused_us']},"
                      f"host_us={cell['host_us']};x{cell['speedup']}",
                      flush=True)
    largest = max(rows, key=lambda r: r["n"] * r["P"])
    big = [r for r in rows
           if r["n"] * r["P"] == largest["n"] * largest["P"]]
    summary = {r["rule"]: r["speedup"] for r in big}
    result = {
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "devices": jax.device_count(),
            "repeats": repeats,
            "max_elems": max_elems,
            "note": "host = AsyncEngine f64 eager reference iteration; "
                    "fused = one jitted aggregate_apply over a resident "
                    "GradLedger; capped rows benchmark at P = "
                    "max_elems//n (nominal flat size recorded).",
        },
        "largest_shape_speedup": summary,
        "rows": rows,
    }
    if out:
        with open(out, "w") as fh:
            json.dump(result, fh, indent=1)
        print(f"agg/written,{out},min_largest_speedup="
              f"{min(summary.values()):.2f}", flush=True)
    return result


def bench_sharded_cell(rule: str, f: int, n: int, p: int, repeats: int,
                       mesh, combine: str = "partial") -> dict:
    """One dp-sharded server iteration (DESIGN.md §14): ShardedGradLedger
    rows live n/dp per shard, the fused rule runs shard-local and the
    iterate finishes with one masked psum (combine="partial"); the
    double-buffered upload scatters into the back buffer."""
    import jax
    import jax.numpy as jnp

    from repro.core.ledger import (ShardedGradLedger,
                                   make_sharded_aggregate_apply)
    from repro.launch.mesh import dp_axis_names

    axes = dp_axis_names(mesh)
    g_src = _stack(n, p)
    received = np.ones(n, bool)
    received[-1] = False
    idx = np.nonzero(received)[0]

    led = ShardedGradLedger(n, p, mesh=mesh, axes=axes)
    led.upload(np.arange(n), g_src)
    step = make_sharded_aggregate_apply(rule, f, GAMMA, mesh, axes, n,
                                        combine)
    rx = jnp.asarray(received)
    state = {"x": jnp.zeros(p, jnp.float32)}

    def fused_iter():
        state["x"] = step(state["x"], led.front_for_aggregate(), rx, ETA)
        state["x"].block_until_ready()

    fused_s = _time(fused_iter, repeats)

    def upload_iter():
        led.upload(idx, g_src[idx])
        led.data.block_until_ready()

    upload_s = _time(upload_iter, repeats)
    return dict(rule=rule, f=f, n=n, P=p, combine=combine, sharded=True,
                fused_us=round(fused_s * 1e6, 1),
                upload_us=round(upload_s * 1e6, 1),
                devices=jax.device_count(), mesh=dict(mesh.shape))


def run_sharded(total_elems: int | None = None, n: int | None = None,
                repeats: int = 2, out: str | None = "BENCH_agg.json",
                combine: str = "partial", smoke: bool = False):
    """Benchmark the dp-sharded ledger and *append* the rows to the
    committed BENCH_agg.json: the (n, P) stack lives sharded over every
    device, so a row can exceed the single-device ``max_elems`` cap the
    replicated sweep is capped at (n*P > 640M with 8 devices).
    trimmed_mean is omitted — it has no shard-local partial form and
    would rebuild the full stack per shard (see dist/registry.py)."""
    import jax
    from repro.launch.mesh import make_mesh

    d = jax.device_count()
    mesh = make_mesh((d,), ("data",))
    n = n or d
    if total_elems is None:
        total_elems = 2_000_000 if smoke else 768_000_000
    p = total_elems // n
    rules = (("mean", 0),) if smoke else \
        (("sum", 0), ("mean", 0), ("cge", 1), ("quantized", 0))
    rows = []
    for rule, f in rules:
        cell = bench_sharded_cell(rule, f, n, p, repeats, mesh, combine)
        rows.append(cell)
        print(f"agg/{rule}_n{n}_sharded{d}dev,{cell['fused_us']},"
              f"nP={n * p};combine={combine}", flush=True)
    if out:
        try:
            with open(out) as fh:
                data = json.load(fh)
        except FileNotFoundError:
            data = {"meta": {}, "rows": []}
        data["rows"] = [r for r in data["rows"]
                        if not r.get("sharded")] + rows
        data["meta"]["sharded_note"] = (
            "sharded rows: ShardedGradLedger over a "
            f"{dict(mesh.shape)} mesh, combine={combine} (shard-local "
            "fused rule + one masked psum); n*P exceeds the replicated "
            "sweep's max_elems cap. No host_us column — the host "
            "reference cannot hold the unsharded stack.")
        with open(out, "w") as fh:
            json.dump(data, fh, indent=1)
        print(f"agg/written,{out},sharded_rows={len(rows)}", flush=True)
    return rows


def main(smoke: bool = False, out: str | None = "BENCH_agg.json",
         record: bool = False, sharded: bool = False):
    if sharded:
        return run_sharded(out=None if smoke else out, smoke=smoke)
    if smoke:
        return run(sizes=(("smoke-64k", 65_536), ("smoke-1m", 1_048_576)),
                   n_agents=(8,), repeats=2,
                   out="BENCH_agg.smoke.json" if record else None)
    return run(out=out)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no JSON (CI stage 6)")
    ap.add_argument("--sharded", action="store_true",
                    help="dp-sharded ledger rows, appended to --out "
                         "(run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--out", default="BENCH_agg.json")
    args = ap.parse_args()
    main(smoke=args.smoke, out=args.out, sharded=args.sharded)
