"""Reference/SPMD parity sweeps on an 8-virtual-device host.

Three suites (``--suite``; run as subprocesses — the device count must
be set before jax initializes):

- ``registry`` (default): for every registered rule, randomized (n, d)
  gradient stacks and ``received`` masks with |S^t| = n - r must agree
  between the ``repro.core.gradagg`` reference and the
  ``repro.dist.collectives`` twin within 1e-5, on both a single dp axis
  ("data") and the composite ("pod", "data") agent indexing.
- ``sharded-ledger`` (DESIGN.md §14): the dp-sharded double-buffered
  ``ShardedGradLedger`` + ``make_sharded_aggregate_apply`` iterate must
  be *bit-identical* to the PR 4 single-buffer device path
  (``GradLedger`` + ``make_aggregate_apply``) for all five rules with
  ``combine="gather"``, and within 1e-5 with ``combine="partial"``;
  the ledger host image must match the reference mid-swap every round,
  and a snapshot -> restore mid-swap must round-trip exactly.
- ``serve-tp`` (DESIGN.md §14): the TP-meshed serving engine (KV pools
  sharded over the kv-head dim, the grouped decode kernel per shard)
  must be *token-identical* to the replicated engine on a mixed-length
  continuous-batching workload, for a GQA arch and an MLA arch, on both
  the superstep path and the superstep_k=1 conformance loop.
"""
import os

# virtual CPU devices: pinned here, not inherited, so a run on a machine
# with an accelerator never reaches for it
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np            # noqa: E402
import jax                    # noqa: E402
import jax.numpy as jnp       # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.dist import collectives as C          # noqa: E402
from repro.dist.registry import get_rule, rule_names  # noqa: E402
from repro.launch.mesh import make_test_mesh     # noqa: E402

ATOL = 1e-5


def check(name, ok):
    print(("PASS " if ok else "FAIL ") + name, flush=True)
    if not ok:
        raise SystemExit(1)


def spmd_apply(mesh, dp_axes, rule, g_all, mask, f):
    """Run the rule's uniform SPMD wrapper, one agent per dp coordinate."""
    dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    def body(gl, m):
        me = C.agent_index(dp_axes)
        return rule.spmd({"g": gl[0]}, m[me], f, dp_axes)["g"]

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                           in_specs=(P(dp_spec), P()), out_specs=P(),
                           axis_names=set(dp_axes), check_vma=False))
    return np.asarray(fn(g_all, mask))


def main_sharded_ledger():
    """dp-sharded double-buffered ledger vs the single-buffer device
    path: bit-identical with combine="gather", 1e-5 with "partial"."""
    from repro.core.ledger import (GradLedger, ShardedGradLedger,
                                   make_aggregate_apply,
                                   make_sharded_aggregate_apply)
    from repro.launch.mesh import dp_axis_names

    rng = np.random.default_rng(0)
    n, d = 8, 1000
    meshes = [make_test_mesh(data=8, model=1),
              make_test_mesh(pod=2, data=2, model=2)]
    for mesh in meshes:
        axes = dp_axis_names(mesh)
        tag = "x".join(map(str, dict(mesh.shape).values()))
        for rule in rule_names():
            f = 1 if get_rule(rule).needs_f else 0
            ref = GradLedger(n, d)
            step_r = make_aggregate_apply(rule, f, 1e6)
            x_r = jnp.zeros(d, jnp.float32)
            combines = ("gather", "partial")
            leds = {c: ShardedGradLedger(n, d, mesh=mesh, axes=axes)
                    for c in combines}
            steps = {c: make_sharded_aggregate_apply(
                rule, f, 1e6, mesh, axes, n, c) for c in combines}
            xs = {c: jnp.zeros(d, jnp.float32) for c in combines}
            for it in range(4):
                ups = rng.choice(n, size=rng.integers(1, n + 1),
                                 replace=False)
                rows = rng.normal(size=(ups.size, d)).astype(np.float32)
                ref.upload(ups, rows)
                for c in combines:
                    leds[c].upload(ups, rows)
                recv = np.zeros(n, bool)
                recv[rng.choice(n, size=6, replace=False)] = True
                x_r = step_r(x_r, ref.front_for_aggregate(),
                             jnp.asarray(recv), 0.01)
                for c in combines:
                    xs[c] = steps[c](xs[c], leds[c].front_for_aggregate(),
                                     jnp.asarray(recv), 0.01)
                # ledger host image must be exact mid-swap, every round
                check(f"ledger[{tag}][{rule}] it{it} host image exact",
                      np.array_equal(leds["gather"].host(), ref.host()))
            exact = np.array_equal(np.asarray(xs["gather"]),
                                   np.asarray(x_r))
            err = float(np.max(np.abs(np.asarray(xs["partial"])
                                      - np.asarray(x_r))))
            check(f"ledger[{tag}][{rule}] gather bit-identical", exact)
            check(f"ledger[{tag}][{rule}] partial err={err:.2e}",
                  err <= ATOL * max(float(np.max(np.abs(x_r))), 1.0))

        # engine-level: agg_backend="sharded" (gather) must track the
        # single-device "device" backend bit for bit over a real run
        from repro.core.async_engine import AsyncEngine, EngineConfig
        from repro.core.redundancy import make_redundant_quadratics

        costs = make_redundant_quadratics(n, 12, spread=0.02, cond=1.5,
                                          seed=0)
        xs_eng = {}
        for backend in ("device", "sharded"):
            eng = AsyncEngine(
                lambda j, x, r: costs.grad(j, x), np.zeros(12),
                EngineConfig(n_agents=n, r=2, rule="cge", f=1,
                             step_size=lambda t: 0.02, proj_gamma=30.0,
                             seed=1, agg_backend=backend),
                loss_fn=costs.loss,
                mesh=mesh if backend == "sharded" else None)
            eng.run(30)
            xs_eng[backend] = eng.x.copy()
        check(f"ledger[{tag}] engine sharded==device bit-identical",
              np.array_equal(xs_eng["device"], xs_eng["sharded"]))

        # donation safety: the two double-buffer slots must be backed by
        # independent device buffers after both __init__ and load() —
        # _scatter_rows donates its destination on accelerator backends,
        # so aliased slots would have the first upload invalidate the
        # other buffer (use-after-donation on the next pending replay)
        def no_alias(ledger):
            pts = [{s.data.unsafe_buffer_pointer()
                    for s in buf.addressable_shards}
                   for buf in ledger._bufs]
            return not (pts[0] & pts[1])

        # snapshot -> restore with an upload pending in the back buffer
        led = ShardedGradLedger(n, d, mesh=mesh, axes=axes)
        check(f"ledger[{tag}] init buffers unaliased", no_alias(led))
        led.upload([0, 3], rng.normal(size=(2, d)).astype(np.float32))
        _ = led.front_for_aggregate()                       # swap once
        led.upload([5], rng.normal(size=(1, d)).astype(np.float32))
        snap = led.host()
        led2 = ShardedGradLedger(n, d, mesh=mesh, axes=axes)
        led2.load(snap)
        check(f"ledger[{tag}] load buffers unaliased", no_alias(led2))
        check(f"ledger[{tag}] restore mid-swap exact",
              np.array_equal(led2.host(), snap))
        _ = led2.front_for_aggregate()
        check(f"ledger[{tag}] swap preserves restored state",
              np.array_equal(led2.host(), snap))
    print("ALL OK", flush=True)


def main_serve_tp():
    """TP-meshed ServeEngine vs the replicated engine: token-identical
    streams on GQA and MLA reduced archs, superstep and k=1 paths."""
    from repro.configs.registry import get_config
    from repro.models.model import init_model
    from repro.serve import PagedCacheConfig, ServeEngine

    prompt_lens, budgets = (5, 9, 3, 6), (4, 7, 2, 5)

    def run(params, cfg, k, mesh=None):
        ccfg = PagedCacheConfig(num_slots=2, page_size=4, num_pages=24,
                                max_pages_per_seq=8)
        eng = ServeEngine(params, cfg, ccfg, superstep_k=k, mesh=mesh)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab_size,
                                size=(ln,)).astype(np.int32)
                   for ln in prompt_lens]
        rids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        out = eng.run()
        return [out[r] for r in rids]

    for arch in ("qwen2-0.5b", "deepseek-v2-236b"):
        cfg = get_config(arch).reduced()
        params = init_model(jax.random.PRNGKey(0), cfg, max_pos=64)
        ref = run(params, cfg, 4)
        mesh = make_test_mesh(data=4, model=2)
        got = run(params, cfg, 4, mesh=mesh)
        check(f"serve-tp[{arch}] superstep token-identical",
              all(np.array_equal(a, b) for a, b in zip(ref, got)))
        got1 = run(params, cfg, 1, mesh=mesh)
        check(f"serve-tp[{arch}] k=1 token-identical",
              all(np.array_equal(a, b) for a, b in zip(ref, got1)))
    print("ALL OK", flush=True)


def main():
    meshes = [
        (make_test_mesh(data=8, model=1), ("data",), 8),
        (make_test_mesh(pod=2, data=2, model=2), ("pod", "data"), 4),
    ]
    rng = np.random.default_rng(0)
    for mesh, dp_axes, n in meshes:
        for name in rule_names():
            rule = get_rule(name)
            for trial, d in enumerate((16, 33, 128)):
                g = jnp.asarray(rng.normal(size=(n, d)) *
                                rng.lognormal(0.0, 1.0, size=(n, 1)),
                                jnp.float32)
                # masked received set with |S^t| = n - r (also r = 0)
                r = trial % max(n // 2, 1)
                drop = rng.choice(n, size=r, replace=False)
                mask = np.ones(n, np.float32)
                mask[drop] = 0.0
                mask = jnp.asarray(mask)
                m = n - r
                f = 1 if (rule.needs_f and m - 2 >= 1) else 0
                if rule.needs_f and m - 2 * f < 1:
                    f = 0
                ref = np.asarray(rule.bind_reference(f)(g, mask > 0))
                out = spmd_apply(mesh, dp_axes, rule, g, mask, f)
                err = float(np.max(np.abs(out - ref)))
                scale = max(float(np.max(np.abs(ref))), 1.0)
                check(f"parity[{'x'.join(map(str, dict(mesh.shape).values()))}]"
                      f"[{name}] n={n} d={d} r={r} f={f} "
                      f"err={err:.2e}", err <= ATOL * scale)
    print("ALL OK", flush=True)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="registry",
                    choices=("registry", "sharded-ledger", "serve-tp"))
    args = ap.parse_args()
    {"registry": main,
     "sharded-ledger": main_sharded_ledger,
     "serve-tp": main_serve_tp}[args.suite]()
