"""Multi-device (8 virtual CPU devices) integration checks, run as a
subprocess from tests/test_collectives_multidev.py so the main pytest
process keeps its single-device view.

Exits 0 iff all checks pass; prints one line per check.
"""
import os

# virtual CPU devices: pinned here, not inherited, so a run on a machine
# with an accelerator never reaches for it
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np            # noqa: E402
import jax                    # noqa: E402
import jax.numpy as jnp       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import gradagg                 # noqa: E402
from repro.dist import collectives as C        # noqa: E402
from repro.launch.mesh import make_test_mesh   # noqa: E402


def check(name, ok):
    print(("PASS " if ok else "FAIL ") + name, flush=True)
    if not ok:
        raise SystemExit(1)


def main():
    mesh = make_test_mesh(data=4, model=2)
    n = 4
    dim = 16
    rng = np.random.default_rng(0)
    g_all = jnp.asarray(rng.normal(size=(n, dim)), jnp.float32)
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0])

    # --- masked_psum == reference agg_sum -----------------------------
    def f(gl, m):
        me = C.agent_index(("data",))
        return C.masked_psum({"g": gl[0]}, m[me], ("data",))["g"]

    with jax.set_mesh(mesh):
        out = jax.jit(jax.shard_map(
            f, in_specs=(P("data"), P()), out_specs=P(),
            axis_names={"data"}, check_vma=False))(g_all, mask)
    ref = gradagg.agg_sum(g_all, mask > 0)
    check("masked_psum", np.allclose(out, ref, atol=1e-5))

    # --- cge_psum == reference agg_cge --------------------------------
    f_byz = 1

    def fc(gl, m):
        me = C.agent_index(("data",))
        agg, keep = C.cge_psum({"g": gl[0]}, m[me] > 0, f_byz, ("data",))
        return agg["g"], keep

    with jax.set_mesh(mesh):
        out, keep = jax.jit(jax.shard_map(
            fc, in_specs=(P("data"), P()), out_specs=(P(), P()),
            axis_names={"data"}, check_vma=False))(g_all, mask)
    ref = gradagg.agg_cge(g_all, mask > 0, f_byz)
    refk = gradagg.cge_mask(g_all, mask > 0, f_byz)
    check("cge_psum_agg", np.allclose(out, ref, atol=1e-5))
    check("cge_psum_keep", np.array_equal(np.asarray(keep),
                                          np.asarray(refk)))

    # --- quantized_psum: small error + error feedback -----------------
    def fq(gl, m, e):
        me = C.agent_index(("data",))
        agg, err = C.quantized_psum({"g": gl[0]}, m[me],
                                    {"g": e[0]}, ("data",))
        return agg["g"], err["g"][None]

    err0 = jnp.zeros((n, dim))
    with jax.set_mesh(mesh):
        out, err = jax.jit(jax.shard_map(
            fq, in_specs=(P("data"), P(), P("data")),
            out_specs=(P(), P("data")),
            axis_names={"data"}, check_vma=False))(g_all, mask, err0)
    exact = gradagg.agg_sum(g_all, mask > 0)
    rel = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
    check(f"quantized_psum rel_err={rel:.4f}", rel < 0.02)
    # residuals recorded for masked-in agents
    check("quantized_err_feedback",
          float(jnp.abs(err).sum()) > 0)

    # --- general train step (cge + stale) on a reduced arch -----------
    from repro.configs.registry import get_config
    from repro.launch.train import (TrainConfig, init_state,
                                    make_general_step, make_train_step)
    cfg = get_config("qwen2-0.5b").reduced()
    for mode in ("cge", "stale", "quantized"):
        tc = TrainConfig(mode=mode, remat_policy="none", f=1, tau=2)
        state = init_state(jax.random.PRNGKey(0), cfg, tc, max_pos=64,
                           n_agents=4)
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 8), 0,
                                 cfg.vocab_size)
        batch = {"tokens": tok, "targets": tok,
                 "weights": jnp.ones(tok.shape, jnp.float32)}
        fresh = jnp.asarray([1.0, 1.0, 0.0, 1.0])
        step = make_general_step(cfg, tc, mesh)
        with jax.set_mesh(mesh):
            new_state, metrics = jax.jit(step)(state, batch, fresh)
        ok = bool(jnp.isfinite(metrics["loss"])) and \
            int(new_state["step"]) == 1
        check(f"general_step[{mode}] loss={float(metrics['loss']):.3f}", ok)

    # --- masked fast path under pjit on the mesh ----------------------
    from repro.dist.sharding import MeshRules, tree_specs, batch_specs
    tc = TrainConfig(remat_policy="none")
    rules = MeshRules(axis_sizes={"data": 4, "model": 2})
    state = init_state(jax.random.PRNGKey(0), cfg, tc, max_pos=64)
    st_specs = tree_specs(state, rules)
    tok = jax.random.randint(jax.random.PRNGKey(1), (8, 8), 0,
                             cfg.vocab_size)
    batch = {"tokens": tok, "targets": tok,
             "weights": jnp.ones(tok.shape, jnp.float32)}
    bt_specs = batch_specs(rules, batch)
    cspecs = tree_specs(state["params"],
                        MeshRules(fsdp_axes=(),
                                  axis_sizes={"data": 4, "model": 2}))
    step = make_train_step(cfg, tc, dp="data", tp="model",
                           param_specs=cspecs,
                           sizes={"data": 4, "model": 2})
    mk = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    with jax.set_mesh(mesh):
        jf = jax.jit(step, in_shardings=(mk(st_specs), mk(bt_specs)))
        new_state, metrics = jf(state, batch)
    check(f"masked_pjit loss={float(metrics['loss']):.3f}",
          bool(jnp.isfinite(metrics["loss"])))

    # --- masked == subset-gradient equivalence under pjit --------------
    w0 = jnp.ones(tok.shape, jnp.float32).at[:4].set(0.0)
    batch0 = dict(batch, weights=w0)
    with jax.set_mesh(mesh):
        s1, m1 = jf(state, batch0)
    # reference: unsharded masked step
    step_ref = make_train_step(cfg, tc)
    s2, m2 = jax.jit(step_ref)(state, batch0)
    d = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        s1["params"], s2["params"])))
    check(f"masked_pjit_vs_single max_param_diff={d:.2e}", d < 5e-4)

    print("ALL OK", flush=True)


if __name__ == "__main__":
    main()
