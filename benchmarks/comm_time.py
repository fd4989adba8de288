"""Paper Figures 2/3/4: loss/accuracy parity + cumulative communication
time vs r, n=20 agents, D-SGD on LeNet over MNIST-like data.

(The container ships no MNIST; the stand-in dataset is documented in
EXPERIMENTS.md. The figure's *claims* — comparable accuracy at equal
iterations, monotone comm-time reduction with diminishing returns beyond
the true straggler count — are asserted on this data.)
"""
from __future__ import annotations

import time

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_lenet import PAPER_EXPERIMENT
from repro.core.async_engine import AsyncEngine, EngineConfig, default_latency
from repro.data.partition import agent_batch, partition
from repro.data.synthetic import mnist_like
from repro.models.lenet import apply_lenet, init_lenet, param_count
from repro.models.model import classifier_loss


def make_agent_grad_fn(agent_sets, batch_size):
    params0 = init_lenet(jax.random.PRNGKey(0))
    flat0, unravel = jax.flatten_util.ravel_pytree(params0)

    @jax.jit
    def grad_flat(flat, x, y):
        def loss(fl):
            logits = apply_lenet(unravel(fl), x)
            return classifier_loss(logits, y, jnp.ones(y.shape[0]))
        return jax.grad(loss)(flat)

    rngs = [np.random.default_rng(100 + j) for j in range(len(agent_sets))]

    def grad_fn(j, x_vec, rng):
        xb, yb = agent_batch(agent_sets[j], batch_size, rngs[j])
        return np.asarray(grad_flat(jnp.asarray(x_vec, jnp.float32),
                                    jnp.asarray(xb), jnp.asarray(yb)))

    return grad_fn, flat0, unravel


def accuracy(flat, unravel, ds, limit=512):
    logits = apply_lenet(unravel(jnp.asarray(flat, jnp.float32)),
                         jnp.asarray(ds.x[:limit]))
    return float((jnp.argmax(logits, -1) == jnp.asarray(
        ds.y[:limit])).mean())


def run(iters: int = 120, r_values=(0, 1, 3, 5, 10, 15), n: int = 20,
        batch: int = 32, n_train: int = 4000, seed: int = 0):
    train, test = mnist_like(n_train=n_train, n_test=1024, seed=seed)
    agent_sets = partition(train, n, overlap=2, seed=seed)
    grad_fn, flat0, unravel = make_agent_grad_fn(agent_sets, batch)
    assert param_count(init_lenet(jax.random.PRNGKey(0))) == 431_080
    lat = default_latency(n, n_stragglers=3, factor=10.0, seed=seed)

    rows = []
    for r in r_values:
        t0 = time.time()
        eng = AsyncEngine(
            grad_fn, np.asarray(flat0),
            EngineConfig(n_agents=n, r=r, rule="mean",
                         step_size=lambda t: 0.05, proj_gamma=1e6,
                         seed=seed),
            latency=lat)
        h = eng.run(iters)
        acc = accuracy(eng.x, unravel, test)
        rows.append(dict(r=r, acc=acc, cum_comm=float(h.cum_comm[-1]),
                         bytes_tx=h.bytes_tx,
                         wall_s=time.time() - t0))
    return rows


def main():
    rows = run()
    base = rows[0]
    for row in rows:
        print(f"comm_time/lenet_r{row['r']},"
              f"{row['wall_s']*1e6/120:.0f},"
              f"acc={row['acc']:.3f};cum_comm={row['cum_comm']:.1f};"
              f"speedup={base['cum_comm']/row['cum_comm']:.2f}x")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
