"""Definition 1's trade-off, measured: (r, eps)-redundancy of shared-data
linear-regression costs as a function of the data-replication overlap, and
the resulting Algorithm-1 error vs r — the redundancy <-> accuracy lever
the paper's abstract describes."""
from __future__ import annotations

import time

import numpy as np

from repro.core.async_engine import AsyncEngine, EngineConfig, default_latency
from repro.core.redundancy import (certify_r_eps, make_shared_data_costs,
                                   theoretical_bound)

N, D = 10, 6


def run(seed: int = 0):
    rows = []
    for overlap in (1, 2, 4):
        costs = make_shared_data_costs(N, D, n_data=400, overlap=overlap,
                                       noise=0.05, seed=seed)
        for r in (1, 2, 3):
            t0 = time.time()
            eps = certify_r_eps(costs, r, samples=800)
            alpha, bound, gam = theoretical_bound(costs, r, eps, samples=100)
            mu = costs.mu()
            eng = AsyncEngine(
                lambda j, x, rng: costs.grad(j, x), np.zeros(D),
                EngineConfig(n_agents=N, r=r, rule="sum",
                             step_size=lambda t: 0.3 / (mu * N)
                             / (1 + 3e-3 * t),
                             proj_gamma=50.0, seed=seed),
                latency=default_latency(N, 2, 8.0, seed=seed),
                x_star=costs.global_min())
            h = eng.run(1200)
            rows.append(dict(overlap=overlap, r=r, eps=eps,
                             bound=bound, dist=h.dist[-1],
                             wall_s=time.time() - t0))
    return rows


def main():
    rows = run()
    for r in rows:
        b = "inf" if not np.isfinite(r["bound"]) else f"{r['bound']:.3f}"
        print(f"redundancy/ov{r['overlap']}_r{r['r']},"
              f"{r['wall_s']*1e6:.0f},"
              f"eps={r['eps']:.4f};D={b};dist={r['dist']:.4f}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
