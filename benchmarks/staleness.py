"""Paper §3.2 (Theorem 4): stale-gradient rule (15) — tau sweep.

Shows: final error is tau-independent (bound D doesn't contain tau) while
waiting time keeps dropping (stale deliveries count toward |T^t| >= n-r).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.async_engine import AsyncEngine, EngineConfig, default_latency
from repro.core.redundancy import make_redundant_quadratics, certify_r_eps

N, D, R = 12, 6, 3


def run(iters: int = 2000, taus=(0, 1, 2, 4, 8), seed: int = 0):
    costs = make_redundant_quadratics(N, D, spread=0.02, cond=1.5, seed=seed)
    mu = costs.mu()
    lat = default_latency(N, 3, 12.0, seed=seed)
    rows = []
    for tau in taus:
        t0 = time.time()
        eng = AsyncEngine(
            lambda j, x, rng: costs.grad(j, x), np.zeros(D),
            EngineConfig(n_agents=N, r=R, mode="stale", tau=tau,
                         rule="sum",
                         step_size=lambda t: 0.3 / (mu * N) / (1 + 3e-3 * t),
                         proj_gamma=50.0, seed=seed),
            latency=lat, x_star=costs.global_min())
        h = eng.run(iters)
        rows.append(dict(tau=tau, dist=h.dist[-1],
                         cum_comm=float(h.cum_comm[-1]),
                         mean_age=float(np.mean(h.staleness)),
                         wall_s=time.time() - t0))
    return rows


def main():
    rows = run()
    for r in rows:
        print(f"staleness/tau{r['tau']},{r['wall_s']*1e6/2000:.0f},"
              f"dist={r['dist']:.4f};cum_comm={r['cum_comm']:.0f};"
              f"mean_age={r['mean_age']:.2f}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
