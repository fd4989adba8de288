"""Paper §4 (Theorems 5/6): Byzantine + straggler tolerance grid —
attacks x aggregation rules, final distance to the honest optimum."""
from __future__ import annotations

import time

import numpy as np

from repro.core.async_engine import AsyncEngine, EngineConfig, default_latency
from repro.core.redundancy import make_redundant_quadratics

N, D, R, F = 12, 6, 2, 2
ATTACKS = ("large_norm", "sign_flip", "random_gaussian", "little_is_enough")
RULES = ("sum", "cge", "trimmed_mean")


def run(iters: int = 1500, seed: int = 0):
    costs = make_redundant_quadratics(N, D, spread=0.02, cond=1.5, seed=seed)
    mu = costs.mu()
    lat = default_latency(N, 2, 8.0, seed=seed)
    byz = (0, 5)
    rows = []
    for attack in ATTACKS:
        for rule in RULES:
            t0 = time.time()
            eng = AsyncEngine(
                lambda j, x, rng: costs.grad(j, x), np.zeros(D),
                EngineConfig(n_agents=N, r=R, f=F, rule=rule,
                             byz_ids=byz, attack=attack,
                             step_size=lambda t: 0.3 / (mu * N)
                             / (1 + 3e-3 * t),
                             proj_gamma=50.0, seed=seed),
                latency=lat, x_star=costs.global_min())
            h = eng.run(iters)
            rows.append(dict(attack=attack, rule=rule, dist=h.dist[-1],
                             wall_s=time.time() - t0))
    return rows


def main():
    rows = run()
    for r in rows:
        print(f"byzantine/{r['attack']}/{r['rule']},"
              f"{r['wall_s']*1e6/1500:.0f},dist={r['dist']:.4f}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
