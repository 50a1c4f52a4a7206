"""Search the scenario space instead of sweeping it exhaustively.

The production question is rarely "evaluate these 12 designs" — it is "what
reserve maximizes revenue, subject to not burning out more than 10% of the
campaigns?". This example runs both scenario-space optimizers
(:mod:`repro.search`) over a synthetic day with the batched Algorithm-2
sweep as the inner loop:

* successive halving over a shrinking reserve × budget box;
* coordinate hill-climb from the logged base design;

then evaluates the exhaustive grid at the resolution the search reached, to
show the optimizers land on the same design for a fraction of the scenario
evaluations — every one of which is accounted by the evaluation ledger.

    PYTHONPATH=src python examples/scenario_search.py
"""
import time

import jax
import numpy as np

from repro.core import CounterfactualEngine
from repro.data import make_synthetic_env
from repro.search import CapRateCeiling, SearchSpace


def main(n_events: int = 16_384, n_campaigns: int = 16,
         budget: int = 96) -> None:
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=10)
    engine = CounterfactualEngine(env.values, env.budgets)
    space = SearchSpace(reserve=(0.0, 0.4), budget_scale=(0.5, 2.0))
    cap_ceiling = CapRateCeiling(0.5)
    print(f"N={n_events} events, C={n_campaigns} campaigns; maximizing "
          f"revenue over reserve×budget_scale {space.bounds()} s.t. "
          f"cap-out rate <= {cap_ceiling.ceiling:.0%}, "
          f"budget {budget} evaluations\n")

    results = {}
    for method in ("halving", "hillclimb"):
        t0 = time.perf_counter()
        res = engine.search(space, method=method, budget=budget,
                            constraints=(cap_ceiling,))
        results[method] = res
        print(f"--- {method} ({time.perf_counter() - t0:.2f}s) ---")
        print(res.format_trajectory())
        assert res.ledger.spent == sum(n for _, n in res.ledger.entries) \
            == sum(h["evaluations"] for h in res.history), "ledger drift"
        print()

    # the exhaustive alternative at a comparable resolution (9×9 grid)
    k = 9
    grid = engine.grid(
        reserves=list(np.linspace(0.0, 0.4, k)),
        budget_scales=list(np.linspace(0.5, 2.0, k)))
    swept = engine.sweep(grid)
    rev = np.asarray(swept.results.revenue)
    caps = np.asarray(swept.results.cap_times) <= n_events
    feasible = caps.mean(-1) <= cap_ceiling.ceiling
    rev_feas = np.where(feasible, rev, -np.inf)
    s_best = int(rev_feas.argmax())
    print(f"exhaustive {k}x{k} grid: {grid.num_scenarios} evaluations -> "
          f"{grid.labels[s_best]} = {rev[s_best]:.2f}")
    for method, res in results.items():
        gap = (rev[s_best] - res.best_value) / rev[s_best]
        print(f"{method:>10}: {res.evaluations} evaluations "
              f"({res.evaluations / grid.num_scenarios:.0%} of the grid), "
              f"revenue within {gap:+.2%} of the grid optimum")
        assert res.evaluations < grid.num_scenarios, \
            "search spent more than the exhaustive grid"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
