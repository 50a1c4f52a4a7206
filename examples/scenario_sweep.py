"""Sweep a design space in one device program.

The whole point of a counterfactual platform (Bottou et al. 2013; Genie) is
answering *grids* of what-ifs — bid multipliers × reserves × budget scalings
— not one scenario per call. This example builds a synthetic day, forms a
3×2×2 design grid around the logged policy, and evaluates all 12 scenarios
with each estimator:

* batched device-resident Algorithm 2 (``method="parallel"``) — production;
* vmapped SORT2AGGREGATE warm-started from the base design's cap times;
* the batched sequential oracle, to show both estimators sit within the
  paper's tolerance scenario-by-scenario.

    PYTHONPATH=src python examples/scenario_sweep.py
"""
import time

import jax
import numpy as np

from repro.core import CounterfactualEngine
from repro.core.metrics import spend_weighted_relative_error
from repro.data import make_synthetic_env


def main(n_events: int = 32_768, n_campaigns: int = 32) -> None:
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=10)
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=[1.0, 0.85, 1.2],
                       reserves=[0.0, 0.05],
                       budget_scales=[1.0, 0.75])
    print(f"N={n_events} events, C={n_campaigns} campaigns, "
          f"S={grid.num_scenarios} scenarios\n")

    t0 = time.perf_counter()
    sweep = engine.sweep(grid, method="parallel")
    jax.block_until_ready(sweep.results.final_spend)
    t_par = time.perf_counter() - t0
    print(f"batched Algorithm 2: {grid.num_scenarios} scenarios in "
          f"{t_par:.2f}s (incl. compile)\n")
    print(sweep.format_delta_table())

    s2a = engine.sweep(grid, method="sort2aggregate")
    oracle = engine.sweep(grid, method="sequential")
    err_par = [float(spend_weighted_relative_error(
        sweep.results.final_spend[s], oracle.results.final_spend[s]))
        for s in range(grid.num_scenarios)]
    err_s2a = [float(spend_weighted_relative_error(
        s2a.results.final_spend[s], oracle.results.final_spend[s]))
        for s in range(grid.num_scenarios)]
    print(f"\nvs batched oracle — spend-weighted relative error: "
          f"Algorithm 2 max {max(err_par):.4f}, "
          f"SORT2AGGREGATE max {max(err_s2a):.4f}, "
          f"max consistency gap {float(np.max(np.asarray(s2a.consistency_gaps))):.0f} events")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
