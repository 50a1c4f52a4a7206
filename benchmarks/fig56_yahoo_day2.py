"""Figs. 5-6: Yahoo-like day-1 -> day-2 counterfactual (volume 100k -> 150k,
fixed budgets). SORT2AGGREGATE warm-started from day-1 cap times vs the
"as is" and "rescale by volume" heuristics; metric = spend-weighted relative
error (Fig. 6's cumulative curve summarized at its mean).

The real Yahoo dataset is request-gated; data/yahoo.py generates the same
published structure (see DESIGN.md §data gates).
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit, time_call
from repro.core import sequential_replay, sort2aggregate
from repro.core.metrics import spend_weighted_relative_error
from repro.data import make_yahoo_like_env
from repro.data.yahoo import as_is_prediction, rescaled_prediction


def main(n_day1: int = 32_768, n_day2: int = 49_152,
         n_campaigns: int = 100) -> None:
    env = make_yahoo_like_env(jax.random.PRNGKey(0), n_keywords=1000,
                              n_campaigns=n_campaigns, n_day1=n_day1,
                              n_day2=n_day2, budget=120.0)
    v1, v2 = env.values(1), env.values(2)
    day1 = sequential_replay(v1, env.budgets, env.rule)
    day2 = sequential_replay(v2, env.budgets, env.rule)

    err_asis = float(spend_weighted_relative_error(
        as_is_prediction(day1.final_spend), day2.final_spend))
    err_scale = float(spend_weighted_relative_error(
        rescaled_prediction(day1.final_spend, n_day1, n_day2, env.budgets),
        day2.final_spend))
    # warm start: day-1 cap times rescaled to day-2 volume (Fig. 5 setup)
    caps1 = np.asarray(day1.cap_times, np.int64)
    warm = np.where(caps1 <= n_day1,
                    np.minimum((caps1 * n_day2) // n_day1, n_day2),
                    n_day2 + 1).astype(np.int32)
    out, us = time_call(
        lambda: sort2aggregate(v2, env.budgets, env.rule,
                               cap_times_init=warm, refine_iters=12),
        repeats=1)
    err_s2a = float(spend_weighted_relative_error(out.result.final_spend,
                                                  day2.final_spend))
    capped = int((np.asarray(day2.cap_times) <= n_day2).sum())
    emit("fig6_heuristic_as_is", 0.0, f"werr={err_asis:.4f}")
    emit("fig6_heuristic_rescale", 0.0, f"werr={err_scale:.4f}")
    emit("fig56_sort2aggregate_warm", us,
         f"werr={err_s2a:.4f};capped={capped}/{n_campaigns};"
         f"refine_iters={out.refine_iters_used}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
