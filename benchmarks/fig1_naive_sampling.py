"""Fig. 1: naive subsample+rescale sequential replay degrades as the sampling
rate drops — the motivation for the paper's machinery.

Setup mirrors §7.1 at CPU-scale: synthetic env, error on campaign |C|,
7 repetitions per rate.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit, time_call
from repro.core import naive_sampled_replay, sequential_replay
from repro.core.metrics import relative_error
from repro.data import make_synthetic_env

N_EVENTS = 65_536
N_CAMPAIGNS = 64
REPEATS = 7


def main(n_events: int = N_EVENTS) -> None:
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=N_CAMPAIGNS, emb_dim=10)
    ref = sequential_replay(env.values, env.budgets, env.rule)
    for rate in (0.5, 0.2, 0.1, 0.05, 0.02):
        errs = []
        us = 0.0
        for rep in range(REPEATS):
            res, dt = time_call(
                lambda k: naive_sampled_replay(
                    env.values, env.budgets, env.rule, k,
                    sample_size=int(n_events * rate)),
                jax.random.fold_in(jax.random.PRNGKey(1), rep),
                repeats=1, warmup=0)
            us = dt
            errs.append(float(relative_error(res.final_spend,
                                             ref.final_spend)))
        emit(f"fig1_naive_rate_{rate}", us,
             f"err_mean={np.mean(errs):.4f};err_max={np.max(errs):.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
