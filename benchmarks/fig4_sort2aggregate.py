"""Fig. 4: SORT2AGGREGATE vs ground truth — scalable AND accurate (contrast
with fig1's naive sampling at matched cost)."""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit, time_call
from repro.core import sequential_replay, sort2aggregate
from repro.core.metrics import spend_weighted_relative_error
from repro.data import make_synthetic_env


def main(n_events: int = 65_536, n_campaigns: int = 64) -> None:
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=10)
    ref = sequential_replay(env.values, env.budgets, env.rule)
    out, us = time_call(
        lambda: sort2aggregate(
            env.values, env.budgets, env.rule, jax.random.PRNGKey(4),
            sample_rate=0.03, vi_iters=120, vi_eta=0.8, vi_eta_decay=0.03,
            vi_batch_size=64, refine_iters=12),
        repeats=1)
    err = float(spend_weighted_relative_error(out.result.final_spend,
                                              ref.final_spend))
    cap_match = float((np.asarray(out.result.cap_times)
                       == np.asarray(ref.cap_times)).mean())
    emit("fig4_sort2aggregate", us,
         f"werr={err:.5f};cap_exact={cap_match:.2f};"
         f"refine_iters={out.refine_iters_used};gap={out.consistency_gap}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
