"""Fig. 2: sequential vs parallel (Algorithm 2) simulation outputs are
extremely close; also reports the serial-depth reduction (rounds vs events).
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit, time_call
from repro.core import parallel_simulate, sequential_replay
from repro.core.metrics import spend_weighted_relative_error
from repro.data import make_synthetic_env


def main(n_events: int = 65_536, n_campaigns: int = 64) -> None:
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=10)
    ref, us_seq = time_call(
        lambda: sequential_replay(env.values, env.budgets, env.rule),
        repeats=1)
    (par, trace), us_par = time_call(
        lambda: parallel_simulate(env.values, env.budgets, env.rule,
                                  return_trace=True), repeats=1, warmup=0)
    err = float(spend_weighted_relative_error(par.final_spend,
                                              ref.final_spend))
    max_err = float(np.max(
        np.abs(np.asarray(par.final_spend) - np.asarray(ref.final_spend))
        / np.maximum(np.asarray(ref.final_spend), 1e-9)))
    emit("fig2_sequential", us_seq, f"N={n_events}")
    emit("fig2_parallel", us_par,
         f"werr={err:.5f};max_rel={max_err:.4f};rounds={trace.num_rounds};"
         f"serial_depth_reduction={n_events / max(trace.num_rounds, 1):.0f}x")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
