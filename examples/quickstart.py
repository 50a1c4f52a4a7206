"""Quickstart: simulate a counterfactual platform change four ways.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import numpy as np

from repro.core import CounterfactualEngine, sequential_replay
from repro.core.metrics import spend_weighted_relative_error
from repro.data import make_synthetic_env


def main():
    print("== burnout-variable counterfactual quickstart ==")
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=32_768,
                             n_campaigns=48, emb_dim=10)
    print(f"events={env.n_events} campaigns={env.n_campaigns} "
          f"(budgets ramp, ~50% cap out)")

    engine = CounterfactualEngine(env.values, env.budgets, env.rule)
    # the counterfactual: raise campaign 7's bid multiplier by 30%
    alt_rule = env.rule.with_multiplier(7, 1.3)
    truth = sequential_replay(env.values, env.budgets, alt_rule)

    for method, kwargs in [
        ("sequential", {}),
        ("parallel", {}),
        ("sort2aggregate", dict(sample_rate=0.03, vi_iters=80, vi_eta=0.8,
                                vi_eta_decay=0.03, vi_batch_size=64,
                                refine_iters=10)),
        ("naive_sampling", dict(sample_size=2048)),
    ]:
        t0 = time.time()
        res = engine.simulate(rule=alt_rule, method=method,
                              key=jax.random.PRNGKey(1), **kwargs)
        jax.block_until_ready(res.final_spend)
        err = float(spend_weighted_relative_error(res.final_spend,
                                                  truth.final_spend))
        capped = int((np.asarray(res.cap_times) <= env.n_events).sum())
        print(f"{method:16s} {time.time() - t0:6.2f}s  werr={err:.5f}  "
              f"capped={capped}")
    print("note: sort2aggregate matches the oracle at a cost that "
          "parallelizes over the event log; naive sampling does not.")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
