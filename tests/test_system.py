"""End-to-end behaviour of the paper's system: counterfactual questions
answered by the production path agree with the oracle."""
import jax
import numpy as np

from repro.core import CounterfactualEngine, sequential_replay
from repro.core.metrics import spend_weighted_relative_error
from repro.data import make_synthetic_env, make_yahoo_like_env


def test_counterfactual_multiplier_change_end_to_end():
    env = make_synthetic_env(jax.random.PRNGKey(10), n_events=8192,
                             n_campaigns=24, emb_dim=8)
    eng = CounterfactualEngine(env.values, env.budgets, env.rule)
    alt = env.rule.with_multiplier(3, 1.5)
    ref = sequential_replay(env.values, env.budgets, alt)
    est = eng.simulate(rule=alt, method="sort2aggregate",
                       key=jax.random.PRNGKey(1), sample_rate=0.1,
                       vi_iters=120, vi_eta=0.8, vi_eta_decay=0.03,
                       vi_batch_size=64, refine_iters=20)
    err = spend_weighted_relative_error(est.final_spend, ref.final_spend)
    assert float(err) < 0.02, float(err)


def test_yahoo_like_day2_pipeline():
    env = make_yahoo_like_env(jax.random.PRNGKey(0), n_keywords=200,
                              n_campaigns=40, n_day1=4096, n_day2=6144,
                              budget=40.0, keywords_per_campaign=10)
    v1, v2 = env.values(1), env.values(2)
    day1 = sequential_replay(v1, env.budgets, env.rule)
    day2 = sequential_replay(v2, env.budgets, env.rule)
    from repro.core import sort2aggregate
    out = sort2aggregate(v2, env.budgets, env.rule,
                         cap_times_init=np.minimum(
                             np.asarray(day1.cap_times), 6144 + 1),
                         refine_iters=10)
    err_s2a = spend_weighted_relative_error(out.result.final_spend,
                                            day2.final_spend)
    from repro.data.yahoo import as_is_prediction, rescaled_prediction
    err_asis = spend_weighted_relative_error(
        as_is_prediction(day1.final_spend), day2.final_spend)
    assert float(err_s2a) < float(err_asis), (float(err_s2a),
                                              float(err_asis))
    assert float(err_s2a) < 0.05
