"""The benchmark's plain reference against the program's own sequential
oracle (``core/sequential.py``) on small inputs, and the benchmark's copied
yardsticks against the originals."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.gen import synthetic, yahoo
from repro.core.metrics import spend_weighted_relative_error
from repro.core.sweep import sweep_sequential
from repro.core.types import AuctionRule


def _case(seed, n_events, n_campaigns, n_lanes):
    rng = np.random.default_rng(seed)
    values = rng.uniform(0.0, 1.0, (n_events, n_campaigns)).astype(
        np.float32)
    budgets = rng.uniform(5.0, 40.0, (n_lanes, n_campaigns)).astype(
        np.float32)
    mult = rng.uniform(0.5, 2.0, (n_lanes, n_campaigns)).astype(np.float32)
    reserve = rng.uniform(0.0, 0.3, n_lanes).astype(np.float32)
    return values, budgets, mult, reserve


@pytest.mark.parametrize("seed,n_events,n_campaigns,n_lanes", [
    (0, 512, 8, 3), (1, 1024, 16, 2), (2, 768, 5, 4)])
def test_reference_is_the_sequential_oracle(seed, n_events, n_campaigns,
                                            n_lanes):
    values, budgets, mult, reserve = _case(seed, n_events, n_campaigns,
                                           n_lanes)
    rules = AuctionRule(multipliers=jnp.asarray(mult),
                        reserve=jnp.asarray(reserve), kind="first_price")
    oracle = sweep_sequential(jnp.asarray(values), jnp.asarray(budgets),
                              rules)
    segment = n_events // 4
    spend, cap = reference.replay(values, budgets, mult, reserve,
                                  segment=segment)
    np.testing.assert_array_equal(spend[-1], np.asarray(oracle.final_spend))
    caps = np.where(cap[-1] < 0, n_events + 1, cap[-1])
    np.testing.assert_array_equal(caps, np.asarray(oracle.cap_times))
    # every snapshot is the replay of its prefix
    for k in range(4):
        n = (k + 1) * segment
        prefix = sweep_sequential(jnp.asarray(values[:n]),
                                  jnp.asarray(budgets), rules)
        np.testing.assert_array_equal(spend[k],
                                      np.asarray(prefix.final_spend))


def test_bf16_control_rounds_only_the_data_path():
    values, budgets, mult, reserve = _case(3, 512, 8, 2)
    low, _ = reference.replay(values, budgets, mult, reserve,
                              dtype="bfloat16")
    rounded = np.asarray(jnp.asarray(values, jnp.bfloat16)
                         .astype(jnp.float32))
    assert not np.array_equal(low, reference.replay(
        values, budgets, mult, reserve)[0])
    assert low.dtype == np.float32
    assert np.isfinite(low).all() and (low >= 0).all()
    assert not np.array_equal(rounded, values)


def test_spend_weighted_error_is_the_papers_metric():
    rng = np.random.default_rng(4)
    s_ref = rng.uniform(0.0, 10.0, (3, 12)).astype(np.float32)
    s_hat = (s_ref * rng.uniform(0.9, 1.1, s_ref.shape)).astype(np.float32)
    ours = reference.spend_weighted_error(s_hat, s_ref)
    for lane in range(3):
        theirs = float(spend_weighted_relative_error(
            jnp.asarray(s_hat[lane]), jnp.asarray(s_ref[lane])))
        assert ours[lane] == pytest.approx(theirs, rel=1e-5)


def test_synthetic_copy_draws_the_papers_distribution():
    from repro.data import make_synthetic_env
    cfg = {"n_events": 20000, "n_campaigns": 20, "emb_dim": 10, "market_seed": 0,
           "b_base": 7.0}
    ours = synthetic.make(jax.random.PRNGKey(0), cfg)
    theirs = make_synthetic_env(jax.random.PRNGKey(0), n_events=20000,
                                n_campaigns=20, emb_dim=10, b_base=7.0)
    np.testing.assert_array_equal(np.asarray(ours["budgets"]),
                                  np.asarray(theirs.budgets))
    a, b = np.asarray(ours["values"]), np.asarray(theirs.values)
    assert a.shape == b.shape and a.dtype == b.dtype
    assert 0.0 < a.min() and a.max() <= 1.0
    # campaign embeddings differ between the draws, so compare the
    # pooled valuation distribution
    assert np.quantile(a, 0.5) == pytest.approx(np.quantile(b, 0.5),
                                                rel=0.25)


def test_yahoo_copy_keeps_the_structure():
    cfg = {"n_keywords": 100, "n_campaigns": 40, "n_day1": 3000, "market_seed": 0,
           "n_day2": 4500, "budget": 2000.0,
           "assumed": {"keywords_per_campaign": 30, "zipf_a": 1.1,
                       "bid_scale": 0.05}}
    data = yahoo.make(jax.random.PRNGKey(1), cfg)
    day1, day2 = np.asarray(data["day1"]), np.asarray(data["day2"])
    assert day1.shape == (3000, 40) and day2.shape == (4500, 40)
    np.testing.assert_array_equal(np.asarray(data["budgets"]),
                                  np.full(40, 2000.0, np.float32))
    # a row is one keyword's column of the bid table: each campaign bids
    # on exactly 30 of the 100 keywords
    table = {}
    for row in np.concatenate([day1, day2]):
        table[row.tobytes()] = row
    bids = np.stack(list(table.values()))
    assert (bids > 0).sum(0).max() <= 30
    assert ((bids > 0).sum(1) >= 1).mean() > 0.9
