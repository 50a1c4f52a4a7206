"""The paper's §7.2 Yahoo-like search market (arXiv:2509.04038 §7.2, with
the structure of Yahoo! Webscope A1 "Search Marketing advertiser bidding
data", which is gated and therefore simulated), made on the device in one
jitted call from the seed.

* K keywords with Zipf popularity (exponent ``zipf_a``), shuffled;
* each of C campaigns bids a constant log-normal bid (scale
  ``bid_scale``, log-sd 0.5) on ``keywords_per_campaign`` distinct
  keywords, and 0 elsewhere;
* day 1 is ``n_day1`` first-price auctions, day 2 ``n_day2``, each on a
  keyword drawn by popularity; event ``n``'s valuation row is the bid
  table's column of its keyword;
* every campaign has the same budget.

The market (the bid table and the keywords' popularity) is drawn from the
configuration's ``market_seed``; the run's seed draws the days' auctions.

A copy of the repository's ``data/yahoo.py``. The per-campaign loop there
is vectorised here (each campaign's keywords are the first
``keywords_per_campaign`` of a random permutation, drawn for all campaigns
at once), so it draws the same distribution but not the same bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=(
    "n_keywords", "n_campaigns", "n_day1", "n_day2",
    "keywords_per_campaign"))
def _make(key, market_key, budget, zipf_a, bid_scale, *, n_keywords,
          n_campaigns, n_day1, n_day2, keywords_per_campaign):
    k_bid, k_kw, k_pop = jax.random.split(market_key, 3)
    k_d1, k_d2 = jax.random.split(key)
    # keywords_per_campaign distinct keywords per campaign: the first ones
    # of an independent random permutation of all keywords
    order = jnp.argsort(jax.random.uniform(k_kw, (n_campaigns, n_keywords)),
                        axis=1)[:, :keywords_per_campaign]
    bids = jnp.exp(0.5 * jax.random.normal(
        k_bid, (n_campaigns, keywords_per_campaign))) * bid_scale
    rows = jnp.arange(n_campaigns)[:, None]
    bid_table = jnp.zeros((n_campaigns, n_keywords), jnp.float32).at[
        rows, order].set(bids.astype(jnp.float32))
    ranks = jnp.arange(1, n_keywords + 1, dtype=jnp.float32)
    probs = ranks ** (-zipf_a)
    probs = jax.random.permutation(k_pop, probs / probs.sum())
    logits = jnp.log(probs)
    day1 = jax.random.categorical(k_d1, logits, shape=(n_day1,))
    day2 = jax.random.categorical(k_d2, logits, shape=(n_day2,))
    table_t = bid_table.T
    budgets = jnp.full((n_campaigns,), budget, jnp.float32)
    return table_t[day1], table_t[day2], budgets


def make(key, cfg: dict) -> dict:
    """``{"day1": (N1, C), "day2": (N2, C), "budgets": (C,)}`` float32 on
    the default device, from ``cfg``; ``key`` draws the days' auctions."""
    a = cfg["assumed"]
    day1, day2, budgets = _make(
        key, jax.random.PRNGKey(cfg["market_seed"]),
        jnp.float32(cfg["budget"]), jnp.float32(a["zipf_a"]),
        jnp.float32(a["bid_scale"]), n_keywords=int(cfg["n_keywords"]),
        n_campaigns=int(cfg["n_campaigns"]), n_day1=int(cfg["n_day1"]),
        n_day2=int(cfg["n_day2"]),
        keywords_per_campaign=int(a["keywords_per_campaign"]))
    return {"day1": day1, "day2": day2, "budgets": budgets}
