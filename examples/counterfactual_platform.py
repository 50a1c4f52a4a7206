"""End-to-end platform example: events are scored into valuations, budgets
burn out, and the platform evaluates a design change with SORT2AGGREGATE.

1. each auction event gets an embedding, drawn as in paper §7.1, Eq. (11):
   e_i = (e_base + 3 xi_i) / 4 — the stand-in for the platform's event
   features;
2. campaign embeddings live in the same space; valuations follow Eq. (12)
   (``repro.data.synthetic.valuation_block``), computed block by block;
3. the platform replays the day under first price, then asks "what if we
   switched to second price with a reserve?" — the production SORT2AGGREGATE
   path answers, validated against the exact sequential oracle.

    PYTHONPATH=src python examples/counterfactual_platform.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (AuctionRule, CounterfactualEngine,
                        sequential_replay)
from repro.core.metrics import spend_weighted_relative_error
from repro.data.synthetic import valuation_block


def embed_events(n_events: int, emb_dim: int, key) -> jnp.ndarray:
    """Stage 1: event embeddings, Eq. (11), one shared base direction plus
    per-event noise."""
    e_base = jax.random.normal(jax.random.fold_in(key, 0), (emb_dim,))
    xi = jax.random.normal(jax.random.fold_in(key, 1), (n_events, emb_dim))
    return (e_base[None, :] + 3.0 * xi) / 4.0


def main():
    t0 = time.time()
    n_events, n_campaigns, emb_dim = 16_384, 40, 16
    key = jax.random.PRNGKey(0)

    print("== stage 1: event embeddings (Eq. 11) ==")
    event_emb = embed_events(n_events, emb_dim, key)
    print(f"   {event_emb.shape} in {time.time() - t0:.1f}s")

    print("== stage 2: valuations (Eq. 12) + budgets ==")
    campaign_emb = jax.random.normal(jax.random.fold_in(key, 3),
                                     (n_campaigns, emb_dim))
    block = 4096
    values = jnp.concatenate([
        valuation_block(event_emb[lo:lo + block] * 2.0, campaign_emb)
        for lo in range(0, n_events, block)])
    budgets = (jnp.arange(1, n_campaigns + 1, dtype=jnp.float32)
               * float(values.mean()) * n_events / n_campaigns / 4)

    print("== stage 3: counterfactual — first price -> second price+reserve ==")
    engine = CounterfactualEngine(values, budgets,
                                  AuctionRule.first_price(n_campaigns))
    alt = AuctionRule.second_price(n_campaigns, reserve=0.05)
    truth = sequential_replay(values, budgets, alt)
    est = engine.simulate(rule=alt, method="sort2aggregate",
                          key=jax.random.PRNGKey(1), sample_rate=0.05,
                          vi_iters=80, vi_eta=0.8, vi_eta_decay=0.03,
                          vi_batch_size=64, refine_iters=10)
    err = float(spend_weighted_relative_error(est.final_spend,
                                              truth.final_spend))
    base = engine.simulate(method="sequential")
    print(f"   revenue first-price : {float(base.final_spend.sum()):10.2f}")
    print(f"   revenue second+res  : {float(est.final_spend.sum()):10.2f} "
          f"(oracle {float(truth.final_spend.sum()):.2f}, werr {err:.4f})")
    print(f"   capped campaigns    : "
          f"{int((np.asarray(est.cap_times) <= n_events).sum())}"
          f"/{n_campaigns}")
    print(f"done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
