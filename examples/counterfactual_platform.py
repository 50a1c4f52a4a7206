"""End-to-end platform example: an LM scores auction events, budgets burn
out, and the platform evaluates a design change with SORT2AGGREGATE.

This wires the two halves of the framework together (paper §4: "f ... may
also include ML inferences that influence the allocation decision"):

1. a reduced xlstm-125m backbone embeds each auction event's token context
   (query/product tokens) — the event-embedding stage of the valuation model;
2. campaign embeddings live in the same space; valuations follow Eq. (12),
   computed by the Pallas auction kernel's oracle path;
3. the platform replays the day under first-price, then asks "what if we
   switched to second-price with a reserve?" — the production SORT2AGGREGATE
   path answers, validated against the exact sequential oracle.

    PYTHONPATH=src python examples/counterfactual_platform.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced_config
from repro.core import (AuctionRule, CounterfactualEngine,
                        sequential_replay)
from repro.core.metrics import spend_weighted_relative_error
from repro.data.synthetic import valuation_block
from repro.models import build_model


def embed_events_with_lm(n_events: int, emb_dim: int, key) -> jnp.ndarray:
    """Stage 1: LM-derived event embeddings (mean-pooled hidden states of a
    reduced xlstm backbone over each event's token context)."""
    cfg = reduced_config("xlstm-125m")
    model = build_model(cfg)
    params = model.init_params(key)
    k_tok = jax.random.fold_in(key, 1)
    seq = 16
    from repro.models import lm as lm_lib
    from repro.models.layers import embed, rmsnorm

    def _group_step(carry, gp):
        x, aux = carry
        x, _, a = lm_lib._apply_group(
            gp, x, cfg, "train", None, None,
            jnp.arange(seq, dtype=jnp.int32)[None, :], seq)
        return (x, aux + a), None

    @jax.jit
    def hidden_pool(tokens):
        # forward without the LM head: embed + blocks + final norm
        x = embed(params["embed"], tokens)
        (x, _), _ = jax.lax.scan(_group_step, (x, jnp.float32(0.0)),
                                 params["groups"])
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return x.mean(axis=1)

    out = []
    bs = 512
    proj = jax.random.normal(jax.random.fold_in(key, 2),
                             (cfg.d_model, emb_dim), jnp.float32) \
        / np.sqrt(cfg.d_model)
    for lo in range(0, n_events, bs):
        hi = min(lo + bs, n_events)
        toks = jax.random.randint(jax.random.fold_in(k_tok, lo),
                                  (hi - lo, seq), 0, cfg.vocab_size)
        h = hidden_pool(toks).astype(jnp.float32)
        out.append(h @ proj)
    return jnp.concatenate(out)


def main():
    t0 = time.time()
    n_events, n_campaigns, emb_dim = 16_384, 40, 16
    key = jax.random.PRNGKey(0)

    print("== stage 1: LM event embeddings (reduced xlstm backbone) ==")
    event_emb = embed_events_with_lm(n_events, emb_dim, key)
    print(f"   {event_emb.shape} in {time.time() - t0:.1f}s")

    print("== stage 2: valuations + budgets ==")
    campaign_emb = jax.random.normal(jax.random.fold_in(key, 3),
                                     (n_campaigns, emb_dim))
    values = valuation_block(event_emb * 2.0, campaign_emb)
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
