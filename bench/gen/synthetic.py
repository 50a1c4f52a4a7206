"""The paper's §7.1 synthetic market (arXiv:2509.04038, Eqs. 11-13), made
on the device in one jitted call from the seed.

* event embeddings   e_i = (e_base + 3 xi_i) / 4,  xi_i ~ N(0, I_d)
* campaign embeddings r_c ~ N(0, I_d)
* valuations         v_c(e_i) = min(exp(r_c . e_i / (2 sqrt(d))) / 10, 1)
* budgets            b^c = k * b_base, k = 1..C

The market (``e_base`` and the campaign embeddings) is drawn from the
configuration's ``market_seed``: one deployment's campaigns. The run's
seed draws the day's events, so every seed replays a fresh day of the same
market, with the same work to within the day's sampling noise.

A copy of the repository's ``data/synthetic.py`` (the benchmark keeps its
own yardstick), drawn as one (N, d) normal block instead of 65,536-row
blocks, so it draws the same distribution but not the same bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n_events", "n_campaigns",
                                             "emb_dim"))
def _make(key, market_key, b_base, *, n_events, n_campaigns, emb_dim):
    k_base, k_r = jax.random.split(market_key)
    k_xi = key
    e_base = jax.random.normal(k_base, (emb_dim,), jnp.float32)
    campaign_emb = jax.random.normal(k_r, (n_campaigns, emb_dim), jnp.float32)
    xi = jax.random.normal(k_xi, (n_events, emb_dim), jnp.float32)
    emb = (e_base[None, :] + 3.0 * xi) / 4.0
    logits = jnp.dot(emb, campaign_emb.T,
                     precision=jax.lax.Precision.HIGHEST) / (
        2.0 * jnp.sqrt(jnp.float32(emb_dim)))
    values = jnp.minimum(jnp.exp(logits) / 10.0, 1.0).astype(jnp.float32)
    budgets = jnp.arange(1, n_campaigns + 1, dtype=jnp.float32) * b_base
    return values, budgets


def make(key, cfg: dict) -> dict:
    """``{"values": (N, C) f32, "budgets": (C,) f32}`` on the default
    device, from ``cfg``'s ``n_events``, ``n_campaigns``, ``emb_dim``,
    ``b_base`` and ``market_seed``; ``key`` draws the events."""
    values, budgets = _make(key, jax.random.PRNGKey(cfg["market_seed"]),
                            jnp.float32(cfg["b_base"]),
                            n_events=int(cfg["n_events"]),
                            n_campaigns=int(cfg["n_campaigns"]),
                            emb_dim=int(cfg["emb_dim"]))
    return {"values": values, "budgets": budgets}
