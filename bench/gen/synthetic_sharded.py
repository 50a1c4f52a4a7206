"""The paper's §7.1 synthetic market (``bench/gen/synthetic.py``), made
already sharded over a mesh: the (N, C) log's rows over the mesh's
``data`` axis, the budgets replicated.

The draw is ``synthetic._make``'s computation, jitted with output
shardings. JAX's random bits are partitionable (``jax_threefry_partitionable``,
on by default), so every chip draws its own rows on its own chip, the log
is never whole on one device, and the rows are the bits ``synthetic``
draws on one device for the same key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.gen.synthetic import _make


@functools.lru_cache(maxsize=None)
def _make_on(mesh):
    return jax.jit(_make.__wrapped__,
                   static_argnames=("n_events", "n_campaigns", "emb_dim"),
                   out_shardings=(NamedSharding(mesh, P("data", None)),
                                  NamedSharding(mesh, P())))


def make(key, cfg: dict, mesh) -> dict:
    """``{"values": (N, C) f32 sharded by rows over ``mesh``'s ``data``
    axis, "budgets": (C,) f32 replicated}``, from the keys ``synthetic``
    reads; ``key`` draws the events."""
    values, budgets = _make_on(mesh)(
        key, jax.random.PRNGKey(cfg["market_seed"]),
        jnp.float32(cfg["b_base"]), n_events=int(cfg["n_events"]),
        n_campaigns=int(cfg["n_campaigns"]), emb_dim=int(cfg["emb_dim"]))
    return {"values": values, "budgets": budgets}
