"""Pure-jnp oracles for the auction_resolve kernels.

Two levels: :func:`auction_resolve_ref` mirrors the embedding-level
single-scenario kernel (valuations computed in-oracle); :func:`resolve_tile_ref`
/ :func:`sweep_resolve_ref` mirror the scenario-batched ``sweep_resolve``
kernel, which takes the valuation matrix directly (the sweep hot path's
representation) and resolves S (multiplier, reserve, mask) variants of it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = jnp.float32(-2.0 ** 30)


def valuations(event_emb: jax.Array, campaign_emb: jax.Array) -> jax.Array:
    """Paper Eq. (12): (T, d), (C, d) -> (T, C) in [0, 1]."""
    d = event_emb.shape[-1]
    logits = (event_emb.astype(jnp.float32)
              @ campaign_emb.astype(jnp.float32).T) \
        / (2.0 * jnp.sqrt(jnp.float32(d)))
    return jnp.minimum(jnp.exp(logits) / 10.0, 1.0)


def auction_resolve_ref(
    event_emb: jax.Array,        # (T, d)
    campaign_emb: jax.Array,     # (C, d)
    multipliers: jax.Array,      # (C,)
    active: jax.Array,           # (C,) or (T, C) bool
    reserve: jax.Array,          # ()
    second_price: bool = False,
):
    """Returns (winners (T,) int32 [-1 = no sale], prices (T,) f32,
    spend_sums (C,) f32)."""
    t, _ = event_emb.shape
    c = campaign_emb.shape[0]
    v = valuations(event_emb, campaign_emb)
    bids = v * multipliers[None, :].astype(jnp.float32)
    act = active if active.ndim == 2 else jnp.broadcast_to(active[None, :],
                                                           (t, c))
    eligible = act & (bids > reserve)
    masked = jnp.where(eligible, bids, NEG)
    winners = jnp.argmax(masked, axis=1).astype(jnp.int32)
    top = jnp.max(masked, axis=1)
    sale = top > NEG
    if second_price:
        masked2 = jnp.where(
            jnp.arange(c)[None, :] == winners[:, None], NEG, masked)
        second = jnp.max(masked2, axis=1)
        prices = jnp.where(sale,
                           jnp.maximum(jnp.where(second > NEG, second,
                                                 reserve), reserve), 0.0)
    else:
        prices = jnp.where(sale, top, 0.0)
    winners = jnp.where(sale, winners, -1)
    onehot = (jnp.arange(c)[None, :] == winners[:, None]).astype(jnp.float32)
    sums = (onehot * prices[:, None]).sum(axis=0)
    return winners, prices.astype(jnp.float32), sums


def resolve_tile_ref(
    values: jax.Array,           # (T, C) — precomputed valuations
    multipliers: jax.Array,      # (C,)
    active: jax.Array,           # (C,) or (T, C) bool
    reserve: jax.Array,          # ()
    second_price: bool = False,
):
    """Single-scenario resolve of a valuation tile (winners, prices, sums)."""
    t, c = values.shape
    bids = values.astype(jnp.float32) * multipliers[None, :].astype(jnp.float32)
    act = active if active.ndim == 2 else jnp.broadcast_to(active[None, :],
                                                           (t, c))
    eligible = act & (bids > reserve)
    masked = jnp.where(eligible, bids, NEG)
    winners = jnp.argmax(masked, axis=1).astype(jnp.int32)
    top = jnp.max(masked, axis=1)
    sale = top > NEG
    if second_price:
        masked2 = jnp.where(
            jnp.arange(c)[None, :] == winners[:, None], NEG, masked)
        second = jnp.max(masked2, axis=1)
        prices = jnp.where(sale,
                           jnp.maximum(jnp.where(second > NEG, second,
                                                 reserve), reserve), 0.0)
    else:
        prices = jnp.where(sale, top, 0.0)
    winners = jnp.where(sale, winners, -1)
    onehot = (jnp.arange(c)[None, :] == winners[:, None]).astype(jnp.float32)
    sums = (onehot * prices[:, None]).sum(axis=0)
    return winners, prices.astype(jnp.float32), sums


def sweep_resolve_ref(
    values: jax.Array,           # (N, C) — shared across scenarios
    multipliers: jax.Array,      # (S, C)
    active: jax.Array,           # (S, C) or (S, N, C) bool
    reserves: jax.Array,         # (S,)
    second_price: bool = False,
):
    """Scenario-batched oracle: S independent tile resolves, vmapped.

    Returns (winners (S, N) int32 [-1 = no sale], prices (S, N) f32,
    spend_sums (S, C) f32)."""
    return jax.vmap(
        lambda m, a, r: resolve_tile_ref(values, m, a, r,
                                         second_price=second_price),
        in_axes=(0, 0, 0))(multipliers, active,
                           jnp.asarray(reserves, jnp.float32))


# ---------------------------------------------------------------------------
# Fused-round oracles (mirror kernels in round_fused.py)
# ---------------------------------------------------------------------------
#
# These mirror the fused Algorithm-2 round kernels: resolve + canonical-grid
# reduction in one function, winners/prices internal only. The partials use
# the same segment_sum arithmetic as ``repro.core.segments.partial_spend_sums``
# (and the prediction the same per-lane math as
# ``repro.core.parallel.lane_predict``), duplicated here so the kernel package
# stays import-independent of ``repro.core`` — parity between the two copies
# is pinned by the driver equivalence tests in tests/test_scenario_sweep.py.


def fused_partials_ref(
    values: jax.Array,           # (N_local, C) — shared across scenarios
    multipliers: jax.Array,      # (S, C)
    active: jax.Array,           # (S, C) bool
    reserves: jax.Array,         # (S,)
    lo: jax.Array,               # (S,) int32 — weight window [lo, hi), global
    hi: jax.Array,               # (S,) int32
    *,
    block_size: int,             # canonical block (ceil(N_global / G))
    reduce_blocks: int = 32,     # G
    second_price: bool = False,
    index_offset=0,              # global index of values[0] (mesh shards)
):
    """(S, G, C) canonical-block partial spends of events in ``[lo, hi)``."""
    n_local, c = values.shape
    gidx = index_offset + jnp.arange(n_local, dtype=jnp.int32)

    def one(m, a, r, lo_s, hi_s):
        winners, prices, _ = resolve_tile_ref(values, m, a, r,
                                              second_price=second_price)
        weight = ((gidx >= lo_s) & (gidx < hi_s)).astype(prices.dtype)
        w = jnp.where(winners < 0, c, winners)
        ids = (gidx // block_size) * (c + 1) + w
        parts = jax.ops.segment_sum(
            prices * weight, ids, num_segments=reduce_blocks * (c + 1))
        return parts.reshape(reduce_blocks, c + 1)[:, :c]

    return jax.vmap(one)(multipliers, active,
                         jnp.asarray(reserves, jnp.float32),
                         jnp.asarray(lo, jnp.int32),
                         jnp.asarray(hi, jnp.int32))


def round_fused_ref(
    values: jax.Array,           # (N, C)
    multipliers: jax.Array,      # (S, C)
    active: jax.Array,           # (S, C) bool
    reserves: jax.Array,         # (S,)
    budgets: jax.Array,          # (S, C)
    s_hat: jax.Array,            # (S, C)
    n_hat: jax.Array,            # (S,) int32
    *,
    block_size: int,
    reduce_blocks: int = 32,
    second_price: bool = False,
):
    """One fused Algorithm-2 round, pure jnp: rate partials over the
    remaining events, the per-lane cap-out prediction, block partials over
    the predicted block. Returns ``(rate_partials (S, G, C), block_partials
    (S, G, C), c_next (S,), no_cap (S,), n_next (S,))``."""
    n_events = values.shape[0]
    n_hat = jnp.asarray(n_hat, jnp.int32)
    rate_parts = fused_partials_ref(
        values, multipliers, active, reserves, n_hat,
        jnp.full_like(n_hat, n_events), block_size=block_size,
        reduce_blocks=reduce_blocks, second_price=second_price)

    # lane_predict, vectorised over lanes (same arithmetic, same order)
    sums = rate_parts.sum(axis=1)
    denom = jnp.maximum(n_events - n_hat[:, None], 1).astype(jnp.float32)
    rates = sums / denom
    ttl = jnp.where(active & (rates > 0),
                    (budgets.astype(jnp.float32) - s_hat) * denom / sums,
                    jnp.float32(jnp.inf))
    ttl = jnp.where(ttl < 0, jnp.float32(0.0), ttl)
    c_next = jnp.argmin(ttl, axis=1).astype(jnp.int32)
    ttl_min = jnp.min(ttl, axis=1)
    no_cap = jnp.isinf(ttl_min)
    step = jnp.minimum(jnp.floor(ttl_min),
                       jnp.float32(n_events)).astype(jnp.int32)
    n_next = jnp.where(no_cap, jnp.int32(n_events),
                       jnp.minimum(n_hat + step, n_events))

    block_parts = fused_partials_ref(
        values, multipliers, active, reserves, n_hat, n_next,
        block_size=block_size, reduce_blocks=reduce_blocks,
        second_price=second_price)
    return rate_parts, block_parts, c_next, no_cap, n_next
