"""Segment-indexed (order-free) replay.

Once the activation history is pinned down as a piecewise-constant
:class:`~repro.core.types.Segments`, every quantity of the replay becomes a
parallel map over events plus reductions — the paper's central scalability
claim (§5 insight, §6 Step 3). This module implements:

* :func:`aggregate` — the "aggregate at scale" step: per-event winners/prices
  and per-campaign totals under a segment history;
* :func:`first_crossing_times` — blockwise detection of where each campaign's
  cumulative spend first crosses its budget *under a fixed segment history*
  (the engine of Step-2 refinement);
* :func:`block_spend_sums` — per-(block, campaign) partial sums, the map-side
  combiner a cluster implementation would emit.

All functions are pure jnp and shard cleanly along the event axis (see
``repro.core.sharded``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import auction
from repro.core.types import AuctionRule, Segments, SimResult, never_capped


@functools.partial(jax.jit, static_argnames=("record_events",
                                             "crossing_block"))
def aggregate(
    values: jax.Array,            # (N, C)
    segments: Segments,
    budgets: jax.Array,           # (C,)
    rule: AuctionRule,
    record_events: bool = True,
    crossing_block: int = 4096,
) -> SimResult:
    """Replay the whole log under a fixed segment history in one parallel pass.

    Every event's activation mask is a gather from ``segments.masks``; the
    resolution is a batched map; totals are segment sums. Cap times are
    *diagnosed* from the replay (first budget crossing) rather than assumed,
    which is the paper's built-in inconsistency check between Step 2 and
    Step 3. ``crossing_block`` sizes :func:`first_crossing_times`' blockwise
    scan (the default keeps the historical decomposition; the chunked
    SORT2AGGREGATE spine matches it to its chunk grid for the bitwise
    contract — see :func:`repro.core.sort2aggregate.refine_fixed_chunked`).
    """
    n_events, n_campaigns = values.shape
    seg_ids = segments.seg_ids(n_events)
    masks = segments.masks[seg_ids]               # (N, C) bool
    winners, prices = auction.resolve(values, masks, rule)
    final_spend = auction.spend_sums(winners, prices, n_campaigns)
    cap_times = first_crossing_times(winners, prices, budgets, n_campaigns,
                                     block=crossing_block)
    return SimResult(
        final_spend=final_spend, cap_times=cap_times,
        winners=winners if record_events else None,
        prices=prices if record_events else None,
        segments=segments)


def first_crossing_times(
    winners: jax.Array, prices: jax.Array, budgets: jax.Array,
    num_campaigns: int, block: int = 4096,
) -> jax.Array:
    """1-based index at which each campaign's cumulative spend crosses its
    budget; ``N+1`` if it never does.

    Blockwise scan: the (T, C) one-hot spend matrix is materialised one block
    at a time; the carry is the (C,) running total. On a cluster this is a
    prefix-sum (two-pass MapReduce); here a ``lax.scan`` over blocks.
    """
    n_events = winners.shape[0]
    sentinel = jnp.int32(never_capped(n_events))
    pad = (-n_events) % block
    w = jnp.pad(winners, (0, pad), constant_values=-1)
    p = jnp.pad(prices, (0, pad))
    n_blocks = w.shape[0] // block
    w = w.reshape(n_blocks, block)
    p = p.reshape(n_blocks, block)

    def step(carry, inp):
        s0, cap = carry
        wb, pb, b_idx = inp
        sm = auction.spend_matrix(wb, pb, num_campaigns)       # (block, C)
        cum = s0[None, :] + jnp.cumsum(sm, axis=0)             # (block, C)
        crossed = cum >= budgets[None, :]
        any_cross = crossed.any(axis=0)
        t_first = jnp.argmax(crossed, axis=0)                  # first True
        t_global = b_idx * block + t_first + 1                 # 1-based
        cap = jnp.where((cap == sentinel) & any_cross,
                        t_global.astype(jnp.int32), cap)
        return (cum[-1], cap), None

    init = (jnp.zeros((num_campaigns,), jnp.float32),
            jnp.full((num_campaigns,), sentinel, jnp.int32))
    (s_final, cap), _ = jax.lax.scan(
        step, init,
        (w, p, jnp.arange(n_blocks, dtype=jnp.int32)))
    del s_final
    return jnp.minimum(cap, sentinel)


# ---------------------------------------------------------------------------
# Canonical blocked reduction (mesh-invariant bit-for-bit arithmetic)
# ---------------------------------------------------------------------------
#
# The Algorithm-2 drivers' two per-round reductions (remaining-rate and
# block-spend) are NOT flat segment sums: they always go through a fixed
# (REDUCE_BLOCKS, C) grid of per-block partials that is summed in one final
# same-shaped reduce. Because each canonical block's partial is accumulated
# in event order regardless of where it is computed, a mesh-sharded driver
# whose shards align with block boundaries produces the *identical* partials
# tensor (each block owned by exactly one device; psum only adds exact
# zeros from the others) and then performs the identical final reduce —
# making `final_spend`/`cap_times` bit-for-bit equal on ANY aligned mesh
# shape, not merely "close". See docs/SCALING.md.

REDUCE_BLOCKS = 32


def reduce_block_size(n_events: int) -> int:
    """Events per canonical reduction block (ceil so any N is covered)."""
    return -(-n_events // REDUCE_BLOCKS)


def sum_blocks(parts: jax.Array) -> jax.Array:
    """The canonical final reduce: ``(..., REDUCE_BLOCKS, C)`` partials ->
    ``(..., C)``, adding the blocks' rows in block order.

    Spelled as an explicit sequence of adds (not ``.sum``) so its rounding
    is the same in every program that computes it — XLA on any backend and
    the fused round kernel's in-kernel prediction (which repeats this order
    row by row) — where a reduce's association order is the compiler's
    choice."""
    acc = parts[..., 0, :]
    for g in range(1, parts.shape[-2]):
        acc = acc + parts[..., g, :]
    return acc


def partial_spend_sums(
    winners: jax.Array, prices: jax.Array, num_campaigns: int,
    weights: jax.Array | None = None,
    *,
    block_size: int,
    index_offset=0,
) -> jax.Array:
    """(REDUCE_BLOCKS, C) per-canonical-block per-campaign partial spends.

    ``index_offset`` is the *global* event index of ``winners[0]`` — a shard
    passes its offset so its local events land in the same canonical blocks
    (and accumulate in the same order) as in a single-device reduction.
    Blocks outside the local range stay exactly 0.0.
    """
    p = prices if weights is None else prices * weights
    w = jnp.where(winners < 0, num_campaigns, winners)
    blk = (index_offset + jnp.arange(winners.shape[0])) // block_size
    ids = blk * (num_campaigns + 1) + w
    parts = jax.ops.segment_sum(
        p, ids, num_segments=REDUCE_BLOCKS * (num_campaigns + 1))
    return parts.reshape(REDUCE_BLOCKS, num_campaigns + 1)[:, :num_campaigns]


def rate_from_events(
    winners: jax.Array, prices: jax.Array, num_campaigns: int,
    start: jax.Array,
) -> jax.Array:
    """Mean per-campaign spend speed of resolved events with index >= start.

    Canonical blocked arithmetic: partials first, one (REDUCE_BLOCKS, C)
    reduce second — see :data:`REDUCE_BLOCKS`.
    """
    n_events = winners.shape[0]
    weight = (jnp.arange(n_events) >= start).astype(prices.dtype)
    parts = partial_spend_sums(winners, prices, num_campaigns, weight,
                               block_size=reduce_block_size(n_events))
    sums = sum_blocks(parts)
    denom = jnp.maximum(n_events - start, 1).astype(sums.dtype)
    return sums / denom


def block_from_events(
    winners: jax.Array, prices: jax.Array, num_campaigns: int,
    lo: jax.Array, hi: jax.Array,
) -> jax.Array:
    """Per-campaign spend of resolved events in the half-open block [lo, hi).

    Same canonical blocked arithmetic as :func:`rate_from_events`.
    """
    n_events = winners.shape[0]
    idx = jnp.arange(n_events)
    weight = ((idx >= lo) & (idx < hi)).astype(prices.dtype)
    parts = partial_spend_sums(winners, prices, num_campaigns, weight,
                               block_size=reduce_block_size(n_events))
    return sum_blocks(parts)


@jax.jit
def masked_rate(
    values: jax.Array,        # (N, C)
    active: jax.Array,        # (C,) bool
    rule: AuctionRule,
    start: jax.Array,         # () int — estimate over events with index >= start
) -> jax.Array:
    """E[f(e, a)] over the *remaining* events under a fixed activation mask.

    Under the random-order relaxation (Asm 3.1) the conditional expectation
    given the first ``start`` events is the empirical mean of the remainder —
    which is exactly what an offline replay can compute in parallel.
    """
    n_events, n_campaigns = values.shape
    winners, prices = auction.resolve(values, active, rule)
    return rate_from_events(winners, prices, n_campaigns, start)


@jax.jit
def block_spend_sums(
    values: jax.Array,        # (N, C)
    active: jax.Array,        # (C,) bool
    rule: AuctionRule,
    lo: jax.Array, hi: jax.Array,   # () int — half-open [lo, hi)
) -> jax.Array:
    """Per-campaign spend over events [lo, hi) under a fixed mask (order-free)."""
    n_events, n_campaigns = values.shape
    winners, prices = auction.resolve(values, active, rule)
    return block_from_events(winners, prices, n_campaigns, lo, hi)
