"""jit'd public wrappers for the auction_resolve kernels.

Each wrapper pads events to the block size and campaigns/embedding dims to
MXU-friendly multiples (padded events are masked by the true row count;
padded campaigns are inactive), dispatches to the Pallas kernel (compiled
on a TPU; ``interpret=None`` resolves at trace time to the interpreter
anywhere else, the CPU validation mode), and un-pads. Per-lane masks reach
the sweep kernels as int32 (per-event masks as int8 tiles, widened in the
kernel) and per-lane scalars as (S,) vectors: Mosaic extracts only 32-bit
scalars and keeps those in SMEM.

* :func:`auction_resolve` — single scenario, valuations computed in-kernel
  from (event, campaign) embeddings off the MXU;
* :func:`sweep_resolve` — S scenarios against one shared precomputed
  valuation matrix, each (block_t, C) tile fetched into VMEM once and reused
  across the whole scenario batch (the ``repro.core.sweep`` hot path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_interpret
from repro.kernels.auction_resolve.auction_resolve import auction_resolve_pallas
from repro.kernels.auction_resolve.round_fused import (round_fused_pallas,
                                                       sweep_partials_pallas)
from repro.kernels.auction_resolve.sweep_resolve import sweep_resolve_pallas


def _pad_to(x: jax.Array, size: int, axis: int, value=0):
    pad = (-x.shape[axis]) % size
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("second_price", "block_t",
                                             "interpret"))
def auction_resolve(
    event_emb: jax.Array,        # (N, d)
    campaign_emb: jax.Array,     # (C, d)
    multipliers: jax.Array,      # (C,)
    active: jax.Array,           # (C,) or (N, C)
    reserve: jax.Array = 0.0,
    *,
    second_price: bool = False,
    block_t: int = 256,
    interpret: bool | None = None,
):
    """Returns (winners (N,) int32 [-1 = no sale], prices (N,) f32,
    per-campaign spend sums (C,) f32)."""
    n, d = event_emb.shape
    c = campaign_emb.shape[0]
    e = _pad_to(_pad_to(event_emb, block_t, 0), 128, 1)
    r = _pad_to(_pad_to(campaign_emb, 128, 0), 128, 1)
    mult = _pad_to(multipliers.astype(jnp.float32), 128, 0)
    live = _pad_to(jnp.ones((n,), jnp.int8), block_t, 0)
    if active.ndim == 2:
        act = _pad_to(_pad_to(active.astype(jnp.int8), block_t, 0), 128, 1)
    else:
        act = _pad_to(active.astype(jnp.int8), 128, 0)
    winners, prices, sums = auction_resolve_pallas(
        e, r, mult, act, live, jnp.asarray(reserve, jnp.float32),
        second_price=second_price, block_t=block_t,
        interpret=use_interpret(interpret), true_d=d)
    return winners[:n], prices[:n], sums[:c]


@functools.partial(jax.jit, static_argnames=("second_price", "block_t",
                                             "interpret"))
def sweep_resolve(
    values: jax.Array,           # (N, C) — shared valuation matrix
    multipliers: jax.Array,      # (S, C)
    active: jax.Array,           # (S, C) or (S, N, C)
    reserves: jax.Array = 0.0,   # (S,) or scalar
    *,
    second_price: bool = False,
    block_t: int = 256,
    interpret: bool | None = None,
):
    """Resolve S scenarios against one valuation matrix in a single kernel.

    Returns (winners (S, N) int32 [-1 = no sale], prices (S, N) f32,
    per-campaign spend sums (S, C) f32), bit-identical per scenario to the
    vmapped ``repro.core.auction.resolve`` path on the same inputs.
    """
    n, c = values.shape
    n_scenarios = multipliers.shape[0]
    v = _pad_to(_pad_to(values.astype(jnp.float32), block_t, 0), 128, 1)
    mult = _pad_to(multipliers.astype(jnp.float32), 128, 1)
    res = jnp.broadcast_to(jnp.asarray(reserves, jnp.float32),
                           (n_scenarios,))
    if active.ndim == 3:
        act = _pad_to(_pad_to(active.astype(jnp.int8), block_t, 1), 128, 2)
    else:
        act = _pad_to(active.astype(jnp.int32), 128, 1)
    winners, prices, sums = sweep_resolve_pallas(
        v, mult, act, res, n_rows=n, second_price=second_price,
        block_t=block_t, interpret=use_interpret(interpret))
    return winners[:, :n], prices[:, :n], sums[:, :c]


def block_tiles(values, *, block_size: int, block_t: int,
                offset_in_block: int = 0):
    """Lay a slice of the event log out as block-relative tiles.

    Row ``r`` of ``values`` sits at global index ``offset + r``, with
    ``offset % block_size == offset_in_block`` (static). Every canonical
    block the slice touches gets ``tiles_per_block`` tiles of ``t`` rows,
    starting at the block's first row; rows outside the slice or past the
    block's end are zero padding that the kernels mask. Campaigns pad to
    lane multiples of 128. ``t`` is ``block_t``, or the block size rounded
    up to 8 rows when blocks are smaller than a tile.

    Returns ``(tiles (n_blocks * tiles_per_block * t, C_pad), t,
    tiles_per_block)``."""
    n, c = values.shape
    t = min(block_t, -(-block_size // 8) * 8)
    tiles_per_block = -(-block_size // t)
    n_blocks = -(-(offset_in_block + n) // block_size)
    c_pad = -(-c // 128) * 128
    # a row gather (row n of the padded source is the zero row): block
    # sizes are rarely a multiple of the 8-row sublane tile, and a
    # (blocks, block_size, C) reshape would force a relayout of the log
    r = jnp.arange(n_blocks * tiles_per_block * t, dtype=jnp.int32)
    within = r % (tiles_per_block * t)
    src = (r // (tiles_per_block * t)) * block_size + within \
        - offset_in_block
    valid = (within < block_size) & (src >= 0) & (src < n)
    v = jnp.pad(values.astype(jnp.float32), ((0, 1), (0, c_pad - c)))
    return jnp.take(v, jnp.where(valid, src, n), axis=0), t, tiles_per_block


def _pad_scenario_state(multipliers, active, reserves):
    """Campaign padding for the fused-round kernels' per-lane state: lane
    multiples of 128, padded campaigns inactive."""
    n_scenarios = multipliers.shape[0]
    mult = _pad_to(multipliers.astype(jnp.float32), 128, 1)
    act = _pad_to(active.astype(jnp.int32), 128, 1)
    res = jnp.broadcast_to(jnp.asarray(reserves, jnp.float32),
                           (n_scenarios,))
    return mult, act, res


@functools.partial(jax.jit, static_argnames=(
    "n_events", "t", "tiles_per_block", "reduce_blocks", "second_price",
    "skip_retired", "interpret"))
def round_fused_tiles(
    tiles: jax.Array,            # block_tiles(values) — the laid-out log
    multipliers: jax.Array,      # (S, C)
    active: jax.Array,           # (S, C) bool — current activation sets
    reserves: jax.Array,         # (S,) or scalar
    budgets: jax.Array,          # (S, C)
    s_hat: jax.Array,            # (S, C) — spends so far
    n_hat: jax.Array,            # (S,) int32 — current event frontier
    lane_alive: jax.Array,       # (S,) bool — False = Algorithm-2 lane frozen
    *,
    n_events: int,               # N, the rows the tiles were laid out from
    t: int,                      # rows a tile, from block_tiles
    tiles_per_block: int,        # from block_tiles
    reduce_blocks: int,          # repro.core.segments.REDUCE_BLOCKS
    second_price: bool = False,
    skip_retired: bool = True,
    interpret: bool | None = None,
):
    """:func:`round_fused` on a log already laid out by :func:`block_tiles`
    (``block_size = ceil(n_events / reduce_blocks)``, no offset): the entry
    a round loop calls, so that the layout is made once, outside it."""
    c = multipliers.shape[1]
    block_size = -(-n_events // reduce_blocks)
    mult, act, res = _pad_scenario_state(multipliers, active, reserves)
    b = _pad_to(budgets.astype(jnp.float32), 128, 1)
    s = _pad_to(s_hat.astype(jnp.float32), 128, 1)
    rate_parts, block_parts, c_next, no_cap, n_next = round_fused_pallas(
        tiles, mult, act, b, s, res, jnp.asarray(n_hat, jnp.int32),
        lane_alive.astype(jnp.int32),
        n_events=n_events, block_size=block_size,
        num_reduce_blocks=reduce_blocks, tiles_per_block=tiles_per_block,
        second_price=second_price, skip_retired=skip_retired, block_t=t,
        interpret=use_interpret(interpret))
    return (rate_parts[:, :, :c], block_parts[:, :, :c],
            jnp.minimum(c_next, c - 1), no_cap != 0, n_next)


@functools.partial(jax.jit, static_argnames=(
    "reduce_blocks", "second_price", "skip_retired", "block_t", "interpret"))
def round_fused(
    values: jax.Array,           # (N, C) — shared valuation matrix
    multipliers: jax.Array,      # (S, C)
    active: jax.Array,           # (S, C) bool — current activation sets
    reserves: jax.Array,         # (S,) or scalar
    budgets: jax.Array,          # (S, C)
    s_hat: jax.Array,            # (S, C) — spends so far
    n_hat: jax.Array,            # (S,) int32 — current event frontier
    lane_alive: jax.Array,       # (S,) bool — False = Algorithm-2 lane frozen
    *,
    reduce_blocks: int,          # repro.core.segments.REDUCE_BLOCKS
    second_price: bool = False,
    skip_retired: bool = True,
    block_t: int = 256,
    interpret: bool | None = None,
):
    """One fused Algorithm-2 round for S scenario lanes (see
    ``round_fused.py``): resolve + rate partials + cap-out prediction +
    block partials in a single kernel launch, with retired lanes skipped.
    Lays ``values`` out, then calls :func:`round_fused_tiles`.

    Returns ``(rate_partials (S, G, C), block_partials (S, G, C),
    c_next (S,) i32, no_cap (S,) bool, n_next (S,) i32)`` — sum a partials
    tensor over its G axis (:func:`repro.core.segments.sum_blocks`) to get
    the (S, C) reduction the per-lane logic consumes."""
    n = values.shape[0]
    with jax.named_scope("relayout"):
        tiles, t, tpb = block_tiles(values, block_size=-(-n // reduce_blocks),
                                    block_t=block_t)
    return round_fused_tiles(
        tiles, multipliers, active, reserves, budgets, s_hat, n_hat,
        lane_alive, n_events=n, t=t, tiles_per_block=tpb,
        reduce_blocks=reduce_blocks, second_price=second_price,
        skip_retired=skip_retired, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "n_rows", "n_events_global", "t", "tiles_per_block", "reduce_blocks",
    "second_price", "skip_retired", "interpret"))
def sweep_partials_tiles(
    tiles: jax.Array,            # block_tiles(values, offset_in_block=...)
    multipliers: jax.Array,      # (S, C)
    active: jax.Array,           # (S, C) bool
    reserves: jax.Array,         # (S,) or scalar
    lo: jax.Array,               # (S,) int32 — global weight window [lo, hi)
    hi: jax.Array,               # (S,) int32
    lane_alive: jax.Array,       # (S,) bool
    offset: jax.Array,           # () int32 — global index of values[0]
    *,
    n_rows: int,                 # rows of the slice the tiles hold
    n_events_global: int,        # N across all shards (canonical grid base)
    t: int,                      # rows a tile, from block_tiles
    tiles_per_block: int,        # from block_tiles
    reduce_blocks: int,
    second_price: bool = False,
    skip_retired: bool = True,
    interpret: bool | None = None,
):
    """:func:`sweep_partials` on a slice already laid out by
    :func:`block_tiles` (``block_size = ceil(n_events_global /
    reduce_blocks)``, the slice's ``offset_in_block``): the entry a round
    loop calls, so that the layout is made once, outside it."""
    c = multipliers.shape[1]
    block_size = -(-n_events_global // reduce_blocks)
    mult, act, res = _pad_scenario_state(multipliers, active, reserves)
    offset = jnp.asarray(offset, jnp.int32)
    place = jnp.stack([offset // block_size, offset, offset + n_rows])
    parts = sweep_partials_pallas(
        tiles, mult, act, res,
        jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
        lane_alive.astype(jnp.int32), place,
        block_size=block_size, num_reduce_blocks=reduce_blocks,
        tiles_per_block=tiles_per_block, second_price=second_price,
        skip_retired=skip_retired, block_t=t,
        interpret=use_interpret(interpret))
    return parts[:, :, :c]


@functools.partial(jax.jit, static_argnames=(
    "n_events_global", "reduce_blocks", "offset_in_block", "second_price",
    "skip_retired", "block_t", "interpret"))
def sweep_partials(
    values: jax.Array,           # (N_local, C) — this shard's valuations
    multipliers: jax.Array,      # (S, C)
    active: jax.Array,           # (S, C) bool
    reserves: jax.Array,         # (S,) or scalar
    lo: jax.Array,               # (S,) int32 — global weight window [lo, hi)
    hi: jax.Array,               # (S,) int32
    lane_alive: jax.Array,       # (S,) bool
    offset: jax.Array,           # () int32 — global index of values[0]
    *,
    n_events_global: int,        # N across all shards (canonical grid base)
    reduce_blocks: int,
    offset_in_block: int = 0,    # offset % canonical block size (static)
    second_price: bool = False,
    skip_retired: bool = True,
    block_t: int = 256,
    interpret: bool | None = None,
):
    """One fused resolve+reduce pass over a slice of the event log: (S, G, C)
    canonical partials of events in ``[lo, hi)``, the slice's rows placed on
    the *global* reduction grid via ``offset``. The same offset mechanism
    serves both sweep-executor axes (repro.core.executor): a mesh shard
    passes its row-major rank × local_n and psums the result; a streaming
    chunk passes ``shard_offset + chunk_index * events_per_chunk`` and
    accumulates across the chunk scan. Shards and chunks hold whole
    canonical blocks (``offset_in_block=0``), so their block-relative tiles
    are the single-device launch's tiles and the output is bit-for-bit the
    slice of its partials — which is what keeps every placement
    bit-for-bit. Only a resumable fold starts mid-block. Lays ``values``
    out, then calls :func:`sweep_partials_tiles`."""
    n = values.shape[0]
    with jax.named_scope("relayout"):
        tiles, t, tpb = block_tiles(
            values, block_size=-(-n_events_global // reduce_blocks),
            block_t=block_t, offset_in_block=offset_in_block)
    return sweep_partials_tiles(
        tiles, multipliers, active, reserves, lo, hi, lane_alive, offset,
        n_rows=n, n_events_global=n_events_global, t=t, tiles_per_block=tpb,
        reduce_blocks=reduce_blocks, second_price=second_price,
        skip_retired=skip_retired, interpret=interpret)
