"""Pallas TPU kernels: one fused Algorithm-2 round (resolve + reductions).

The scenario-batched sweep loop (``repro.core.sweep.sweep_state_machine``)
spends each cap-out round on one resolve of the shared (N, C) valuation
matrix followed by two reductions of the resolved (S, N) winners/prices —
the per-scenario remaining-rate and the exact block spends. With the
``sweep_resolve`` kernel those winners/prices round-trip through HBM: the
kernel writes (S, N) int32 + (S, N) f32, and ``segments.partial_spend_sums``
reads them straight back just to collapse them onto the canonical
(REDUCE_BLOCKS, C) reduction grid. Algorithm 2 never consumes the raw
per-event outcomes — only the blocked spend partials — so the whole round is
fusable: this module resolves each (block_t, C) valuation tile against all S
scenario variants AND accumulates the (S, 32, C) canonical-block partials in
a VMEM-resident output block, emitting only reduction-shaped tensors.
Winners and prices never touch HBM.

Two kernels:

* :func:`round_fused_pallas` — the one-pass round for the single-device
  sweep: grid ``(2, num_tiles, S)``, phase slowest, scenario innermost.
  Phase 0 accumulates the rate partials (events ``>= n_hat``); at the first
  phase-1 step the kernel runs the per-lane cap-out prediction
  (``repro.core.executor.lane_predict``'s arithmetic, lane by lane)
  against the VMEM-resident partials and stores ``(c_next, no_cap, n_next)``;
  phase 1 accumulates the block partials (events in ``[n_hat, n_next)``).
  One kernel launch per round, two streams of the valuation matrix, zero
  per-event HBM output.
* :func:`sweep_partials_pallas` — one weighted partials pass (events in
  ``[lo, hi)``, per scenario) for drivers that must split the round at a
  reduction boundary: the mesh driver psums the rate partials, runs the
  prediction on the globally-reduced tensor, then issues this kernel again
  for the block partials — the kernel's (S, 32, C) output IS the psum
  operand (see docs/SCALING.md). The event-chunked streaming executor
  (``chunks=`` in repro.core.executor) reuses the same kernel per chunk:
  ``index_offset`` places each chunk's rows on the global canonical grid,
  and the chunk scan's accumulation is exact for the same
  unique-block-ownership reason the psum is (docs/ARCHITECTURE.md).

Block-relative tiles: the wrappers (``ops.py``) lay the log out so that no
(block_t, C) tile straddles a canonical reduction block — the tile grid
restarts at every block start, padding each block's last tile. A tile's
spends therefore fold into exactly one ``(scenario, block)`` row of the
partials, as the tile's row sum, and the grouping of every block's
additions depends only on the global canonical grid: a mesh shard or an
event chunk holding whole blocks produces the identical tiles, hence the
identical partials, as the single-device launch. That is what keeps
``final_spend``/``cap_times`` bit-for-bit across placements on a TPU
(docs/SCALING.md). The in-kernel prediction sums the G partials rows in
the same sequential order as :func:`repro.core.segments.sum_blocks`, the
sum every other placement uses.

Step skipping: a (tile, lane) grid step does tile work only where
:func:`_step_live` says so — its lane is alive (the per-scenario
``lane_alive`` mask; statically, ``skip_retired=True``: a lane whose
Algorithm-2 state is frozen contributes nothing, and the drivers discard
its outputs by select) and the tile's rows, clipped at their canonical
block's end, meet the lane's weight window (``[n_hat, N)`` in the rate
pass, ``[n_hat, n_next)`` in the block pass, ``[lo, hi)`` within the
slice for :func:`sweep_partials_pallas`). Both kernels call that one
predicate, so every placement skips the same steps. A skipped step would
add ``onehot · (price · 0)`` to the partials: prices are finite, layout
padding rows are zeros, and the partials start at +0 and only gain
non-negative terms, so the product is +0.0 and skipping it leaves every
output bitwise what the full grid gives. The tiles behind the earliest
window of any lane are not even fetched: a scalar-prefetched index map
clamps each step's tile to ``[first, last]`` (:func:`_first_live_tile`),
so a run of skipped steps repeats one block index and Pallas issues no
copy for it; a slice wholly behind every window clamps to its own last
tile. Retired lanes' skipping is asserted masked-vs-unmasked
bit-identical in ``tests/test_scenario_sweep.py`` /
``tests/test_sharded_sweep.py``; window skipping in
``tests/test_kernels.py``, where every step that runs with all weights 0
is poisoned with NaN.

TPU layout: per-lane scalars (reserves, ``n_hat``, ``lane_alive``, window
bounds, the predicted ``(c_next, no_cap, n_next)``) live in SMEM; per-lane
rows stay in resident (S, C) blocks read with a dynamic one-row slice;
per-row quantities are (T, 1) columns. VMEM need is modelled by
``repro.core.executor.round_fused_bytes`` against the compiler's scoped
limit (tests/test_tpu_compile.py holds the model to the compiler).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.auction_resolve.sweep_resolve import SMEM, resolve_tile


def _tile_rows(tile, *, tiles_per_block: int, block_t: int):
    """(block offset within the slice, (T, 1) block-relative row index) of
    one block-relative tile."""
    b = tile // tiles_per_block
    rel = (tile % tiles_per_block) * block_t + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, 1), 0)
    return b, rel


def _step_live(tile, lo, hi, alive, *, skip_retired: bool, first_block,
               block_size: int, block_t: int, tiles_per_block: int):
    """Whether a (tile, lane) grid step does tile work: the tile's global
    rows ``[start, end)`` — clipped at its canonical block's end, where
    the layout's padding starts — meet the lane's window ``[lo, hi)``,
    and, with ``skip_retired``, the lane is alive. ``first_block`` is the
    canonical block of the slice's first tile. Scalar int32 work only."""
    block_start = (first_block + tile // tiles_per_block) * block_size
    start = block_start + (tile % tiles_per_block) * block_t
    end = jnp.minimum(start + block_t, block_start + block_size)
    live = jnp.maximum(start, lo) < jnp.minimum(end, hi)
    if skip_retired:
        live = live & (alive != 0)
    return live


def _first_live_tile(lo, hi, alive, *, skip_retired: bool, first_block,
                     slice_start, slice_end, block_size: int, block_t: int,
                     tiles_per_block: int, n_tiles: int):
    """(1,) int32: the first tile that any step :func:`_step_live` admits
    can read — the tile of the earliest row, within the slice's rows
    ``[slice_start, slice_end)``, of any lane's window ``[lo, hi)`` (alive
    lanes only, with ``skip_retired``); the last tile where no window
    holds a row. The tile index maps clamp to it, so the skipped steps
    before it fetch nothing new."""
    lo = jnp.maximum(lo, slice_start)
    has_rows = lo < jnp.minimum(hi, slice_end)
    if skip_retired:
        has_rows = has_rows & (alive != 0)
    row = jnp.min(jnp.where(has_rows, lo, slice_end))
    # rows are non-negative: truncating division, with no sign fix-up
    tile = (jax.lax.div(row, block_size) - first_block) * tiles_per_block \
        + jax.lax.div(jax.lax.rem(row, block_size), block_t)
    return jnp.clip(tile, 0, n_tiles - 1).astype(jnp.int32).reshape(1)


def _clamp_tile(tile, first_ref, n_tiles: int):
    """The tile a grid step reads: ``min(max(tile, first), last)``."""
    return jnp.minimum(jnp.maximum(tile, first_ref[0]), n_tiles - 1)


def _tile_partials(v, mult, reserve, act, weight, g, *, num_blocks: int,
                   second_price: bool):
    """One tile's weighted per-campaign spends placed on the (G, C)
    canonical grid: row ``g`` (the tile's block) holds the tile's row sum,
    every other row exact zeros.

    The sum is a (G, T) x (T, C) product with the block's one-hot, at full
    f32 precision (a one-pass bf16 product would round every spend to 8
    mantissa bits): on the MXU it splits each spend exactly into bf16
    parts, and in interpret mode XLA adds the rows in order, as the jnp
    drivers' ``segment_sum`` does — which keeps the interpret-mode kernel
    bitwise the jnp loop on CPU. (A constant ones row would let XLA
    rewrite the product into a reduce of its own association order.)"""
    _, prices, onehot = resolve_tile(v, mult, reserve, act,
                                     second_price=second_price)
    rows = jax.lax.broadcasted_iota(jnp.int32, (num_blocks, v.shape[0]), 0)
    return jnp.dot((rows == g).astype(jnp.float32),
                   onehot * (prices * weight),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _block_sum(parts_ref, s, num_blocks: int):
    """Sum lane ``s``'s (G, C) partials rows in order — the arithmetic of
    :func:`repro.core.segments.sum_blocks`, so the in-kernel prediction
    sees the same rates as every other placement's."""
    acc = parts_ref[s, pl.ds(0, 1), :]
    for g in range(1, num_blocks):
        acc = acc + parts_ref[s, pl.ds(g, 1), :]
    return acc


def _predict_lane(sums, b, s_hat, act, n_hat, *, n_events: int):
    """``repro.core.executor.lane_predict`` for one lane, op for op, from
    its (1, C) remaining-spend sums. Every value stays a (1, C) or (1, 1)
    vector; returns (c_next, no_cap, n_next) as (1, 1) int32."""
    c = sums.shape[1]
    n_hat = jnp.full((1, 1), n_hat, jnp.int32)
    denom = jnp.maximum(n_events - n_hat, 1).astype(jnp.float32)
    rates = sums / denom
    ttl = jnp.where(act & (rates > 0), (b - s_hat) * denom / sums,
                    jnp.float32(jnp.inf))
    ttl = jnp.where(ttl < 0, jnp.float32(0.0), ttl)
    ttl_min = jnp.min(ttl, axis=1, keepdims=True)                # (1, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    c_next = jnp.min(jnp.where(ttl == ttl_min, cols, c), axis=1,
                     keepdims=True)                              # argmin
    no_cap = jnp.isinf(ttl_min)
    step = jnp.minimum(jnp.floor(ttl_min),
                       jnp.float32(n_events)).astype(jnp.int32)
    n_next = jnp.where(no_cap, jnp.int32(n_events),
                       jnp.minimum(n_hat + step, n_events))
    return c_next, no_cap.astype(jnp.int32), n_next


def _round_kernel(first_ref, v_ref, mult_ref, act_ref, b_ref, s_hat_ref,
                  reserve_ref, n_hat_ref, alive_ref,
                  rate_parts_ref, block_parts_ref, c_next_ref, no_cap_ref,
                  n_next_ref,
                  *, second_price: bool, skip_retired: bool, n_events: int,
                  block_size: int, num_blocks: int, block_t: int,
                  tiles_per_block: int):
    # first_ref, the scalar-prefetched clamp, is read by the index map only
    phase = pl.program_id(0)
    tile = pl.program_id(1)
    scn = pl.program_id(2)
    n_lanes = mult_ref.shape[0]

    @pl.when((phase == 0) & (tile == 0) & (scn == 0))
    def _init():
        rate_parts_ref[...] = jnp.zeros_like(rate_parts_ref)
        block_parts_ref[...] = jnp.zeros_like(block_parts_ref)

    # phase transition: the per-lane cap-out prediction, run once against
    # the now-complete rate partials (all O(S*C) state is VMEM-resident);
    # the per-lane scalars land in SMEM, where phase 1 reads n_next back
    @pl.when((phase == 1) & (tile == 0) & (scn == 0))
    def _predict():
        def lane(s, carry):
            row = pl.ds(s, 1)
            c_next, no_cap, n_next = _predict_lane(
                _block_sum(rate_parts_ref, s, num_blocks), b_ref[row, :],
                s_hat_ref[row, :], act_ref[row, :] != 0, n_hat_ref[s],
                n_events=n_events)
            c_next_ref[s] = c_next[0, 0]
            no_cap_ref[s] = no_cap[0, 0]
            n_next_ref[s] = n_next[0, 0]
            return carry

        jax.lax.fori_loop(0, n_lanes, lane, 0)

    # phase 0: remaining events [n_hat, N); phase 1: the predicted block
    # [n_hat, n_next) — same weight, upper-clipped. Both windows end at or
    # before N; rows past their block's end are padding.
    hi = jnp.where(phase == 0, jnp.int32(n_events), n_next_ref[scn])

    @pl.when(_step_live(tile, n_hat_ref[scn], hi, alive_ref[scn],
                        skip_retired=skip_retired, first_block=0,
                        block_size=block_size, block_t=block_t,
                        tiles_per_block=tiles_per_block))
    def _tile_work():
        g, rel = _tile_rows(tile, tiles_per_block=tiles_per_block,
                            block_t=block_t)
        gidx = g * block_size + rel
        weight = ((rel < block_size) & (gidx >= n_hat_ref[scn])
                  & (gidx < hi)).astype(jnp.float32)
        part = _tile_partials(
            v_ref[...].astype(jnp.float32), mult_ref[pl.ds(scn, 1), :],
            reserve_ref[scn], act_ref[pl.ds(scn, 1), :] != 0, weight, g,
            num_blocks=num_blocks, second_price=second_price)

        @pl.when(phase == 0)
        def _():
            rate_parts_ref[scn] += part

        @pl.when(phase == 1)
        def _():
            block_parts_ref[scn] += part


def round_fused_pallas(
    tiles: jax.Array,            # (n_tiles * T, C_pad) block-relative tiles
    multipliers: jax.Array,      # (S, C_pad) f32
    active: jax.Array,           # (S, C_pad) int32
    budgets: jax.Array,          # (S, C_pad) f32
    s_hat: jax.Array,            # (S, C_pad) f32
    reserves: jax.Array,         # (S,) f32
    n_hat: jax.Array,            # (S,) int32
    lane_alive: jax.Array,       # (S,) int32 — 0 = Algorithm-2 lane frozen
    *,
    n_events: int,               # true N (pre-padding)
    block_size: int,             # canonical reduction block (ceil(N / G))
    num_reduce_blocks: int,      # G — repro.core.segments.REDUCE_BLOCKS
    tiles_per_block: int,
    second_price: bool = False,
    skip_retired: bool = True,
    block_t: int = 256,
    interpret: bool = False,
):
    """One fused Algorithm-2 round for all S scenario lanes over the whole
    log, laid out by ``ops.block_tiles``.

    Returns ``(rate_partials (S, G, C), block_partials (S, G, C),
    c_next (S,) i32, no_cap (S,) i32, n_next (S,) i32)`` — only
    reduction-shaped outputs; the (S, N) winners/prices live and die in VMEM.
    """
    rows, c = tiles.shape
    s = multipliers.shape[0]
    assert rows % block_t == 0, (rows, block_t)
    g = num_reduce_blocks

    n_tiles = rows // block_t
    first = _first_live_tile(
        n_hat, jnp.int32(n_events), lane_alive, skip_retired=skip_retired,
        first_block=0, slice_start=0, slice_end=n_events,
        block_size=block_size, block_t=block_t,
        tiles_per_block=tiles_per_block, n_tiles=n_tiles)
    kernel = functools.partial(
        _round_kernel, second_price=second_price, skip_retired=skip_retired,
        n_events=n_events, block_size=block_size, num_blocks=g,
        block_t=block_t, tiles_per_block=tiles_per_block)

    full_sc = pl.BlockSpec((s, c), lambda p, i, j, f: (0, 0))
    parts = pl.BlockSpec((s, g, c), lambda p, i, j, f: (0, 0, 0))
    lane_i32 = jax.ShapeDtypeStruct((s,), jnp.int32)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                # first
            grid=(2, n_tiles, s),
            in_specs=[
                pl.BlockSpec((block_t, c), lambda p, i, j, f: (
                    _clamp_tile(i, f, n_tiles), 0)),        # tiles
                full_sc, full_sc, full_sc, full_sc,   # mult, active, b, s_hat
                SMEM, SMEM, SMEM,                     # reserves, n_hat, alive
            ],
            out_specs=[parts, parts, SMEM, SMEM, SMEM],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((s, g, c), jnp.float32),
            jax.ShapeDtypeStruct((s, g, c), jnp.float32),
            lane_i32, lane_i32, lane_i32,
        ],
        interpret=interpret,
        name="round_fused",
    )(first, tiles, multipliers, active, budgets, s_hat, reserves, n_hat,
      lane_alive)


def _partials_kernel(first_ref, v_ref, mult_ref, act_ref, reserve_ref,
                     lo_ref, hi_ref, alive_ref, place_ref,
                     parts_ref,
                     *, second_price: bool, skip_retired: bool,
                     block_size: int, num_blocks: int, block_t: int,
                     tiles_per_block: int):
    # first_ref, the scalar-prefetched clamp, is read by the index map only
    tile = pl.program_id(0)
    scn = pl.program_id(1)

    @pl.when((tile == 0) & (scn == 0))
    def _init():
        parts_ref[...] = jnp.zeros_like(parts_ref)

    # place_ref: (first canonical block of the slice, global index of the
    # slice's first row, of its last row + 1)
    @pl.when(_step_live(tile, jnp.maximum(lo_ref[scn], place_ref[1]),
                        jnp.minimum(hi_ref[scn], place_ref[2]),
                        alive_ref[scn], skip_retired=skip_retired,
                        first_block=place_ref[0], block_size=block_size,
                        block_t=block_t, tiles_per_block=tiles_per_block))
    def _tile_work():
        b, rel = _tile_rows(tile, tiles_per_block=tiles_per_block,
                            block_t=block_t)
        g = place_ref[0] + b
        gidx = g * block_size + rel
        weight = ((rel < block_size) & (gidx >= place_ref[1])
                  & (gidx < place_ref[2]) & (gidx >= lo_ref[scn])
                  & (gidx < hi_ref[scn])).astype(jnp.float32)
        parts_ref[scn] += _tile_partials(
            v_ref[...].astype(jnp.float32), mult_ref[pl.ds(scn, 1), :],
            reserve_ref[scn], act_ref[pl.ds(scn, 1), :] != 0, weight, g,
            num_blocks=num_blocks, second_price=second_price)


def sweep_partials_pallas(
    tiles: jax.Array,            # (n_tiles * T, C_pad) block-relative tiles
    multipliers: jax.Array,      # (S, C_pad) f32
    active: jax.Array,           # (S, C_pad) int32
    reserves: jax.Array,         # (S,) f32
    lo: jax.Array,               # (S,) int32 — weight window [lo, hi)
    hi: jax.Array,               # (S,) int32
    lane_alive: jax.Array,       # (S,) int32
    place: jax.Array,            # (3,) int32 — first block, row range
    *,
    block_size: int,
    num_reduce_blocks: int,
    tiles_per_block: int,
    second_price: bool = False,
    skip_retired: bool = True,
    block_t: int = 256,
    interpret: bool = False,
):
    """One fused resolve+reduce pass: (S, G, C) canonical partials of the
    spends of events in ``[lo, hi)`` per scenario. ``place`` puts a mesh
    shard's or chunk's tiles on the *global* canonical grid, so the output
    is the tensor :func:`repro.core.segments.partial_spend_sums` produces —
    and therefore exactly the mesh driver's psum operand."""
    rows, c = tiles.shape
    s = multipliers.shape[0]
    assert rows % block_t == 0, (rows, block_t)
    g = num_reduce_blocks
    n_tiles = rows // block_t
    first = _first_live_tile(
        lo, hi, lane_alive, skip_retired=skip_retired, first_block=place[0],
        slice_start=place[1], slice_end=place[2], block_size=block_size,
        block_t=block_t, tiles_per_block=tiles_per_block, n_tiles=n_tiles)
    kernel = functools.partial(
        _partials_kernel, second_price=second_price,
        skip_retired=skip_retired, block_size=block_size, num_blocks=g,
        block_t=block_t, tiles_per_block=tiles_per_block)
    full_sc = pl.BlockSpec((s, c), lambda i, j, f: (0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                # first
            grid=(n_tiles, s),
            in_specs=[
                pl.BlockSpec((block_t, c), lambda i, j, f: (
                    _clamp_tile(i, f, n_tiles), 0)),
                full_sc, full_sc,                 # multipliers, active
                SMEM, SMEM, SMEM, SMEM, SMEM,     # reserves, lo, hi, alive,
            ],                                    # place
            out_specs=pl.BlockSpec((s, g, c), lambda i, j, f: (0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((s, g, c), jnp.float32),
        interpret=interpret,
        name="sweep_partials",
    )(first, tiles, multipliers, active, reserves, lo, hi, lane_alive, place)
