"""Pallas TPU kernel: scenario-batched auction resolution (the sweep hot path).

The scenario-sweep drivers (``repro.core.sweep``) spend their time resolving
the same (N, C) valuation matrix under S design variants — per-scenario bid
multipliers, reserves, and live/activation masks. The vmapped jnp path streams
the full valuation matrix from HBM once *per scenario*; this kernel inverts
the loop: the grid is ``(num_blocks, num_scenarios)`` with the scenario axis
innermost, and the values BlockSpec maps every inner step to the SAME
(block_t, C) tile, so Pallas fetches the tile into VMEM once per block and
resolves all S scenarios against it before moving on — S-fold reuse of the
dominant HBM read (and of the (N, d) @ (d, C) matmul that produced the tile,
which would otherwise be recomputed per scenario by the embedding-level
single-scenario kernel in ``auction_resolve.py``).

Per (block, scenario) step the VPU does the row-wise masked argmax (top-2 for
second price) and the per-campaign one-hot spend reduction; per-scenario spend
sums accumulate across the sequential grid in the (S, C) output block, which
has a constant index map and therefore stays resident in VMEM for the whole
grid — the kernel-level "combiner" of the MapReduce formulation.

TPU layout rules the kernel follows (Mosaic refuses the alternatives):

* per-scenario rows (multipliers, activation) stay as one resident (S, C)
  block read with a dynamic one-row slice — a (1, C) block of an (S, C)
  array breaks the (8, 128) block rule; masks travel as int32;
* per-scenario scalars (reserves) live in SMEM;
* per-event quantities are (T, 1) columns (``keepdims`` reductions), and
  the (S, block_t) winners/prices output blocks hold every scenario's row
  of one event block, written one row per scenario step;
* padded event rows are masked by a static row count, not an input.

VMEM budget per step (fp32): block_t*C (values tile) + block_t*C (masked
bids) + S*C (sums) + O(block_t + C) vectors — with the defaults block_t=256,
C<=1024, S<=64 this stays well under 16 MB; the caller (ops.py) pads block_t
and C to multiples of 128 so every tile is VPU-lane aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -2.0 ** 30    # python float: jnp constants would be captured tracers

SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole array, scalar memory


def resolve_tile(v, mult, reserve, act, *, second_price: bool):
    """Resolve one (T, C) tile under one scenario's (multiplier (1, C),
    reserve scalar, activation (1, C) or (T, C) bool) variant.

    Returns (winners (T, 1) i32 [-1 = no sale], prices (T, 1) f32, onehot
    (T, C) f32 of the winning campaign). Winners are the first index of the
    row maximum — ``jnp.argmax``'s tie rule — computed as a min over column
    indices so every intermediate keeps the (T, 1) column layout."""
    t, c = v.shape
    bids = v * mult
    eligible = act & (bids > reserve)
    masked = jnp.where(eligible, bids, NEG)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, c), 1)
    top = jnp.max(masked, axis=1, keepdims=True)
    winners = jnp.min(jnp.where(masked == top, cols, c), axis=1,
                      keepdims=True)
    sale = top > NEG
    if second_price:
        masked2 = jnp.where(cols == winners, NEG, masked)
        second = jnp.max(masked2, axis=1, keepdims=True)
        prices = jnp.where(sale,
                           jnp.maximum(jnp.where(second > NEG, second,
                                                 reserve), reserve), 0.0)
    else:
        prices = jnp.where(sale, top, 0.0)
    winners = jnp.where(sale, winners, -1)
    onehot = (cols == winners).astype(jnp.float32)
    return winners, prices.astype(jnp.float32), onehot


def _row(col):
    """(T, 1) column -> (1, T) row, through a lane-dense (T, 128) tile (the
    transpose Mosaic supports)."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], 128)))[:1, :]


def _kernel(v_ref, mult_ref, act_ref, reserve_ref,
            winners_ref, prices_ref, sums_ref,
            *, second_price: bool, per_event_mask: bool, n_rows: int,
            block_t: int):
    blk = pl.program_id(0)
    scn = pl.program_id(1)

    @pl.when((blk == 0) & (scn == 0))
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    v = v_ref[...].astype(jnp.float32)                    # (T, C) shared tile
    mult = mult_ref[pl.ds(scn, 1), :]                     # (1, C) scenario s
    act = (act_ref[...].astype(jnp.int32) if per_event_mask
           else act_ref[pl.ds(scn, 1), :]) != 0
    rows = blk * block_t + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_t, 1), 0)
    live = rows < n_rows                                  # (T, 1) real rows
    winners, prices, onehot = resolve_tile(
        v, mult, reserve_ref[scn], act & live, second_price=second_price)

    winners_ref[pl.ds(scn, 1), :] = _row(winners)
    prices_ref[pl.ds(scn, 1), :] = _row(prices)
    sums_ref[pl.ds(scn, 1), :] += jnp.sum(onehot * prices, axis=0,
                                          keepdims=True)  # (1, C)


def sweep_resolve_pallas(
    values: jax.Array,           # (N_pad, C) — shared valuation tile source
    multipliers: jax.Array,      # (S, C) f32
    active: jax.Array,           # (S, C) int32 or (S, N_pad, C) int8
    reserves: jax.Array,         # (S,) f32
    *,
    n_rows: int,                 # true N; rows past it are padding
    second_price: bool = False,
    block_t: int = 256,
    interpret: bool = False,
):
    n, c = values.shape
    s = multipliers.shape[0]
    assert n % block_t == 0, (n, block_t)
    per_event = active.ndim == 3

    grid = (n // block_t, s)     # scenario axis innermost: tile reused S times
    kernel = functools.partial(_kernel, second_price=second_price,
                               per_event_mask=per_event, n_rows=n_rows,
                               block_t=block_t)

    full_sc = pl.BlockSpec((s, c), lambda i, j: (0, 0))
    act_spec = (pl.BlockSpec((None, block_t, c), lambda i, j: (j, i, 0))
                if per_event else full_sc)
    event_rows = pl.BlockSpec((s, block_t), lambda i, j: (0, i))
    winners, prices, sums = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, c), lambda i, j: (i, 0)),  # values tile
            full_sc,                                          # multipliers
            act_spec,                                         # activation
            SMEM,                                             # reserves
        ],
        out_specs=[event_rows, event_rows, full_sc],
        out_shape=[
            jax.ShapeDtypeStruct((s, n), jnp.int32),
            jax.ShapeDtypeStruct((s, n), jnp.float32),
            jax.ShapeDtypeStruct((s, c), jnp.float32),
        ],
        interpret=interpret,
    )(values, multipliers, active, reserves)
    return winners, prices, sums
