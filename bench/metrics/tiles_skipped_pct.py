"""tiles_skipped_pct (kernels), read as ``tiles_skipped_pct.<part>`` in the
sweep cells: the share of the fused kernels' (phase, tile, lane) grid
steps that do no tile work, in percent, over the rounds of the sweep's
round record (``execute_sweep``'s ``num_rounds`` and ``boundaries``).

Each round runs two passes over every tile for every lane: the rate pass
(``round_fused``'s phase 0, or the first ``sweep_partials``) and the block
pass (phase 1, or the second). A step does tile work where its lane is
alive in the round and its tile's rows, clipped at their canonical
block's end, meet the lane's window: ``[n_hat, N)`` in the rate pass,
``[n_hat, n_next)`` in the block pass, within the chip's own rows where
the log is sharded by rows; the four-chip cell sums the steps of every
chip. The tiles are ``ops.block_tiles``' over all lanes in one launch:
``REDUCE_BLOCKS`` canonical blocks of ``ceil(N / REDUCE_BLOCKS)`` rows,
each cut into tiles of ``BLOCK_T`` rows, or of the block rounded up to 8
rows where blocks are smaller.

Silent where no ``executor.sweep`` span of the traced window carries
``tile_skip="window"``: there the kernels run every step of an alive lane,
or the program runs no kernel (``bench/program_spans.py``)."""
import numpy as np

from bench import program_spans

REDUCE_BLOCKS = 32      # repro.core.segments.REDUCE_BLOCKS
BLOCK_T = 256           # engine.sweep's block_t


def slice_tiles(offset: int, n_rows: int, block_size: int, t: int):
    """Global ``(start, end)`` rows of the block-relative tiles of the
    slice ``[offset, offset + n_rows)``, in order, each clipped at its
    block's end."""
    tiles_per_block = -(-block_size // t)
    n_blocks = -(-(offset % block_size + n_rows) // block_size)
    block = (offset // block_size + np.arange(n_blocks))[:, None]
    start = block * block_size + np.arange(tiles_per_block)[None, :] * t
    end = np.minimum(start + t, (block + 1) * block_size)
    return start.ravel(), end.ravel()


def tiles_met(start, end, lo, hi):
    """For each window ``[lo, hi)`` (arrays), the number of tiles whose
    rows meet it. Tile starts and ends both rise with the tile index."""
    before_hi = np.searchsorted(start, hi, side="left")
    ended = np.searchsorted(end, lo, side="right")
    return np.where(lo < hi, np.maximum(before_hi - ended, 0), 0)


def skipped_pct(num_rounds, boundaries, n_events: int, shards: int = 1):
    """The share of grid steps with no tile work, in percent, for a round
    record on ``shards`` chips that hold ``n_events / shards`` rows each."""
    num_rounds = np.asarray(num_rounds)
    boundaries = np.asarray(boundaries)
    block_size = -(-n_events // REDUCE_BLOCKS)
    t = min(BLOCK_T, -(-block_size // 8) * 8)
    local = n_events // shards
    live = steps = 0
    for k in range(shards):
        first, stop = k * local, (k + 1) * local
        start, end = slice_tiles(first, local, block_size, t)
        for j in range(int(num_rounds.max(initial=0))):
            alive = num_rounds > j
            lo = np.maximum(boundaries[alive, j], first)
            for hi in (n_events, boundaries[alive, j + 1]):
                live += int(tiles_met(start, end, lo,
                                      np.minimum(hi, stop)).sum())
            steps += 2 * len(start) * len(num_rounds)
    return 100.0 * (1.0 - live / steps) if steps else None


def read(run):
    sweeps = program_spans.named(program_spans.window_records(),
                                 "executor.sweep")
    if not any(r.attrs.get("tile_skip") == "window" for r in sweeps):
        return None
    obs = run["obs"]
    record = obs.get("round_record")
    if record is None:
        return None
    return skipped_pct(record["num_rounds"], record["boundaries"],
                       obs["n_events"], obs.get("shards", 1))
