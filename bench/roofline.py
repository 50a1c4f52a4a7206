"""The least work of an Algorithm-2 sweep, counted from the sweep's own
round record, whatever implements the round.

Each round needs the spend rate of the remaining log ``[n_hat, N)`` under
the lane's active set, and the exact spends of its block ``[n_hat,
n_next)``, a part of the same rows. So the least a round must read is the
log's rows from the earliest ``n_hat`` of any lane still alive to the end,
once, at the published campaign count (not padded), in float32. The sum
over the rounds of the slowest lane is the sweep's least HBM traffic.

The round is elementwise and reduction work on the VPU, whose rate has no
published v5e peak, so the bound used is HBM bandwidth alone. Should an
implementation ever reuse rows across rounds, its share could pass 100%:
then this count is too high and must be corrected, not clamped.
"""
from __future__ import annotations

import numpy as np


def sweep_log_rows(num_rounds, boundaries, n_events: int) -> int:
    """Rows a sweep must read: ``num_rounds`` (S,) and ``boundaries``
    (S, C+2) as ``execute_sweep`` returns them (``boundaries[s, j]`` is
    lane ``s``'s block start in round ``j``)."""
    num_rounds = np.asarray(num_rounds)
    boundaries = np.asarray(boundaries)
    rows = 0
    for j in range(int(num_rounds.max(initial=0))):
        alive = num_rounds > j
        rows += n_events - int(boundaries[alive, j].min())
    return rows


def sweep_log_bytes(num_rounds, boundaries, n_events: int,
                    n_campaigns: int, itemsize: int = 4) -> int:
    return sweep_log_rows(num_rounds, boundaries, n_events) \
        * n_campaigns * itemsize
