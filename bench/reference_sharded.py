"""The benchmark's plain reference on a sharded log: ``bench/reference.py``'s
sequential replay of Eqs. 1-3, carried across the shards in log order.

No single chip holds the log, so each shard's rows are replayed on the chip
that holds them, from the ``(spend, cap)`` carried out of the shard before
it: the same steps ``bench.reference.replay`` takes over the whole log, in
the same order. It imports nothing of the system under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import _replay_segment


@functools.partial(jax.jit, static_argnames=("dtype", "spend_dtype"))
def _replay_shard(carry, rows, n0, budgets, multipliers, reserve, *, dtype,
                  spend_dtype):
    return _replay_segment(carry, rows, n0, budgets.astype(spend_dtype),
                           multipliers, reserve, dtype, spend_dtype)


def shards_in_order(values) -> list:
    """``(first row, rows)`` of each distinct row block of ``values``, in
    log order; ``rows`` is the block as it lies on its device."""
    blocks = {}
    for shard in values.addressable_shards:
        blocks.setdefault(shard.index[0].start or 0, shard.data)
    return sorted(blocks.items(), key=lambda kv: kv[0])


def replay(values, budgets, multipliers, reserve, *, dtype="float32",
           spend_dtype="float32"):
    """Sequential replay of S lanes over a log sharded by rows.

    Returns the final ``(spend, cap)`` as (S, C) host arrays, as the last
    prefix of ``bench.reference.replay``: ``cap`` holds 1-based cap times,
    -1 where the campaign never capped."""
    n_lanes, n_campaigns = np.shape(budgets)
    dtype, spend_dtype = jnp.dtype(dtype).name, jnp.dtype(spend_dtype).name
    carry = (np.zeros((n_lanes, n_campaigns), spend_dtype),
             np.full((n_lanes, n_campaigns), -1, np.int32))
    inputs = tuple(np.asarray(x, np.float32)
                   for x in (budgets, multipliers, reserve))
    with jax.default_matmul_precision("highest"):
        for n0, rows in shards_in_order(values):
            device, = rows.devices()
            put = lambda x: jax.device_put(x, device)
            carry = _replay_shard(put(carry), rows, put(np.int32(n0)),
                                  *map(put, inputs), dtype=dtype,
                                  spend_dtype=spend_dtype)
    spend, cap = carry
    return np.asarray(spend, np.float32), np.asarray(cap)
