"""jit'd public wrapper for capped_scan (pads N to block, C to lanes)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_interpret
from repro.kernels.capped_scan.capped_scan import capped_scan_pallas


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def capped_scan(
    values: jax.Array,       # (N, C)
    budgets: jax.Array,      # (C,)
    multipliers: jax.Array | None = None,
    reserve: jax.Array = 0.0,
    *,
    block_t: int = 512,
    interpret: bool | None = None,
):
    n, c = values.shape
    if multipliers is None:
        multipliers = jnp.ones((c,), jnp.float32)
    pad_n = (-n) % block_t
    pad_c = (-c) % 128
    v = jnp.pad(values.astype(jnp.float32), ((0, pad_n), (0, pad_c)),
                constant_values=-1.0)          # padded rows/cols never win
    b = jnp.pad(budgets.astype(jnp.float32), (0, pad_c),
                constant_values=jnp.inf)       # padded campaigns never cap
    m = jnp.pad(multipliers.astype(jnp.float32), (0, pad_c))
    winners, prices, spend, cap = capped_scan_pallas(
        v, b, m, jnp.asarray(reserve, jnp.float32), block_t=block_t,
        interpret=use_interpret(interpret))
    cap = jnp.minimum(cap[:c], n + 1)          # padded-N sentinel -> n+1
    return winners[:n], prices[:n], spend[:c], cap
