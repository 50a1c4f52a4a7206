"""Measured plan autotuning: tune once, serve every later sweep tuned.

A :class:`~repro.core.executor.SweepPlan`'s performance knobs — Pallas
event tile, event/scenario chunk sizes, host-stream prefetch, retired-lane
predication — are all *bitwise-equivalence* axes: any legal setting
returns the exact same answers (the executor's chunk-equivalence
contracts), so picking them is purely a wall-clock decision. This example
runs the full tuning loop (docs/TUNING.md):

1. ``engine.tune()`` — enumerate the legal knob lattice, rank it with the
   roofline cost model, time the top candidates paired against the
   default plan (``benchmarks.common.time_pair`` interleaved medians),
   and persist the winner in the tuning cache (``TUNING_cache.json`` /
   ``$REPRO_TUNING_CACHE``);
2. ``engine.sweep(grid, tuned=True)`` — the plan resolves through that
   cache with no further measurement;
3. the bitwise assertion: tuned answers equal the default plan's answers
   bit for bit — this is the CI tuning smoke contract.

    PYTHONPATH=src python examples/tuned_sweep.py
"""
import os
import tempfile
import time

import jax
import numpy as np

from repro.core import CounterfactualEngine
from repro.data import make_synthetic_env
from repro.tune import TuningCache


def main(n_events: int = 8192, n_campaigns: int = 16) -> None:
    # keep the example hermetic: the cache lives in a temp dir, not the cwd
    cache_path = os.path.join(tempfile.mkdtemp(prefix="repro_tune_"),
                              "TUNING_cache.json")
    os.environ["REPRO_TUNING_CACHE"] = cache_path
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=10)
    engine = CounterfactualEngine(env.values, env.budgets)
    print(f"N={n_events} events, C={n_campaigns} campaigns, "
          f"backend={jax.default_backend()} x{jax.device_count()}\n")

    # the production grid we want tuned sweeps of; tuning keys on shapes
    # (not designs), so tuning on it covers every same-sized grid
    grid = engine.grid(bid_scales=(1.0, 1.25, 1.5),
                       budget_scales=(1.0, 0.5))

    # 1. one measured tuning pass (tiny trial budget — CI smoke scale)
    t0 = time.perf_counter()
    report = engine.tune(grid, trials=5, quick_trials=2, top_k=3,
                         max_events=4096, cache_path=cache_path)
    print(f"tune() in {time.perf_counter() - t0:.2f}s: "
          f"{report.n_candidates} legal candidates, "
          f"winner ({report.origin}) = {report.winner_config}")
    if report.speedup is not None:
        print(f"paired medians: tuned {report.us_tuned:.0f}us vs default "
              f"{report.us_default:.0f}us ({report.speedup:.2f}x)")
    entry = TuningCache.load(cache_path).get(report.key)
    assert entry is not None and entry["config"] == report.winner_config, \
        "tuning cache did not persist the winner"
    print(f"cache entry [{report.key}] written to {cache_path}\n")

    # 2. + 3. every later same-shape sweep resolves through the cache —
    # and answers bit-for-bit the default plan (the CI smoke assertion)
    ref = engine.sweep(grid)
    tuned = engine.sweep(grid, tuned=True)
    assert np.array_equal(np.asarray(ref.results.final_spend),
                          np.asarray(tuned.results.final_spend)), \
        "tuned sweep diverged from the default plan (final_spend)"
    assert np.array_equal(np.asarray(ref.results.cap_times),
                          np.asarray(tuned.results.cap_times)), \
        "tuned sweep diverged from the default plan (cap_times)"
    rev = np.asarray(tuned.results.revenue)
    print(f"sweep(tuned=True) over {grid.num_scenarios} scenarios: "
          f"bitwise identical to the default plan "
          f"(best {grid.labels[int(rev.argmax())]} = {rev.max():.2f})")
    print("TUNED_SWEEP_OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
