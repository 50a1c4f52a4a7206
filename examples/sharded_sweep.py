"""Scale a scenario sweep across a device mesh — the docs/SCALING.md worked
example.

Runs the same 8-scenario design grid three ways and proves they agree
bit-for-bit:

* single-device batched Algorithm 2 (``driver="batched"``, the PR-1/2 path);
* events sharded over every visible device (``driver="sharded"``);
* events × scenarios on a 2-D mesh (half the devices shard the event log,
  the other half split the scenario grid), when ≥4 devices are visible.

Real meshes come from real TPUs; in this container (and CI) fake CPU devices
exercise the identical program:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/sharded_sweep.py

With one device it degenerates to the 1×1 mesh — still bit-for-bit, which is
the base case of the contract.
"""
import time

import jax
import numpy as np

from repro.core import CounterfactualEngine
from repro.data import make_synthetic_env
from repro.launch.mesh import SweepMeshSpec


def run(engine, grid, label, **sweep_kwargs):
    t0 = time.perf_counter()
    sweep = engine.sweep(grid, method="parallel", **sweep_kwargs)
    jax.block_until_ready(sweep.results.final_spend)
    dt = time.perf_counter() - t0
    print(f"{label:<34s} {grid.num_scenarios} scenarios in {dt:6.2f}s "
          f"(incl. compile)")
    return sweep


def main(n_events: int = 32_768, n_campaigns: int = 32) -> None:
    n_devices = len(jax.devices())
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=10)
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=[1.0, 0.85, 1.1, 1.25],
                       budget_scales=[1.0, 0.75])
    print(f"N={n_events} events, C={n_campaigns} campaigns, "
          f"S={grid.num_scenarios} scenarios, {n_devices} device(s)\n")

    base = run(engine, grid, "batched (single device)")

    specs = [("sharded, events x{}".format(n_devices),
              SweepMeshSpec.for_devices())]
    if n_devices >= 4:
        specs.append((
            "sharded, events x{} + scenarios x2".format(n_devices // 2),
            SweepMeshSpec.for_devices(num_event_devices=n_devices // 2,
                                      num_scenario_devices=2)))
    for label, spec in specs:
        sweep = run(engine, grid, label, driver="sharded", mesh=spec)
        exact = (np.array_equal(np.asarray(sweep.results.final_spend),
                                np.asarray(base.results.final_spend))
                 and np.array_equal(np.asarray(sweep.results.cap_times),
                                    np.asarray(base.results.cap_times)))
        print(f"{'':<34s} bit-for-bit vs batched: {exact}")
        assert exact, "mesh drivers must be bitwise-identical (SCALING.md)"

    print()
    print(base.format_delta_table())


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
