"""Multi-device core algorithms (8 fake CPU devices via subprocess).

The sharded drivers (shard_map + psum over the event axis) must reproduce the
single-process results. Runs in a subprocess because the device count is
fixed at first jax init.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    assert len(jax.devices()) == 8
    from repro.data import make_synthetic_env
    from repro.core import sequential_replay, parallel_simulate, Segments
    from repro.core import sharded as sh

    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=8192,
                             n_campaigns=24, emb_dim=8)
    ref = sequential_replay(env.values, env.budgets, env.rule)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    vals = sh.shard_events(env.values, mesh)

    # Algorithm 2 with mesh-sharded reductions == single-process Algorithm 2,
    # bit-for-bit: the closures reduce on the canonical block grid and this
    # mesh is aligned (shards of 1024 = whole blocks of 256)
    rate_fn, block_fn = sh.make_sharded_kernels(mesh, env.rule)
    par_sh = parallel_simulate(env.values, env.budgets, env.rule,
                               rate_fn=rate_fn(vals), block_fn=block_fn(vals))
    par_1p = parallel_simulate(env.values, env.budgets, env.rule)
    assert np.array_equal(np.asarray(par_sh.final_spend),
                          np.asarray(par_1p.final_spend))
    assert np.array_equal(np.asarray(par_sh.cap_times),
                          np.asarray(par_1p.cap_times))

    # sharded aggregate at oracle caps == oracle
    segs = Segments.from_cap_times(ref.cap_times, env.n_events)
    agg = sh.sharded_aggregate(mesh, vals, segs, env.budgets, env.rule)
    np.testing.assert_allclose(np.asarray(agg.final_spend),
                               np.asarray(ref.final_spend), rtol=1e-3,
                               atol=1e-3)
    assert np.array_equal(np.asarray(agg.cap_times),
                          np.asarray(ref.cap_times))

    # sharded VI converges toward cap fractions
    pi = sh.estimate_pi_sharded(mesh, vals, env.budgets, env.rule,
                                jax.random.PRNGKey(3), num_iters=400,
                                local_batch=16, eta=0.5, eta_decay=0.01)
    frac = np.minimum(np.asarray(ref.cap_times) / env.n_events, 1.0)
    mae = float(np.abs(np.asarray(pi) - frac).mean())
    assert mae < 0.08, mae
    print("SHARDED_OK", mae)

    # mesh-batched scenario sweep == single-device batched loop, bit-for-bit,
    # on an 8-way event mesh AND a 4(event)x2(scenario) mesh
    from repro.core import ScenarioGrid, sweep_state_machine
    from repro.core.sharded import sweep_sharded
    from repro.launch.mesh import SweepMeshSpec
    grid = ScenarioGrid.product(env.rule, env.budgets,
                                bid_scales=[1.0, 0.9, 1.2],
                                budget_scales=[1.0, 0.5])
    sw_ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                                 resolve="jnp")
    for spec in [SweepMeshSpec.for_devices(),
                 SweepMeshSpec.for_devices(num_event_devices=4,
                                           num_scenario_devices=2)]:
        for resolve in ("jnp", "fused"):
            out = sweep_sharded(env.values, grid.budgets, grid.rules, spec,
                                resolve=resolve)
            for name, a, b in zip(("s_hat", "cap", "retired", "bnds", "rnd",
                                   "n_hat"), out, sw_ref):
                assert np.array_equal(np.asarray(a), np.asarray(b)), \\
                    (resolve, spec.event_axes, spec.scenario_axis, name)
    print("SWEEP_SHARDED_OK")
""")


@pytest.mark.slow
def test_sharded_core_on_8_devices():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # fake host devices, never a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout
    assert "SWEEP_SHARDED_OK" in out.stdout
