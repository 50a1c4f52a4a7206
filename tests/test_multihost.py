"""Multi-host sweep placement (``placement="multihost"``).

Two layers of coverage:

* in-process wiring tests — ``SweepMeshSpec.for_processes()`` degenerates
  to the local-device mesh under one process, so the multihost placement
  must be bit-for-bit the sharded and batched sweeps on whatever devices
  are visible (4 in the forced-host CI step, 1 otherwise);
* a real ``jax.distributed`` smoke test — two OS processes × two fake CPU
  devices each (gloo collectives), every process holding only its
  contiguous half of the event log, asserting final_spend / cap_times are
  bitwise identical to the single-process run of the full log. Runs in
  subprocesses because both the device count and the distributed runtime
  are fixed at first jax init.
"""
import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _env():
    from repro.data import make_synthetic_env
    return make_synthetic_env(jax.random.PRNGKey(3), n_events=512,
                              n_campaigns=8, emb_dim=6)


def _grid(env):
    from repro.core import ScenarioGrid
    return ScenarioGrid.product(env.rule, env.budgets,
                                bid_scales=[1.0, 1.2],
                                budget_scales=[1.0, 0.6])


def test_multihost_single_process_bitwise():
    """Under one process, placement='multihost' == sharded == batched,
    bit-for-bit (the wiring contract the 2-process test extends)."""
    from repro.core import SweepPlan, execute_sweep
    from repro.launch.mesh import SweepMeshSpec
    env, grid = _env(), _grid(_env())
    spec = SweepMeshSpec.for_processes()
    assert not spec.is_multiprocess
    ref = execute_sweep(env.values, grid.budgets, grid.rules,
                        SweepPlan(placement="batched"))
    sh = execute_sweep(env.values, grid.budgets, grid.rules,
                       SweepPlan(placement="sharded",
                                 mesh=SweepMeshSpec.for_devices()))
    mh = execute_sweep(env.values, grid.budgets, grid.rules,
                       SweepPlan(placement="multihost", mesh=spec))
    for name, a, b, c in zip(("final_spend", "cap_times", "retired",
                              "boundaries", "num_rounds", "n_hat"),
                             mh, sh, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"vs sharded: {name}")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c),
                                      err_msg=f"vs batched: {name}")


def test_multihost_engine_and_rejections():
    from repro.core import CounterfactualEngine, SweepPlan, execute_sweep
    from repro.launch.mesh import SweepMeshSpec
    env, grid = _env(), _grid(_env())
    eng = CounterfactualEngine(env.values, env.budgets, env.rule)
    ref = eng.sweep(eng.grid(bid_scales=(1.0, 1.2)))
    out = eng.sweep(eng.grid(bid_scales=(1.0, 1.2)), driver="multihost",
                    mesh=SweepMeshSpec.for_processes())
    np.testing.assert_array_equal(np.asarray(out.results.final_spend),
                                  np.asarray(ref.results.final_spend))
    np.testing.assert_array_equal(np.asarray(out.results.cap_times),
                                  np.asarray(ref.results.cap_times))
    # a multihost plan without a mesh fails at construction
    with pytest.raises(ValueError, match="mesh"):
        SweepPlan(placement="multihost")
    # scenario-axis process meshes are not supported
    if len(jax.devices()) >= 2:
        spec = SweepMeshSpec.for_devices(len(jax.devices()) // 2, 2)
        with pytest.raises(ValueError, match="scenario"):
            execute_sweep(env.values, grid.budgets, grid.rules,
                          SweepPlan(placement="multihost", mesh=spec))


_WORKER = textwrap.dedent("""
    import os
    rank = int(os.environ["MH_RANK"])
    from repro.compat import distributed_initialize
    distributed_initialize(os.environ["MH_COORD"], 2, rank)
    import jax, jax.numpy as jnp, numpy as np
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 4, len(jax.devices())
    from repro.data import make_synthetic_env
    from repro.core import (ScenarioGrid, SweepPlan, execute_sweep,
                            sweep_state_machine)
    from repro.launch.mesh import SweepMeshSpec

    env = make_synthetic_env(jax.random.PRNGKey(3), n_events=1024,
                             n_campaigns=8, emb_dim=6)
    grid = ScenarioGrid.product(env.rule, env.budgets,
                                bid_scales=[1.0, 1.2],
                                budget_scales=[1.0, 0.6])
    # single-process reference on this process's local default device
    ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="jnp")
    spec = SweepMeshSpec.for_processes()
    assert spec.is_multiprocess
    # each process holds ONLY its contiguous half of the global log
    half = env.n_events // 2
    local = env.values[rank * half:(rank + 1) * half]
    out = execute_sweep(local, grid.budgets, grid.rules,
                        SweepPlan(placement="multihost", mesh=spec,
                                  resolve="jnp"))
    for name, a, b in zip(("final_spend", "cap_times", "retired",
                           "boundaries", "num_rounds", "n_hat"), out, ref):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    print("MULTIHOST_OK", rank)
""")


@pytest.mark.slow
def test_two_process_multihost_matches_single_process():
    """2 jax.distributed processes × 2 fake CPU devices each: the sweep of
    a log whose halves live on different processes is bitwise the
    single-process run of the full log."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # fake host devices, never a chip
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["PYTHONPATH"] = SRC
        env["MH_RANK"] = str(rank)
        env["MH_COORD"] = coord
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = [p.communicate(timeout=900) for p in procs]
    for rank, (p, (stdout, stderr)) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank}: {stderr[-3000:]}"
        assert f"MULTIHOST_OK {rank}" in stdout, stdout
