"""CPU rehearsal of chip_smoke.py: its phases at N=8192, C=16, S=4.

The script itself refuses to run without a TPU; these tests call its phase
functions directly (the device check is main()'s, not the phases'), so a
wrong path, argument or control flow shows up here before any chip time is
spent. On CPU ``resolve="auto"`` picks the jnp back-end, so the kernel is
not in the program and every back-end comparison is bitwise.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.core import CounterfactualEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]
SMALL = dict(n_events=8192, n_campaigns=16, emb_dim=10, b_base=None)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def small(smoke):
    env, setup_s = smoke.make_env(0, **SMALL)
    assert setup_s > 0
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=smoke.BID_SCALES[:2],
                       reserves=smoke.RESERVES[:2])
    assert grid.num_scenarios == 4
    return env, engine, grid


def test_single_chip_phases_rehearse_on_cpu(smoke, small):
    env, engine, grid = small
    sweep = smoke.phase_sweep(engine, grid, require_kernel=False)
    assert not sweep["kernel"]               # CPU: the jnp back-end
    assert sweep["result"].final_spend.shape == (4, SMALL["n_campaigns"])
    assert sweep["scenario_events_per_s"] > 0

    oracle = smoke.phase_oracle(engine, grid, sweep["result"])
    assert len(oracle["errors"]) == 4
    assert max(oracle["errors"]) < smoke.ORACLE_BOUND

    backends = smoke.phase_backends(engine, grid, sweep["result"],
                                    events_per_chunk=env.n_events // 8)
    assert backends["max_spend_diff"] == 0.0
    assert backends["cap_times_differ"] == 0

    asks = smoke.what_ifs(engine.base_rule, env.budgets)
    assert len(asks) == 8
    service = smoke.phase_service(env, engine.base_rule, asks, n_slabs=4)
    assert service["asks"] == 8
    assert service["stats"]["appends"] == 4
    assert service["stats"]["batches"] == 1


def test_main_refuses_cpu_and_prints_no_result(smoke, capsys):
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok": true' not in captured.out
    assert "not a TPU" in captured.err


def test_sharded_phase_rehearses_on_4_cpu_devices():
    """The --chips 4 path on four virtual CPU devices (a child process:
    the device count is fixed when JAX starts)."""
    code = f"""
import importlib.util, json, jax
spec = importlib.util.spec_from_file_location("chip_smoke",
                                              {str(ROOT / 'chip_smoke.py')!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.core import CounterfactualEngine
assert len(jax.devices()) == 4
env, _ = smoke.make_env(0, **{SMALL!r})
grid = CounterfactualEngine(env.values, env.budgets).grid(
    bid_scales=smoke.BID_SCALES[:2], reserves=smoke.RESERVES[:2])
rep = smoke.phase_sharded(env, grid, jax.devices())
print(json.dumps({{"rows": rep["shard_rows"],
                   "devices": rep["shard_devices"]}}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["rows"] == [SMALL["n_events"] // 4] * 4
    assert rep["devices"] == [0, 1, 2, 3]
