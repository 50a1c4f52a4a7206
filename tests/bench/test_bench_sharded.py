"""The four-chip cell ``bigday-x4.grid8``, rehearsed on the CPU at a tiny
size: its generator, its reference and its driver on a mesh of 4 fake CPU
devices (in a subprocess, since the device count is fixed at start-up),
and its readers on hand-made runs."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench_sizes
from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "bigday-x4.grid8"

# 4 x 8192 events over 4 shards of whole canonical blocks (1,024 rows),
# with budgets scaled with N as the configuration scales them
TINY_X4 = {"config": {"n_events": 4 * 8192, "n_campaigns": 16,
                      "b_base": 24.0, "shards": 4}}

# the harness's rehearsal of every cell (test_bench_harness.py) finds a
# cell's tiny size in bench_sizes.TINY; it runs in a process with one CPU
# device, where this cell's mesh is that one device
bench_sizes.TINY.setdefault(
    CELL, {"config": dict(TINY_X4["config"], shards=1)})

SCRIPT = r"""
import json, sys, time
root, tiny = sys.argv[1], json.loads(sys.argv[2])
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from bench import harness, reference, reference_sharded
from bench.gen import synthetic, synthetic_sharded
from repro.launch.mesh import SweepMeshSpec
assert len(jax.devices()) == 4
out = {}

cfg = dict(harness.load_cell(root, "bigday-x4.grid8")[2], **tiny["config"])
spec = SweepMeshSpec.for_devices(4)
key = harness.key_for(2**33 + 5)
sharded = synthetic_sharded.make(key, cfg, spec.mesh)
whole = synthetic.make(key, cfg)
values = sharded["values"]
out["sharding"] = values.sharding == NamedSharding(spec.mesh, P("data", None))
out["shard_rows"] = sorted({s.data.shape[0] for s in values.addressable_shards})
out["log_bitwise"] = bool(np.array_equal(np.asarray(values),
                                         np.asarray(whole["values"])))
out["budgets_bitwise"] = bool(np.array_equal(np.asarray(sharded["budgets"]),
                                             np.asarray(whole["budgets"])))

rng = np.random.default_rng(3)
n_lanes = 3
budgets = np.tile(np.asarray(whole["budgets"]), (n_lanes, 1))
mult = rng.uniform(0.8, 1.5, budgets.shape).astype(np.float32)
res = np.array([0.0, 0.05, 0.2], np.float32)
spend, cap = reference_sharded.replay(values, budgets, mult, res)
spends, caps = reference.replay(whole["values"], budgets, mult, res)
out["reference_bitwise"] = bool(np.array_equal(spend, spends[-1])
                                and np.array_equal(cap, caps[-1]))
out["capped"] = int((cap > 0).sum())

result = harness.run_cell(root, "bigday-x4.grid8", 2**33 + 7, 1.0, False,
                          t_start=time.perf_counter(), require_chip=False,
                          overrides=tiny, log=lambda msg: None)
out["result"] = result
print("RESULT " + json.dumps(out))
"""


def _tiny():
    out = {k: dict(v) for k, v in TINY_X4.items()}
    out["limits"] = dict(bench_sizes.TINY_LIMITS)
    return out


@pytest.fixture(scope="module")
def on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, ROOT, json.dumps(_tiny())], env=env,
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_generator_shards_the_log_with_the_one_device_bits(on_four_devices):
    out = on_four_devices
    assert out["sharding"] and out["shard_rows"] == [8192]
    assert out["log_bitwise"] and out["budgets_bitwise"]


def test_sharded_reference_is_the_whole_log_replay(on_four_devices):
    """Carried across shards in log order, the replay's spends and cap
    times are bitwise ``bench.reference.replay``'s over the whole log."""
    assert on_four_devices["reference_bitwise"]
    assert on_four_devices["capped"] > 0


def test_cell_runs_on_four_devices(on_four_devices):
    result = on_four_devices["result"]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["checks"]["sweeps_differ"] == {"value": 0.0, "limit": 0.0}
    assert result["checks"]["window_compiles"]["value"] == 0.0
    assert set(result["metrics"]) == {"sweep_scn_events_per_s", "setup_s"}
    assert result["device"]["count"] == 4


def test_cell_lists_its_metrics():
    spec = harness.load_spec(ROOT)
    e2e = {m["name"] for m in harness.cell_metrics(spec, CELL, "end_to_end")}
    assert e2e == {"sweep_scn_events_per_s", "setup_s"}
    layer = {m["name"] for m in harness.cell_metrics(spec, CELL,
                                                     "per_layer")}
    assert layer == {"rounds.x4", "round_ms.x4", "device_idle_pct.x4",
                     "sweep_partials_roofline.x4",
                     "sweep_partials_share_pct.x4",
                     "collective_share_pct.x4", "shards_live_pct.x4"}
    cell, _ = harness.find_cell(spec, CELL)
    assert cell["chips"] == 4


# readers, on hand-made runs: 2 sweeps of N=400 over 4 shards of 100 rows;
# lane 0 runs rounds from rows 0, 150, 350; lane 1 from 0, 250
RECORD = {"num_rounds": np.array([3, 2]),
          "boundaries": np.array([[0, 150, 350, 400],
                                  [0, 250, 400, 400]])}
OBS = {"round_record": RECORD, "sweeps": 2, "n_events": 400,
       "n_campaigns": 100, "shards": 4}
DEVICE = {"kind": "TPU v5 lite"}


def _trace(**kernel_s):
    return {"busy_s": 2.0, "window_s": 2.5, "devices": 4,
            "kernel_s": kernel_s}


def _read(metric, trace, obs=OBS):
    reader = harness.load_reader(ROOT, metric)
    return reader.read({"obs": obs, "trace": trace, "device": DEVICE,
                        "cell": {"chips": 4}})


def test_sweep_partials_readers():
    # rows from the alive frontier: 400 + 250 + 50 over the 3 rounds
    least = 2 * 700 * 100 * 4 / (4 * 819e9)
    trace = _trace(sweep_partials=1.5, collectives=0.1)
    assert _read("sweep_partials_roofline.x4", trace) == pytest.approx(
        100 * least / 1.5)
    assert _read("sweep_partials_share_pct.x4", trace) == pytest.approx(75.0)
    assert _read("collective_share_pct.x4", trace) == pytest.approx(5.0)


@pytest.mark.parametrize("metric", ["sweep_partials_roofline.x4",
                                    "sweep_partials_share_pct.x4",
                                    "collective_share_pct.x4"])
def test_trace_readers_are_silent_without_their_kernel(metric):
    """A one-chip trace: the one-launch round, no collective."""
    assert _read(metric, _trace(round_fused=1.9)) is None
    assert _read(metric, None) is None


def test_shards_live_reader():
    # frontiers 0, 150, 350: 4, 3 and 1 of the 4 shards hold live rows
    assert _read("shards_live_pct.x4", None) == pytest.approx(
        100 * 8 / 12)
    assert _read("shards_live_pct.x4", None,
                 dict(OBS, shards=None)) is None


def test_readers_find_the_collectives_by_their_instruction_names():
    from bench.trace import _is_kernel
    names = harness.load_reader(ROOT, "collective_share_pct.x4") \
        .KERNELS["collectives"]
    for op in ("psum.18", "all-reduce.3", "all-reduce-start.1"):
        assert _is_kernel(op, names), op
    for op in ("sweep_partials.1", "fusion.3", "psum_done"):
        assert not _is_kernel(op, names), op
