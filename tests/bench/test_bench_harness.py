"""The benchmark harness, rehearsed on the CPU at tiny sizes: every cell
runs end to end through its traffic driver, cells and metrics are found
by name from data, and ``bench/run.py`` refuses to measure off the chip."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import harness
from bench_sizes import tiny

ROOT = harness.os.path.dirname(harness.os.path.dirname(
    harness.os.path.abspath(harness.__file__)))
SPEC = harness.load_spec(ROOT)
CELLS = [w["name"] for w in SPEC["workloads"]]


def _run(cell, root=ROOT, overrides=None, seconds=1.0, seed=2**33 + 7):
    return harness.run_cell(root, cell, seed, seconds, False,
                            t_start=time.perf_counter(), require_chip=False,
                            overrides=overrides or tiny(cell),
                            log=lambda msg: None)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_at_tiny_size(cell):
    result = _run(cell)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    want = {m["name"] for m in harness.cell_metrics(SPEC, cell,
                                                    "end_to_end")}
    assert set(result["metrics"]) == want and "setup_s" in want
    for name, m in result["metrics"].items():
        assert m["value"] > 0 and np.isfinite(m["value"]), name
    assert result["device"]["platform"] == "cpu"
    json.dumps(result)


def test_every_cell_reports_setup_an_end_to_end_and_a_layer_metric():
    for cell in CELLS:
        e2e = {m["name"] for m in harness.cell_metrics(SPEC, cell,
                                                       "end_to_end")}
        layer = harness.cell_metrics(SPEC, cell, "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert layer, cell
        for m in layer:
            assert m["moves"] in e2e, (cell, m["name"])
            assert callable(harness.load_reader(ROOT, m["name"]).read), \
                m["name"]


def test_run_py_exits_nonzero_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "paper71.single", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=ROOT)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "not a TPU" in proc.stderr


def test_run_py_fails_without_the_program(tmp_path):
    """A checkout holding only the benchmark's own files has no system
    under test: the run fails and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper71.single",
         "--seed", "1", "--seconds", "1", "--trace", "0"], env=env,
        capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


def test_a_cell_defined_as_data_alone_runs(tmp_path):
    """A later change adds a cell, a traffic mix and a per-layer metric by
    adding files and BENCHMARK.json entries only."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({
        "name": "paper71.grid4", "config": "paper71", "traffic": "grid4",
        "chips": 1, "why": "a 2 x 2 grid"})
    spec["per_layer"].append({
        "name": "sweeps.batch", "unit": "sweeps", "better": "higher",
        "source": "program_counter", "layer": "executor",
        "moves": "sweep_scn_events_per_s", "workloads": ["paper71.grid4"]})
    for m in spec["end_to_end"]:
        if m["name"] == "sweep_scn_events_per_s":
            m["workloads"].append("paper71.grid4")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench" / "traffic" / "grid4.json").write_text(json.dumps(
        {"driver": "sweep", "bid_scales": [1.0, 2.0],
         "reserves": [0.0, 0.2]}))
    (tmp_path / "bench" / "limits" / "paper71.grid4.json").write_text(
        json.dumps({"max_spend_err": 0.08}))
    (tmp_path / "bench" / "metrics" / "sweeps.batch.py").write_text(
        "def read(run):\n    return float(run['obs']['sweeps'])\n")
    overrides = tiny("paper71.grid32")
    result = _run("paper71.grid4", root=str(tmp_path), overrides=overrides)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"sweep_scn_events_per_s", "setup_s"}
    layer = harness.cell_metrics(harness.load_spec(str(tmp_path)),
                                 "paper71.grid4", "per_layer")
    assert "sweeps.batch" in {m["name"] for m in layer}
    reader = harness.load_reader(str(tmp_path), "sweeps.batch")
    assert reader.read({"obs": {"sweeps": 3}}) == 3.0


def test_a_metric_split_by_cells_reads_its_quantity():
    """``rounds.single`` has no file of its own: it is read by
    ``bench/metrics/rounds.py``, and the single cell's throughput
    ``sweep_scn_events_per_s.single`` is the driver's
    ``sweep_scn_events_per_s``."""
    single = harness.load_reader(ROOT, "rounds.single")
    batch = harness.load_reader(ROOT, "rounds.batch")
    assert single.__file__ == batch.__file__
    assert single.__file__.endswith(os.path.join("metrics", "rounds.py"))
    record = {"num_rounds": np.array([3, 7])}
    assert single.read({"obs": {"round_record": record}}) == 7.0
    values = {"sweep_scn_events_per_s": 5.0, "ask_p50_ms": 2.0}
    assert harness.end_to_end_value(
        values, "sweep_scn_events_per_s.single") == 5.0
    assert harness.end_to_end_value(values, "ask_p50_ms") == 2.0
    result = _run("paper71.single")
    assert set(result["metrics"]) == {"sweep_scn_events_per_s.single",
                                      "setup_s"}


def test_service_warmup_leaves_nothing_to_compile_in_the_window():
    """Asks that arrive faster than the service answers fill every batch:
    each admitted count of uncached designs, and so each stacking,
    padding and replay program, has to be warm before the window."""
    overrides = tiny("yahoo72.asks")
    overrides["traffic"]["rate_per_s"] = 100.0
    result = _run("yahoo72.asks", overrides=overrides, seconds=2.0)
    assert result["checks"]["window_compiles"] == {"value": 0.0,
                                                   "limit": 0.0}
    assert result["correct"] is True


def test_a_window_that_compiles_is_not_correct(monkeypatch):
    from bench.drivers import sweep
    real = sweep.window

    def compiling_window(state, seconds):
        obs = real(state, seconds)
        jax.jit(lambda x: x * 3.0 + 1.0)(jax.numpy.ones(7))
        return obs

    monkeypatch.setattr(sweep, "window", compiling_window)
    result = _run("paper71.grid32")
    assert result["checks"]["window_compiles"]["value"] >= 1.0
    assert result["correct"] is False


def test_schedule_gives_every_seed_the_same_work():
    from bench.drivers import service
    traffic = harness.load_json(os.path.join(ROOT, "bench", "traffic",
                                             "asks.json"))
    budgets = np.full(200, 2000.0, np.float32)
    runs = []
    for _ in range(2):
        due, design_of_ask, designs = service.make_schedule(
            20.0, traffic, budgets)
        n = len(due)
        assert n == round(traffic["rate_per_s"] * 20.0)
        assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 20
        pooled = design_of_ask < traffic["pool_size"]
        assert pooled.sum() == round(traffic["pool_share"] * n)
        fresh = design_of_ask[~pooled]
        assert len(set(fresh.tolist())) == len(fresh)
        runs.append((due, design_of_ask, designs))
    (due1, ask1, designs1), (due2, ask2, designs2) = runs
    # the same arrivals and the same asks in the same order: the run's seed
    # draws only the log they are asked of
    np.testing.assert_array_equal(due1, due2)
    np.testing.assert_array_equal(ask1, ask2)
    assert len(set(ask1.tolist())) > traffic["pool_size"]
    for a, b in zip(designs1, designs2):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
