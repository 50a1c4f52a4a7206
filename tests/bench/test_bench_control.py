"""The control fails the check: the plain reference computed on a
bfloat16 log and bids (spends still in float32), put in the program's
place, at a size a test run can hold, against each cell's own limits.

On the chip the same control was read at the cells' own sizes (PERF.md);
here the sizes are cut so that a CPU runs them, with the §7.1 budgets
scaled with N so that about half the campaigns still cap."""
import time

import jax.numpy as jnp
import pytest

from bench import harness
from bench.reference import replay

ROOT = harness.os.path.dirname(harness.os.path.dirname(
    harness.os.path.abspath(harness.__file__)))

N71 = 65536
SIZES = {
    "paper71.grid32": ("max_uncapped_err", {
        "config": {"n_events": N71, "b_base": 70.0 * N71 / 1e6},
        "traffic": {"bid_scales": [1.0, 2.0], "reserves": [0.0]}}),
    "paper71.single": ("max_uncapped_err", {
        "config": {"n_events": N71, "b_base": 70.0 * N71 / 1e6}}),
    "yahoo72.asks": ("max_median_err", {
        "config": {"n_day1": 8192, "n_day2": 12288,
                   "budget": 2000.0 * 8192 / 100000},
        "traffic": {"rate_per_s": 4.0, "events_per_chunk": 4096,
                    "reference_segment": 4096,
                    "reference_lane_block": 16}}),
}
PROGRAM = ("repro.core.counterfactual", "repro.core.executor",
           "repro.serve.counterfactual")


@pytest.mark.parametrize("cell", sorted(SIZES))
def test_control_in_the_programs_place_is_not_correct(cell, monkeypatch):
    import importlib
    number, overrides = SIZES[cell]
    real = importlib.import_module("repro.core.executor").execute_sweep

    def control(values, budgets, rules, plan, **kwargs):
        out = real(values, budgets, rules, plan, **kwargs)
        spend, _ = replay(values, budgets, rules.multipliers, rules.reserve,
                          dtype="bfloat16")
        return (jnp.asarray(spend[-1]),) + tuple(out[1:])

    for name in PROGRAM:
        monkeypatch.setattr(importlib.import_module(name), "execute_sweep",
                            control)
    result = harness.run_cell(ROOT, cell, 7, 2.0, False,
                              t_start=time.perf_counter(),
                              require_chip=False, overrides=overrides,
                              log=lambda msg: None)
    assert result["correct"] is False, result["checks"]
    check = result["checks"][number]
    assert check["value"] > check["limit"], result["checks"]
