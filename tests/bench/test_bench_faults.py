"""The correctness check fails a run whose timed path is broken.

Each test skips the harness's look for a chip and drives the rest of a
run at a tiny size, with the program broken underneath, and sees
``correct`` come out false: a sweep that returns its state unchanged,
half of the scenario batch left out (the other half's answers repeated in
its place), and an answer altered where it is produced. The cells run on
one chip, so there is no exchange between chips to leave out."""
import time

import jax.numpy as jnp
import pytest

from bench import harness
from bench_sizes import tiny

ROOT = harness.os.path.dirname(harness.os.path.dirname(
    harness.os.path.abspath(harness.__file__)))


def _unchanged(out):
    s_hat, cap, *rest = out
    return (jnp.zeros_like(s_hat), jnp.full_like(cap, cap.max()), *rest)


def _half_batch(out):
    s_hat, cap, *rest = out
    half = max(1, s_hat.shape[0] // 2)
    keep = lambda x: jnp.concatenate(
        [x[:half]] * (-(-x.shape[0] // half)))[:x.shape[0]]
    if s_hat.shape[0] == 1:     # one lane: leave out half of its log
        return (s_hat * 0.5, cap, *rest)
    return (keep(s_hat), keep(cap), *rest)


def _altered(out):
    s_hat, cap, *rest = out
    return (s_hat.at[0].multiply(0.5), cap, *rest)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered}
PATCH = {"paper71.grid32": "repro.core.counterfactual.execute_sweep",
         "paper71.single": "repro.core.counterfactual.execute_sweep",
         "yahoo72.asks": "repro.serve.counterfactual.execute_sweep"}


def _run(cell):
    return harness.run_cell(ROOT, cell, 11, 1.0, False,
                            t_start=time.perf_counter(), require_chip=False,
                            overrides=tiny(cell), log=lambda msg: None)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(PATCH))
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    import importlib
    module_name, attr = PATCH[cell].rsplit(".", 1)
    module = importlib.import_module(module_name)
    real = getattr(module, attr)
    monkeypatch.setattr(module, attr,
                        lambda *a, **k: FAULTS[fault](real(*a, **k)))
    result = _run(cell)
    assert result["correct"] is False, result["checks"]
    # the comparison with the reference catches it by itself
    check = result["checks"]["max_spend_err"]
    assert check["value"] > check["limit"], result["checks"]
