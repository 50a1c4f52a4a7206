"""Batch what-if sweeps: ``CounterfactualEngine.sweep`` over a design grid,
back to back.

Mix parameters: ``bid_scales`` and ``reserves`` (the grid is their product
around the base design, the first combination being the base).

The window runs sweeps back to back from its start; the last sweep started
inside the window is finished and counted. The throughput is all the
scenario·events of those sweeps over the time from the window's start to
the last sweep's completion.

The check compares the window's last sweep, every lane, with the plain
reference's sequential replay (the numbers of
``bench.reference.error_numbers`` that the cell's limits name), and
requires every sweep of the window, and the post-window round-record call,
to be bitwise that sweep (``sweeps_differ``)."""
from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import compared, error_numbers, replay


def setup(ctx):
    from repro.core import CounterfactualEngine
    from repro.core.counterfactual import ScenarioGrid
    gen = importlib.import_module(f"bench.gen.{ctx.config['generator']}")
    with ctx.spans("setup.inputs"):
        data = gen.make(ctx.key, ctx.config)
        jax.block_until_ready(data["values"])
    engine = CounterfactualEngine(data["values"], data["budgets"])
    grid = engine.grid(bid_scales=tuple(ctx.traffic["bid_scales"]),
                       reserves=tuple(ctx.traffic["reserves"]))
    # the window's program at the window's shapes; unbounded budgets end
    # every lane after one round, so warming costs one round, not a sweep
    warm_grid = ScenarioGrid(rules=grid.rules, labels=grid.labels,
                             budgets=jnp.full_like(grid.budgets, jnp.inf))
    with ctx.spans("setup.warmup"):
        jax.block_until_ready(engine.sweep(warm_grid).results.final_spend)
    return {"ctx": ctx, "data": data, "engine": engine, "grid": grid}


def window(state, seconds):
    engine, grid, spans = state["engine"], state["grid"], state["ctx"].spans
    outputs, ends = [], []
    t0 = time.perf_counter()
    with spans("bench.window"):
        while time.perf_counter() - t0 < seconds:
            with spans("engine.sweep"):
                result = engine.sweep(grid)
            with spans("block_until_ready"):
                jax.block_until_ready(result.results.final_spend)
            ends.append(time.perf_counter())
            outputs.append(result.results.final_spend)
    elapsed = ends[-1] - t0
    n_events, _ = engine.values.shape
    work = len(ends) * grid.num_scenarios * n_events
    return {"end_to_end": {"sweep_scn_events_per_s": work / elapsed},
            "attempted": len(ends), "failed": 0, "sweeps": len(ends),
            "elapsed_s": elapsed, "outputs": outputs,
            "n_events": n_events, "n_campaigns": engine.n_campaigns,
            "n_scenarios": grid.num_scenarios,
            "note": f"{len(ends)} sweeps in {elapsed!r} s"}


def finish(state, obs):
    """The round record of the window's program: one ``execute_sweep``
    call with the plan ``engine.sweep`` runs (the same compiled
    program)."""
    from repro.core.executor import execute_sweep, plan_for_driver
    grid = state["grid"]
    s_hat, _, _, boundaries, num_rounds, _ = execute_sweep(
        state["engine"].values, grid.budgets, grid.rules,
        plan_for_driver("batched"))
    obs["round_record"] = {"num_rounds": np.asarray(num_rounds),
                           "boundaries": np.asarray(boundaries)}
    obs["record_spend"] = np.asarray(s_hat)


def reference_spend(state, **precision):
    """The reference replay of every lane of the grid (``precision``: the
    reference's ``dtype`` / ``spend_dtype``)."""
    grid = state["grid"]
    spend, _ = replay(state["data"]["values"], np.asarray(grid.budgets),
                      np.asarray(grid.rules.multipliers),
                      np.asarray(grid.rules.reserve), **precision)
    return spend[-1]


def check(state, obs, limits):
    outputs = [np.asarray(x) for x in obs.pop("outputs")]
    last = outputs[-1]
    differ = sum(not np.array_equal(x, last) for x in outputs[:-1]) \
        + (not np.array_equal(obs["record_spend"], last))
    del state["engine"]                # the program's state is freed
    numbers = error_numbers(last, reference_spend(state),
                            np.asarray(state["grid"].budgets))
    return compared(numbers, limits) + [("sweeps_differ", float(differ),
                                         0.0)]
