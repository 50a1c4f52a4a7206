"""Batch what-if sweeps over a log that no single chip holds:
``CounterfactualEngine.sweep(grid, driver="sharded", mesh=...)``, back to
back, on a log made already sharded over the configuration's ``shards``
chips (one ``data`` mesh axis) and never gathered.

Mix parameters: those of ``bench.drivers.sweep``. The window is that
driver's window, the sweeps run on the mesh; the check compares the last
sweep with the plain reference replayed shard by shard
(``bench/reference_sharded.py``), and requires every sweep of the window,
and the post-window round-record call, to be bitwise that sweep
(``sweeps_differ``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference_sharded
from bench.drivers import sweep
from bench.gen import synthetic_sharded
from bench.reference import compared, error_numbers


class _OnMesh:
    """The engine as ``bench.drivers.sweep.window`` calls it, its sweeps on
    the mesh."""

    def __init__(self, engine, spec):
        self.engine, self.spec = engine, spec
        self.values, self.n_campaigns = engine.values, engine.n_campaigns

    def sweep(self, grid):
        return self.engine.sweep(grid, driver="sharded", mesh=self.spec)


def setup(ctx):
    from repro.core import CounterfactualEngine
    from repro.core.counterfactual import ScenarioGrid
    from repro.launch.mesh import SweepMeshSpec
    spec = SweepMeshSpec.for_devices(int(ctx.config["shards"]))
    with ctx.spans("setup.inputs"):
        data = synthetic_sharded.make(ctx.key, ctx.config, spec.mesh)
        jax.block_until_ready(data["values"])
    engine = CounterfactualEngine(data["values"], data["budgets"])
    grid = engine.grid(bid_scales=tuple(ctx.traffic["bid_scales"]),
                       reserves=tuple(ctx.traffic["reserves"]))
    engine = _OnMesh(engine, spec)
    # unbounded budgets end every lane after one round: the window's
    # program, warmed for the cost of one round
    warm_grid = ScenarioGrid(rules=grid.rules, labels=grid.labels,
                             budgets=jnp.full_like(grid.budgets, jnp.inf))
    with ctx.spans("setup.warmup"):
        jax.block_until_ready(engine.sweep(warm_grid).results.final_spend)
    return {"ctx": ctx, "data": data, "engine": engine, "grid": grid,
            "spec": spec}


def window(state, seconds):
    obs = sweep.window(state, seconds)
    obs["shards"] = state["spec"].event_device_count
    return obs


def finish(state, obs):
    """The round record of the window's program: one ``execute_sweep``
    call with the plan ``engine.sweep(driver="sharded")`` runs."""
    from repro.core.executor import execute_sweep, plan_for_driver
    grid = state["grid"]
    s_hat, _, _, boundaries, num_rounds, _ = execute_sweep(
        state["data"]["values"], grid.budgets, grid.rules,
        plan_for_driver("sharded", mesh=state["spec"]))
    obs["round_record"] = {"num_rounds": np.asarray(num_rounds),
                           "boundaries": np.asarray(boundaries)}
    obs["record_spend"] = np.asarray(s_hat)


def reference_spend(state, **precision):
    """The reference replay of every lane of the grid, shard by shard
    (``precision``: the reference's ``dtype`` / ``spend_dtype``)."""
    grid = state["grid"]
    spend, _ = reference_sharded.replay(
        state["data"]["values"], np.asarray(grid.budgets),
        np.asarray(grid.rules.multipliers), np.asarray(grid.rules.reserve),
        **precision)
    return spend


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
