"""Checkpoints of the sweep's carried burnout state: a save/restore
round-trip, a resumed fold bitwise the straight one, and the async writer."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.core import ScenarioGrid
from repro.core.executor import (SweepPlan, execute_sweep_resumable,
                                 initial_carry)
from repro.data import make_synthetic_env

_N, _C, _SLABS = 1024, 8, 4


@pytest.fixture()
def small_setup(tmp_path):
    """A two-lane design grid over a synthetic log cut into four slabs, and
    ``fold(carry, i)``: the carry after folding slab ``i`` into it."""
    env = make_synthetic_env(jax.random.PRNGKey(7), n_events=_N,
                             n_campaigns=_C, emb_dim=6)
    grid = ScenarioGrid.product(env.rule, env.budgets,
                                bid_scales=[1.0, 1.2])
    slabs = np.split(np.asarray(env.values), _SLABS)
    plan = SweepPlan(placement="batched", resolve="jnp")

    def fold(carry, i):
        _, carry = execute_sweep_resumable(slabs[i], grid.budgets,
                                           grid.rules, plan, carry=carry)
        return carry

    return grid, fold, tmp_path


def _assert_same(a, b):
    assert a.n_events_seen == b.n_events_seen
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_roundtrip(small_setup):
    grid, fold, tmp = small_setup
    carry = fold(None, 0)
    save_checkpoint(tmp / "ckpt", 1, carry)
    restored, manifest = restore_checkpoint(tmp / "ckpt", carry)
    assert manifest["step"] == 1
    _assert_same(restored, carry)


def test_resume_is_bit_identical(small_setup):
    """fold(4 slabs) == fold(2) -> checkpoint -> restore -> fold(2)."""
    grid, fold, tmp = small_setup
    straight = None
    for i in range(_SLABS):
        straight = fold(straight, i)

    carry = None
    for i in range(2):
        carry = fold(carry, i)
    save_checkpoint(tmp / "ck2", 2, carry,
                    extra={"n_events_seen": carry.n_events_seen})
    # restore into a fresh carry's structure: nothing of the live one is used
    like = initial_carry(grid.num_scenarios, _C)
    resumed, man = restore_checkpoint(tmp / "ck2", like)
    resumed = dataclasses.replace(
        resumed, n_events_seen=man["extra"]["n_events_seen"])
    for i in range(man["step"], _SLABS):
        resumed = fold(resumed, i)
    _assert_same(resumed, straight)


def test_async_checkpointer(small_setup):
    grid, fold, tmp = small_setup
    carry = fold(None, 0)
    ck = AsyncCheckpointer(tmp / "async", keep=2)
    for i in (1, 2, 3):
        ck.save(i, carry, extra={"i": i})
    ck.wait()
    assert latest_step(tmp / "async") == 3
    # retention
    steps = sorted(p.name for p in (tmp / "async").glob("step_*"))
    assert len(steps) == 2
    ck.close()
