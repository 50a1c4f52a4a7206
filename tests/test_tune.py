"""The measured plan autotuner (repro.tune): cache, ranking, bitwiseness.

Three contracts:

* the persistent tuning cache round-trips winners, survives corruption and
  schema drift by degrading to the cost-model fallback, and never becomes
  a correctness dependency;
* the candidate lattice and its roofline ranking are deterministic, legal
  by construction (the executor's own alignment checks), and the VMEM
  table is a *hard* filter — an infeasible configuration never surfaces;
* the tuner only ever moves bitwise-equivalence knobs: a tuned plan's
  outputs equal the default plan's bit-for-bit across every
  placement x resolve cell, at 1 device here and at 4 forced host
  devices in the subprocess half (the same harness pattern as
  tests/test_scenario_sweep.py).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AuctionRule, CounterfactualEngine, ScenarioGrid
from repro.core import executor as ex
from repro.data import make_synthetic_env
from repro.tune import (Candidate, TuningCache, autotune, cache_key,
                        candidate_from_config, default_candidate,
                        enumerate_candidates, rank_candidates, resolve_plan,
                        shape_for)
from repro.tune import space as space_lib

N_EVENTS = 2048
N_CAMPAIGNS = 16


@pytest.fixture(scope="module")
def env():
    return make_synthetic_env(jax.random.PRNGKey(3), n_events=N_EVENTS,
                              n_campaigns=N_CAMPAIGNS, emb_dim=8)


@pytest.fixture(scope="module")
def grid(env):
    base = AuctionRule.first_price(N_CAMPAIGNS)
    return ScenarioGrid.product(base, env.budgets, bid_scales=[1.0, 1.3],
                                budget_scales=[1.0, 0.5])


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets its own cache file; nothing leaks into the cwd."""
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "tune.json"))


def _tuned_plan(**kw):
    return ex.SweepPlan(block_t="auto", tuned=True, **kw)


# ---------------------------------------------------------------------------
# (a) the persistent cache
# ---------------------------------------------------------------------------

def test_cache_round_trip(tmp_path):
    path = tmp_path / "cache.json"
    cache = TuningCache.load(path)
    assert cache.entries == {}
    key = "cpu|d1|N2048|C16|S4|batched|jnp|device"
    cache.put(key, {"block_t": 512, "scenarios_per_chunk": 2},
              us_tuned=10.0, hardware="cpu")
    cache.save()
    back = TuningCache.load(path)
    entry = back.get(key)
    assert entry["config"]["block_t"] == 512
    assert entry["origin"] == "measured"
    assert entry["us_tuned"] == 10.0
    # unknown keys in a cached config (a newer writer) are ignored
    cand = candidate_from_config({"block_t": 512, "new_knob": 7})
    assert cand.block_t == 512


def test_cache_key_buckets_pow2():
    mk = lambda n: space_lib.ProblemShape(n_events=n, n_campaigns=16,
                                          n_scenarios=4)
    # shapes within a factor of two share an entry; across it they don't
    assert cache_key(mk(1500)) == cache_key(mk(2048))
    assert cache_key(mk(2048)) != cache_key(mk(2049))


def test_cache_schema_mismatch_and_corruption_fall_back(tmp_path):
    # wrong schema version: load degrades to an empty view
    versioned = tmp_path / "old.json"
    versioned.write_text(json.dumps(
        {"schema": 999, "entries": {"k": {"config": {"block_t": 1024}}}}))
    assert TuningCache.load(versioned).entries == {}
    # corrupt JSON: same
    corrupt = tmp_path / "corrupt.json"
    corrupt.write_text("{not json")
    assert TuningCache.load(corrupt).entries == {}
    # and resolution still answers (pure cost-model fallback, no raise)
    plan = resolve_plan(_tuned_plan(), n_events=N_EVENTS,
                        n_campaigns=N_CAMPAIGNS, n_scenarios=4,
                        cache=TuningCache.load(corrupt))
    assert not ex.needs_tuning(plan)
    assert isinstance(plan.block_t, int)


def test_cached_winner_is_validated_against_exact_shape(tmp_path):
    """Buckets are coarser than shapes: an entry that is illegal for the
    exact dimensions (spc=3 does not divide S=4) must fall back to the
    cost model instead of shipping a plan the executor would reject."""
    plan = _tuned_plan()
    shape = shape_for(plan, n_events=N_EVENTS, n_campaigns=N_CAMPAIGNS,
                      n_scenarios=4)
    cache = TuningCache.load(tmp_path / "c.json")
    cache.put(cache_key(shape), {"scenarios_per_chunk": 3})
    bad = resolve_plan(plan, n_events=N_EVENTS, n_campaigns=N_CAMPAIGNS,
                       n_scenarios=4, cache=cache)
    assert bad.scenario_chunks is None or \
        bad.scenario_chunks.scenarios_per_chunk != 3
    # a legal entry IS honoured
    cache.put(cache_key(shape), {"scenarios_per_chunk": 2})
    good = resolve_plan(plan, n_events=N_EVENTS, n_campaigns=N_CAMPAIGNS,
                        n_scenarios=4, cache=cache)
    assert good.scenario_chunks.scenarios_per_chunk == 2
    assert good.tuned is False and isinstance(good.block_t, int)


# ---------------------------------------------------------------------------
# (b) the lattice + cost model
# ---------------------------------------------------------------------------

def test_plan_block_t_validation():
    assert ex.SweepPlan(block_t="auto").block_t == "auto"
    for bad in (0, -128, "big", True):
        with pytest.raises(ValueError, match="block_t"):
            ex.SweepPlan(block_t=bad)


def test_lattice_is_legal_deterministic_and_incumbent_first():
    plan = _tuned_plan()
    shape = shape_for(plan, n_events=N_EVENTS, n_campaigns=N_CAMPAIGNS,
                      n_scenarios=8)
    cands = enumerate_candidates(plan, shape)
    assert cands[0] == default_candidate(plan)
    assert len(cands) == len(set(cands)) > 1
    for c in cands:
        assert space_lib.is_legal(c, plan, shape)
        # legal by construction == the executor's own checks accept them
        if c.events_per_chunk is not None:
            ex.check_chunks(ex.ChunkSpec(c.events_per_chunk),
                            n_events=shape.n_events,
                            local_n=shape.n_events)
        if c.scenarios_per_chunk is not None:
            ex.check_scenario_chunks(
                ex.ScenarioChunkSpec(c.scenarios_per_chunk),
                n_scenarios=shape.n_scenarios,
                local_s=shape.n_scenarios)
    # ranking is deterministic (ties break on the knob tuple)
    r1 = rank_candidates(plan, shape)
    r2 = rank_candidates(plan, shape)
    assert [c for c, _ in r1] == [c for c, _ in r2]
    assert all(a[1].total <= b[1].total for a, b in zip(r1, r1[1:]))


def test_pinned_knobs_are_never_overridden():
    """An explicit chunk size is a stated contract (service append
    alignment rides on it): tuned=True must not move it."""
    plan = ex.SweepPlan(chunks=ex.ChunkSpec(512),
                        scenario_chunks=ex.ScenarioChunkSpec(2),
                        block_t=128, tuned=True)
    shape = shape_for(plan, n_events=N_EVENTS, n_campaigns=N_CAMPAIGNS,
                      n_scenarios=4)
    for c in enumerate_candidates(plan, shape):
        resolved = c.apply(plan)
        assert resolved.chunks.events_per_chunk == 512
        assert resolved.scenario_chunks.scenarios_per_chunk == 2
        assert resolved.block_t == 128


def test_vmem_infeasible_candidates_never_surface():
    """docs/ALGORITHMS.md: S=64 lanes at C=1024 overflow the one-launch
    VMEM budget (round_fused_fits says no) — the lattice must not offer
    any such explicit configuration, and is_legal must reject it."""
    plan = _tuned_plan(resolve="fused", interpret=True)
    shape = space_lib.ProblemShape(
        n_events=4096, n_campaigns=1024, n_scenarios=64,
        resolve="fused")
    assert not ex.round_fused_fits(64, 1024)
    bad = Candidate(block_t=256, scenarios_per_chunk=64)
    assert not space_lib.vmem_feasible(bad, plan, shape)
    assert not space_lib.is_legal(bad, plan, shape)
    for c in enumerate_candidates(plan, shape):
        assert space_lib.vmem_feasible(c, plan, shape)
        if c.scenarios_per_chunk is not None:
            assert ex.round_fused_fits(c.scenarios_per_chunk, 1024,
                                       c.block_t)


# ---------------------------------------------------------------------------
# (c) tuned == default, bit for bit
# ---------------------------------------------------------------------------

def _outputs(values, budgets, rules, plan):
    return ex.execute_sweep(values, budgets, rules, plan)


@pytest.mark.parametrize("kind", ["first_price", "second_price"])
@pytest.mark.parametrize("placement", ["device", "batched", "sharded"])
@pytest.mark.parametrize("resolve", ["jnp", "fused"])
def test_tuned_plan_is_bitwise_default(env, grid, placement, resolve, kind):
    """Resolution through cache + cost model moves only bitwise-equivalence
    knobs: every output of the tuned plan equals the default plan's
    exactly, for each placement x resolve x auction-kind cell (fused off
    TPU runs its interpret-mode kernel so block_t actually reaches a grid;
    sharded here runs the shard_map program on however many devices this
    process has — the 4-device half is the subprocess test below)."""
    from repro.launch.mesh import SweepMeshSpec
    interpret = True if resolve == "fused" else None
    mesh = (SweepMeshSpec.for_devices(
        num_event_devices=jax.device_count())
        if placement == "sharded" else None)
    if placement == "device":
        budgets, rules = grid.budgets[1], AuctionRule(
            multipliers=grid.rules.multipliers[1],
            reserve=jnp.asarray(grid.rules.reserve, jnp.float32)[1],
            kind=kind)
    else:
        budgets, rules = grid.budgets, AuctionRule(
            multipliers=grid.rules.multipliers, reserve=grid.rules.reserve,
            kind=kind)
    base_plan = ex.SweepPlan(placement=placement, resolve=resolve,
                             interpret=interpret, mesh=mesh)
    tuned_plan = ex.SweepPlan(placement=placement, resolve=resolve,
                              interpret=interpret, mesh=mesh,
                              block_t="auto", tuned=True)
    ref = _outputs(env.values, budgets, rules, base_plan)
    out = _outputs(env.values, budgets, rules, tuned_plan)
    for name, a, b in zip(("final_spend", "cap_times", "retired",
                           "boundaries", "num_rounds", "n_hat"), out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{placement}/{resolve} "
                                              f"{name}")


def test_tuned_plan_bitwise_through_measured_cache(env, grid, tmp_path):
    """The full loop: autotune measures (tiny budget), persists a winner,
    and a later tuned sweep resolves THROUGH that cache entry to the same
    bits as the default plan."""
    plan = _tuned_plan()
    report = autotune(env.values, grid.budgets, grid.rules, plan,
                      trials=2, quick_trials=1, top_k=2, max_events=512)
    assert report.origin == "measured"
    assert report.n_candidates > 1
    assert Path(report.cache_path).exists()
    # the persisted entry is the one resolution consults
    cache = TuningCache.load(report.cache_path)
    assert cache.get(report.key)["config"] == report.winner_config
    resolved = resolve_plan(plan, n_events=N_EVENTS,
                            n_campaigns=N_CAMPAIGNS,
                            n_scenarios=grid.budgets.shape[0], cache=cache)
    assert resolved == report.plan(plan)
    ref = _outputs(env.values, grid.budgets, grid.rules, ex.SweepPlan())
    out = _outputs(env.values, grid.budgets, grid.rules, resolved)
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_tune_then_tuned_sweep(env, grid, tmp_path, monkeypatch):
    """engine.tune() fills the cache; engine.sweep(tuned=True) serves
    through it, bit-for-bit the untuned sweep."""
    cache_path = tmp_path / "engine.json"
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(cache_path))
    engine = CounterfactualEngine(env.values, env.budgets)
    report = engine.tune(trials=2, quick_trials=1, top_k=2, max_events=512,
                         cache_path=cache_path)
    assert report.speedup is None or report.speedup >= 1.0
    assert cache_path.exists()
    ref = engine.sweep(grid)
    out = engine.sweep(grid, tuned=True)
    auto = engine.sweep(grid, block_t="auto")
    for r in (out, auto):
        np.testing.assert_array_equal(
            np.asarray(r.results.final_spend),
            np.asarray(ref.results.final_spend))
        np.testing.assert_array_equal(
            np.asarray(r.results.cap_times),
            np.asarray(ref.results.cap_times))


def test_service_tuned_passthrough_and_tune(env, tmp_path, monkeypatch):
    """A tuned=True service answers bitwise an untuned one; service.tune()
    pins the measured winner without changing any answer; host stores
    direct callers to the ctor flag instead."""
    from repro.serve.counterfactual import CounterfactualService
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "svc.json"))
    ref = CounterfactualService(env.budgets, events_per_chunk=256)
    ref.append(env.values)
    want = ref.ask().result()
    tuned = CounterfactualService(env.budgets, events_per_chunk=256,
                                  tuned=True)
    tuned.append(env.values)
    got = tuned.ask().result()
    np.testing.assert_array_equal(got.final_spend, want.final_spend)
    np.testing.assert_array_equal(got.cap_times, want.cap_times)
    report = tuned.tune(scenarios=2, trials=2, quick_trials=1, top_k=2,
                        max_events=512)
    assert not ex.needs_tuning(tuned.plan)      # winner pinned
    assert tuned.plan == report.plan(
        ex.SweepPlan(block_t="auto", tuned=True))
    got2 = tuned.ask(budgets=env.budgets * 0.5).result()
    want2 = ref.ask(budgets=env.budgets * 0.5).result()
    np.testing.assert_array_equal(got2.final_spend, want2.final_spend)
    host = CounterfactualService(env.budgets, events_per_chunk=256,
                                 store="host")
    host.append(np.asarray(env.values))
    with pytest.raises(ValueError, match="tuned=True"):
        host.tune()


def test_resumable_and_s2a_normalise_tuned_plans(env, grid):
    """Fold windows and the sort2aggregate spine run the untuned default
    (the tuner models full parallel sweeps only) — a tuned plan must not
    change their bits either."""
    plan = _tuned_plan()
    carry = ex.initial_carry(grid.budgets.shape[0], N_CAMPAIGNS)
    out, _ = ex.execute_sweep_resumable(env.values, grid.budgets,
                                        grid.rules, plan, carry=carry)
    ref, _ = ex.execute_sweep_resumable(env.values, grid.budgets,
                                        grid.rules, ex.SweepPlan(),
                                        carry=ex.initial_carry(
                                            grid.budgets.shape[0],
                                            N_CAMPAIGNS))
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))


@pytest.mark.skipif("CI_SUBPROCESS" in os.environ,
                    reason="no nested subprocess runs")
def test_tuned_sharded_bitwise_4dev():
    """The forced-4-host-device half: engine.tune(driver='sharded') then
    engine.sweep(driver='sharded', tuned=True) — bitwise the default
    sharded sweep AND the single-device reference."""
    script = textwrap.dedent("""
        import os, numpy as np, jax, jax.numpy as jnp
        assert jax.device_count() == 4, jax.device_count()
        from repro.core import AuctionRule, CounterfactualEngine, \\
            ScenarioGrid
        from repro.data import make_synthetic_env
        from repro.launch.mesh import SweepMeshSpec
        env = make_synthetic_env(jax.random.PRNGKey(3), n_events=2048,
                                 n_campaigns=16, emb_dim=8)
        base = AuctionRule.first_price(16)
        grid = ScenarioGrid.product(base, env.budgets,
                                    bid_scales=[1.0, 1.3],
                                    budget_scales=[1.0, 0.5])
        mesh = SweepMeshSpec.for_devices(num_event_devices=4)
        engine = CounterfactualEngine(env.values, env.budgets)
        rep = engine.tune(driver="sharded", mesh=mesh, trials=2,
                          quick_trials=1, top_k=2, max_events=1024)
        assert rep.origin == "measured", rep.origin
        ref = engine.sweep(grid)
        for resolve in ("jnp", "fused"):
            out = engine.sweep(grid, driver="sharded", mesh=mesh,
                               resolve=resolve, tuned=True)
            base_out = engine.sweep(grid, driver="sharded", mesh=mesh,
                                    resolve=resolve)
            for r in (out, base_out):
                assert np.array_equal(
                    np.asarray(r.results.final_spend),
                    np.asarray(ref.results.final_spend)), resolve
                assert np.array_equal(
                    np.asarray(r.results.cap_times),
                    np.asarray(ref.results.cap_times)), resolve
        print("TUNED_SHARDED_4DEV_OK")
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # fake host devices, never a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    env["CI_SUBPROCESS"] = "1"
    env["REPRO_TUNING_CACHE"] = str(
        Path(env.get("TMPDIR", "/tmp")) / "tune_4dev.json")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TUNED_SHARDED_4DEV_OK" in out.stdout
