"""Hypothesis property tests on the system's invariants."""
import functools

import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st
import hypothesis.extra.numpy as hnp

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (AuctionRule, Segments, auction, capped_sum,
                        sequential_replay, spend_sums)

settings.register_profile("ci", deadline=None, max_examples=25,
                          derandomize=True)
settings.load_profile("ci")

values_strat = hnp.arrays(
    np.float32, st.tuples(st.integers(4, 64), st.integers(2, 12)),
    elements=st.floats(0.0, 1.0, width=32))


@given(values_strat, st.integers(0, 2**31 - 1))
def test_spend_sums_permutation_invariant(vals, seed):
    """The MapReduce 'reduce' is order-free (the paper's core enabling fact)."""
    c = vals.shape[1]
    rule = AuctionRule.first_price(c)
    w, p = auction.resolve(jnp.asarray(vals), jnp.ones((c,), bool), rule)
    s1 = spend_sums(w, p, c)
    perm = np.random.default_rng(seed).permutation(vals.shape[0])
    w2, p2 = auction.resolve(jnp.asarray(vals[perm]), jnp.ones((c,), bool),
                             rule)
    s2 = spend_sums(w2, p2, c)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5,
                               atol=1e-6)


@given(values_strat)
def test_infinite_budgets_match_uncapped_sum(vals):
    c = vals.shape[1]
    rule = AuctionRule.first_price(c)
    res = sequential_replay(jnp.asarray(vals),
                            jnp.full((c,), jnp.inf), rule)
    w, p = auction.resolve(jnp.asarray(vals), jnp.ones((c,), bool), rule)
    np.testing.assert_allclose(np.asarray(res.final_spend),
                               np.asarray(spend_sums(w, p, c)), rtol=1e-4,
                               atol=1e-5)


@given(values_strat, st.integers(0, 11), st.floats(0.05, 0.5))
def test_budget_monotonicity(vals, c_idx, base):
    """Raising one campaign's budget never decreases its own final spend
    (the lattice/monotonicity property the paper's Step-2 argument needs)."""
    n, c = vals.shape
    c_idx = c_idx % c
    rule = AuctionRule.first_price(c)
    budgets = jnp.full((c,), base, jnp.float32)
    lo = sequential_replay(jnp.asarray(vals), budgets, rule)
    hi = sequential_replay(jnp.asarray(vals),
                           budgets.at[c_idx].mul(4.0), rule)
    assert float(hi.final_spend[c_idx]) >= float(lo.final_spend[c_idx]) - 1e-5


@given(st.integers(1, 200), st.floats(0.1, 50.0))
def test_capped_sum_bounds(n, budget):
    xs = jnp.linspace(0.0, 1.0, n)
    out = float(capped_sum(xs, budget))
    assert out <= budget + 1e-6
    assert out <= float(xs.sum()) + 1e-6
    assert out == min(budget, float(xs.sum())) or abs(
        out - min(budget, float(xs.sum()))) < 1e-4


_SWEEP_N, _SWEEP_C = 512, 8        # canonical reduce block = 512/32 = 16


@functools.lru_cache(maxsize=1)
def _sweep_env():
    from repro.data import make_synthetic_env
    return make_synthetic_env(jax.random.PRNGKey(3), n_events=_SWEEP_N,
                              n_campaigns=_SWEEP_C, emb_dim=6)


@given(st.sampled_from([16, 32, 64, 128, 256, 512]),
       st.floats(0.7, 1.4), st.floats(0.2, 2.0))
def test_chunked_sweep_bitwise_any_aligned_chunk(epc, bid, bud):
    """Event-chunked streaming is bit-for-bit the in-memory batched sweep
    for EVERY aligned chunk size (multiples of the canonical reduce block
    dividing N), across random scenario designs — the executor-layer
    analogue of the mesh-invariance property."""
    from repro.core import ScenarioGrid, sweep_state_machine
    env = _sweep_env()
    grid = ScenarioGrid.product(AuctionRule.first_price(_SWEEP_C),
                                env.budgets, bid_scales=[1.0, bid],
                                budget_scales=[1.0, bud])
    ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="jnp")
    out = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="jnp", chunks=epc)
    for name, a, b in zip(("final_spend", "cap_times", "retired",
                           "boundaries", "num_rounds", "n_hat"), out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"chunks={epc}: {name}")


@given(st.sampled_from([16, 32, 64, 128, 256, 512]),
       st.sampled_from([1, 3, 4]),
       st.sampled_from(["jnp", "fused"]),
       st.sampled_from(["device", "batched"]),
       st.booleans(),
       st.floats(0.7, 1.4), st.floats(0.2, 2.0))
def test_host_streamed_sweep_bitwise_any_aligned_chunk(
        epc, n_slabs, resolve, placement, prefetch, bid, bud):
    """Host-streamed execution is bit-for-bit the device-resident sweep
    for EVERY aligned chunk size × placement × resolve back-end × pipeline
    mode (double-buffered and synchronous per-chunk puts), with the log
    split across arbitrary (even ragged) host slab boundaries — the
    memory-unbounded analogue of the event-chunk invariance property."""
    from repro.core import ScenarioGrid, SweepPlan, execute_sweep
    from repro.core.executor import ChunkSpec, HostStream
    env = _sweep_env()
    grid = ScenarioGrid.product(AuctionRule.first_price(_SWEEP_C),
                                env.budgets, bid_scales=[1.0, bid],
                                budget_scales=[1.0, bud])
    interpret = True if resolve == "fused" else None
    spec = ChunkSpec(epc, source="host", prefetch=prefetch)
    stream = HostStream(
        [np.asarray(s) for s in np.array_split(np.asarray(env.values),
                                               n_slabs)])
    label = f"epc={epc} slabs={n_slabs} {resolve}/{placement} " \
            f"prefetch={prefetch}"
    if placement == "device":
        # one unbatched lane
        rule1, budgets1 = grid.scenario(1)
        args = (budgets1, rule1)
    else:
        args = (grid.budgets, grid.rules)
    ref = execute_sweep(env.values, *args,
                        SweepPlan(placement=placement, resolve=resolve,
                                  interpret=interpret))
    out = execute_sweep(stream, *args,
                        SweepPlan(placement=placement, resolve=resolve,
                                  interpret=interpret, chunks=spec))
    for name, a, b in zip(("final_spend", "cap_times", "retired",
                           "boundaries", "num_rounds", "n_hat"), out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{label}: {name}")


@given(st.sampled_from([1, 2, 4]),
       st.sampled_from([None, 16, 64, 128]),
       st.sampled_from(["jnp", "fused"]),
       st.sampled_from(["device", "batched", "sharded"]),
       st.floats(0.7, 1.4), st.floats(0.2, 2.0))
def test_scenario_chunked_sweep_bitwise_any_aligned_chunk(
        spc, epc, resolve, placement, bid, bud):
    """Scenario-chunked execution is bit-for-bit the unchunked program on
    final_spend/cap_times for EVERY aligned chunk size, across placements
    (device / batched / sharded — the latter over however many devices are
    visible, 4 in the forced-host CI step), resolve back-ends jnp / fused,
    and composed with aligned event chunks — the S-axis analogue of the
    event-chunk invariance property above."""
    from repro.core import (ScenarioGrid, SweepPlan, execute_sweep,
                            sweep_parallel)
    from repro.launch.mesh import SweepMeshSpec
    env = _sweep_env()
    grid = ScenarioGrid.product(AuctionRule.first_price(_SWEEP_C),
                                env.budgets, bid_scales=[1.0, bid],
                                budget_scales=[1.0, bud])
    interpret = True if resolve == "fused" else None
    ref = sweep_parallel(env.values, grid.budgets, grid.rules,
                         resolve="jnp")
    label = f"spc={spc} epc={epc} {resolve}/{placement}"
    if placement == "device":
        # one unbatched lane: only the trivial chunk divides S=1
        rule1, budgets1 = grid.scenario(1)
        plan = SweepPlan(placement="device", resolve=resolve,
                         interpret=interpret, chunks=epc, scenario_chunks=1)
        s_hat, cap_times, *_ = execute_sweep(env.values, budgets1, rule1,
                                             plan)
        np.testing.assert_array_equal(
            np.asarray(s_hat), np.asarray(ref.final_spend[1]), err_msg=label)
        np.testing.assert_array_equal(
            np.asarray(cap_times), np.asarray(ref.cap_times[1]),
            err_msg=label)
        return
    kwargs = dict(resolve=resolve, interpret=interpret, chunks=epc,
                  scenario_chunks=spc)
    if placement == "sharded":
        n_dev = len(jax.devices())
        if n_dev >= 4 and spc <= 2:
            # event x scenario mesh: per-device lanes = S/2 = 2
            kwargs["mesh"] = SweepMeshSpec.for_devices(n_dev // 2, 2)
        else:
            kwargs["mesh"] = SweepMeshSpec.for_devices()
        kwargs["driver"] = "sharded"
    out = sweep_parallel(env.values, grid.budgets, grid.rules, **kwargs)
    np.testing.assert_array_equal(np.asarray(out.final_spend),
                                  np.asarray(ref.final_spend),
                                  err_msg=label)
    np.testing.assert_array_equal(np.asarray(out.cap_times),
                                  np.asarray(ref.cap_times), err_msg=label)


@given(st.floats(0.05, 0.5), st.floats(0.5, 0.95),
       st.sampled_from([None, 64, 128]),
       st.sampled_from(["batched", "sharded"]))
def test_crn_overlay_sweep_bitwise_any_layout(sigma, prob, epc, placement):
    """The CRN contract at the executor layer: a stochastic overlay family
    (bid noise + participation jitter) is bitwise invariant across event
    chunks, scenario chunks, and sharding — noise draws depend only on the
    global (event, campaign) cell, never on the execution layout. Runs the
    mesh over however many devices are visible (4 in the forced-host CI
    step)."""
    from repro.core import CounterfactualEngine
    from repro.launch.mesh import SweepMeshSpec
    from repro.scenarios import (BidNoise, ParticipationJitter,
                                 PauseCampaign, compile_family)
    env = _sweep_env()
    eng = CounterfactualEngine(env.values, env.budgets,
                               AuctionRule.first_price(_SWEEP_C))
    fam = compile_family(
        env.values, env.budgets, eng.base_rule,
        [BidNoise(sigma), [ParticipationJitter(prob), PauseCampaign(2)],
         [BidNoise(sigma), ParticipationJitter(prob)]],
        key=jax.random.PRNGKey(5))
    ref = eng.sweep(fam)
    kwargs = dict(chunks=epc, scenario_chunks=2)
    if placement == "sharded":
        kwargs.update(driver="sharded", mesh=SweepMeshSpec.for_devices())
    out = eng.sweep(fam, **kwargs)
    label = f"sigma={sigma} prob={prob} epc={epc} {placement}"
    np.testing.assert_array_equal(np.asarray(out.results.final_spend),
                                  np.asarray(ref.results.final_spend),
                                  err_msg=label)
    np.testing.assert_array_equal(np.asarray(out.results.cap_times),
                                  np.asarray(ref.results.cap_times),
                                  err_msg=label)
    # the paused lane's campaign is exactly off, noise or not
    assert np.asarray(out.results.final_spend)[2, 2] == 0.0


@given(st.lists(st.integers(1, 100), min_size=1, max_size=8),
       st.integers(50, 200))
def test_segments_from_cap_times_invariants(caps, n):
    caps = jnp.asarray([min(c, n + 1) for c in caps], jnp.int32)
    segs = Segments.from_cap_times(caps, n)
    b = np.asarray(segs.boundaries)
    assert b[0] == 0 and b[-1] == n
    assert (np.diff(b) >= 0).all()
    # activation monotone: each campaign's mask is non-increasing across segs
    m = np.asarray(segs.masks).astype(int)
    assert (np.diff(m, axis=0) <= 0).all()
    # event->segment mapping consistent with boundaries
    sid = np.asarray(segs.seg_ids(n))
    for j, s in enumerate(sid):
        assert b[s] <= j < max(b[s + 1], b[s] + 1) or b[s] == b[s + 1]
