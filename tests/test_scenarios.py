"""Metamorphic + golden tests for the scenario-family layer.

Three contracts lock :mod:`repro.scenarios` to the executor:

* **CRN metamorphics** — identical intervention specs produce bitwise
  identical lanes; adding scenarios to a family never changes other lanes'
  bits; results are invariant to scenario ordering and to every
  event/scenario chunk schedule (draws depend only on global (event,
  campaign) identity, never on lane index or execution layout).
* **Null identity** — a null intervention (full windows, sigma 0, prob 1)
  is bitwise the overlay-free base program under every placement × resolve
  × chunking combination, even on the per-event eligibility path.
* **Goldens** — a hand-computed 3-campaign / 8-event log where pausing and
  boosting reroute known auctions to known runners-up, including the
  Algorithm-2 capped case (predicted rate-based cap boundary vs the exact
  sequential crossing), and Shapley attribution satisfying the efficiency
  axiom exactly on a dyadic 2-axis grid.
"""
import functools

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (AuctionRule, CounterfactualEngine, ScenarioOverlay,
                        SweepPlan, execute_sweep, sequential_replay,
                        sweep_parallel, vi)
from repro.launch.mesh import SweepMeshSpec
from repro.scenarios import (AddEntrant, BidNoise, BoostCampaign,
                             BudgetPacing, MultiplierJitter,
                             ParticipationJitter, PauseCampaign, ScaleBids,
                             ScaleBudgets, SetReserve, compile_family,
                             shapley_values)

N, C = 512, 8


@functools.lru_cache(maxsize=1)
def _env():
    from repro.data import make_synthetic_env
    return make_synthetic_env(jax.random.PRNGKey(3), n_events=N,
                              n_campaigns=C, emb_dim=6)


def _engine():
    env = _env()
    return CounterfactualEngine(env.values, env.budgets,
                                AuctionRule.first_price(C))


def _spends_caps(swept):
    return (np.asarray(swept.results.final_spend),
            np.asarray(swept.results.cap_times))


# ---------------------------------------------------------------------------
# golden log: 3 campaigns x 8 events, all values dyadic (exact in float32)
# ---------------------------------------------------------------------------

GOLDEN_ROWS = [
    [.5, .75, .25], [.25, .5, .125], [.75, .25, .5], [.125, .75, .25],
    [.5, .25, .75], [.25, .5, .75], [.5, .25, .25], [.25, .5, .25],
]
# first price, reserve 0, budgets 10 (no caps): every event's winner and
# price are hand-readable off the rows; ties go to the lowest index.
GOLDEN_BASE_SPEND = [1.25, 2.5, 1.5]          # revenue 5.25
GOLDEN_PAUSE1_SPEND = [2.25, 0.0, 1.75]       # c1's 4 wins reroute; rev 4.0
GOLDEN_BOOST2_SPEND = [0.5, 2.5, 4.0]         # c2 x2 takes e2/e4/e5; rev 7.0
GOLDEN_PAUSE1_BOOST2_SPEND = [1.25, 0.0, 5.0]  # composed; revenue 6.25


def _golden_engine():
    values = jnp.asarray(GOLDEN_ROWS, jnp.float32)
    budgets = jnp.full((3,), 10.0, jnp.float32)
    return CounterfactualEngine(values, budgets, AuctionRule.first_price(3))


def test_golden_pause_reroutes_known_auctions():
    """Pausing c1 hands e0/e1 to c0, e3/e7 to the runner-up column —
    hand-computed final spends, exact in float (dyadic values)."""
    eng = _golden_engine()
    fam = compile_family(eng.values, eng.budgets, eng.base_rule,
                         [PauseCampaign(1)])
    swept = eng.sweep(fam)
    spend, caps = _spends_caps(swept)
    np.testing.assert_array_equal(spend[0], np.float32(GOLDEN_BASE_SPEND))
    np.testing.assert_array_equal(spend[1], np.float32(GOLDEN_PAUSE1_SPEND))
    assert float(swept.results.revenue[0]) == 5.25
    assert float(swept.results.revenue[1]) == 4.0
    # paused campaign: no spend, never caps
    assert spend[1, 1] == 0.0 and caps[1, 1] == 9


def test_golden_boost_and_composition():
    eng = _golden_engine()
    fam = compile_family(
        eng.values, eng.budgets, eng.base_rule,
        [BoostCampaign(2, 2.0), [PauseCampaign(1), BoostCampaign(2, 2.0)]])
    spend, _ = _spends_caps(eng.sweep(fam))
    np.testing.assert_array_equal(spend[1], np.float32(GOLDEN_BOOST2_SPEND))
    np.testing.assert_array_equal(spend[2],
                                  np.float32(GOLDEN_PAUSE1_BOOST2_SPEND))


def test_golden_capped_algorithm2_semantics():
    """With c1's budget at 1.0, the oracle caps c1 at event 2 (cumulative
    .75 + .5 crosses 1.0); Algorithm 2 predicts the cap from the round's
    spend *rate* (8 x 1.0/2.5 -> event 4). Final spends coincide exactly —
    the divergence is only in the predicted boundary, which is the
    documented Algorithm-2 contract, not a bug."""
    eng = _golden_engine()
    budgets = jnp.asarray([10.0, 1.0, 10.0], jnp.float32)
    fam = compile_family(eng.values, budgets, eng.base_rule, [])
    swept = CounterfactualEngine(eng.values, budgets,
                                 eng.base_rule).sweep(fam)
    spend, caps = _spends_caps(swept)
    oracle = sequential_replay(eng.values, budgets, eng.base_rule)
    np.testing.assert_array_equal(spend[0], np.float32([1.5, 1.25, 1.75]))
    np.testing.assert_array_equal(spend[0], np.asarray(oracle.final_spend))
    np.testing.assert_array_equal(caps[0], [9, 4, 9])
    np.testing.assert_array_equal(np.asarray(oracle.cap_times), [9, 2, 9])


def test_golden_entrant_takes_every_auction():
    """An entrant bidding 1.0 everywhere outbids every dyadic row: its lane
    spends 8.0 and every incumbent drops to 0; the base lane is untouched
    (the entrant's column exists but its window is empty)."""
    eng = _golden_engine()
    fam = compile_family(
        eng.values, eng.budgets, eng.base_rule,
        [AddEntrant(budget=10.0, values=np.ones(8, np.float32),
                    slot="newco")])
    assert fam.values.shape == (8, 4)
    spend, _ = _spends_caps(eng.sweep(fam))
    np.testing.assert_array_equal(spend[0],
                                  np.float32(GOLDEN_BASE_SPEND + [0.0]))
    np.testing.assert_array_equal(spend[1], np.float32([0, 0, 0, 8.0]))


def test_golden_shapley_efficiency_exact():
    """2-axis dyadic grid: phi_pause = -1.0, phi_boost = +2.0, summing
    EXACTLY (not approximately) to the total delta 6.25 - 5.25 = 1.0."""
    eng = _golden_engine()
    att = eng.attribute({"pause1": PauseCampaign(1),
                         "boost2": BoostCampaign(2, 2.0)})
    assert att.phi == {"pause1": -1.0, "boost2": 2.0}
    assert att.base_value == 5.25 and att.total_value == 6.25
    assert att.total_delta == 1.0
    assert att.efficiency_gap == 0.0
    assert "pause1" in att.format_table()


def test_shapley_values_unit():
    sv = shapley_values(("a", "b"), {frozenset(): 5.25,
                                     frozenset({"a"}): 4.0,
                                     frozenset({"b"}): 7.0,
                                     frozenset({"a", "b"}): 6.25})
    assert sv == {"a": -1.0, "b": 2.0}
    with pytest.raises(ValueError, match="missing"):
        shapley_values(("a", "b"), {frozenset(): 1.0})


def test_shapley_three_axes_efficiency():
    """3-axis attribution on the synthetic environment: 2^3 lattice swept
    in one program, efficiency within one float rounding."""
    eng = _engine()
    att = eng.attribute(
        {"boost": BoostCampaign(2, 1.5), "pause": PauseCampaign(5),
         "reserve": SetReserve(0.1)},
        key=jax.random.PRNGKey(11))
    assert len(att.subset_values) == 8
    assert att.efficiency_gap <= 1e-6 * max(1.0, abs(att.total_delta))


# ---------------------------------------------------------------------------
# null-intervention identity: bitwise the base program everywhere
# ---------------------------------------------------------------------------

def _null_overlay(s, c, key):
    """A null overlay that still exercises the per-event eligibility path:
    full windows, sigma 0, prob 1, time_varying=True."""
    return ScenarioOverlay(
        live_start=jnp.zeros((s, c), jnp.int32),
        live_stop=jnp.full((s, c), N, jnp.int32),
        bid_sigma=jnp.zeros((s, c), jnp.float32),
        part_prob=jnp.ones((s, c), jnp.float32),
        key=key, time_varying=True)


@pytest.mark.parametrize("kind", ["first_price", "second_price"])
@pytest.mark.parametrize("resolve", ["jnp", "fused"])
@pytest.mark.parametrize("placement", ["device", "batched", "sharded"])
@pytest.mark.parametrize("chunking", [(None, None), (64, 1)])
def test_null_overlay_bitwise_base(placement, resolve, chunking, kind):
    env = _env()
    epc, spc = chunking
    key = jax.random.PRNGKey(17)
    budgets = jnp.stack([env.budgets, env.budgets * 0.4])
    rules = AuctionRule(multipliers=jnp.ones((2, C), jnp.float32),
                       reserve=jnp.full((2,), 0.05, jnp.float32),
                       kind=kind)
    if placement == "device":
        # one unbatched lane; the overlay's fields are (C,) rows — this is
        # the executor's device-placement expansion path
        plan = SweepPlan(placement="device", resolve=resolve, chunks=epc,
                         scenario_chunks=spc)
        rule1 = AuctionRule(multipliers=rules.multipliers[1],
                            reserve=rules.reserve[1], kind=rules.kind)
        row = ScenarioOverlay(
            live_start=jnp.zeros((C,), jnp.int32),
            live_stop=jnp.full((C,), N, jnp.int32),
            bid_sigma=jnp.zeros((C,), jnp.float32),
            part_prob=jnp.ones((C,), jnp.float32),
            key=key, time_varying=True)
        ref = execute_sweep(env.values, budgets[1], rule1, plan)
        out = execute_sweep(env.values, budgets[1], rule1, plan, overlay=row)
        for name, a, b in zip(("final_spend", "cap_times"), out[:2],
                              ref[:2]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        return
    kwargs = dict(resolve=resolve, chunks=epc, scenario_chunks=spc)
    if placement == "sharded":
        kwargs.update(driver="sharded", mesh=SweepMeshSpec.for_devices())
    ref = sweep_parallel(env.values, budgets, rules, **kwargs)
    out = sweep_parallel(env.values, budgets, rules,
                         overlay=_null_overlay(2, C, key), **kwargs)
    np.testing.assert_array_equal(np.asarray(out.final_spend),
                                  np.asarray(ref.final_spend))
    np.testing.assert_array_equal(np.asarray(out.cap_times),
                                  np.asarray(ref.cap_times))


def test_null_interventions_compile_overlay_free():
    """Identity interventions (ScaleBids(1), full-log pacing) fold away at
    compile time and the lane is bitwise the base lane."""
    eng = _engine()
    fam = compile_family(
        eng.values, eng.budgets, eng.base_rule,
        [[ScaleBids(1.0), ScaleBudgets(1.0), BudgetPacing(3, 0, None)]])
    assert fam.overlay is None
    spend, caps = _spends_caps(eng.sweep(fam))
    np.testing.assert_array_equal(spend[1], spend[0])
    np.testing.assert_array_equal(caps[1], caps[0])


def test_zero_sigma_stochastic_lane_bitwise_base():
    """A family that is all-identity interventions folds to overlay=None at
    compile time; a sibling noisy lane forces the whole family onto the
    per-event CRN path, where the sigma=0 / prob=1 lane still must not move
    a single bit vs the base lane."""
    eng = _engine()
    folded = compile_family(
        eng.values, eng.budgets, eng.base_rule,
        [[BidNoise(0.0), ParticipationJitter(1.0)]],
        key=jax.random.PRNGKey(23))
    assert folded.overlay is None
    fam = compile_family(
        eng.values, eng.budgets, eng.base_rule,
        [[BidNoise(0.0), ParticipationJitter(1.0)], BidNoise(0.4)],
        key=jax.random.PRNGKey(23))
    assert fam.overlay is not None and fam.overlay.per_event
    spend, caps = _spends_caps(eng.sweep(fam))
    np.testing.assert_array_equal(spend[1], spend[0])
    np.testing.assert_array_equal(caps[1], caps[0])
    assert not np.array_equal(spend[2], spend[0])


def test_per_event_overlay_rejects_kernel_resolve():
    eng = _engine()
    fam = compile_family(eng.values, eng.budgets, eng.base_rule,
                         [BidNoise(0.3)], key=jax.random.PRNGKey(2))
    with pytest.raises(ValueError, match="jnp resolve path"):
        eng.sweep(fam, resolve="pallas")


def test_overlay_family_rejects_s2a():
    eng = _engine()
    fam = compile_family(eng.values, eng.budgets, eng.base_rule,
                         [PauseCampaign(0)])
    with pytest.raises(ValueError, match="parallel"):
        eng.sweep(fam, method="sort2aggregate")


def test_static_pause_overlay_runs_on_pallas_bitwise():
    """Empty-or-full windows fold into the activation mask, so the kernel
    back-ends stay eligible and bit-identical to jnp."""
    eng = _engine()
    fam = compile_family(eng.values, eng.budgets, eng.base_rule,
                         [PauseCampaign(2)])
    assert fam.overlay is not None and not fam.overlay.per_event
    ref = eng.sweep(fam, resolve="jnp")
    out = eng.sweep(fam, resolve="pallas")
    np.testing.assert_array_equal(*map(np.asarray, (out.results.final_spend,
                                                    ref.results.final_spend)))
    np.testing.assert_array_equal(*map(np.asarray, (out.results.cap_times,
                                                    ref.results.cap_times)))


# ---------------------------------------------------------------------------
# CRN metamorphic properties — a fixed deterministic panel of intervention
# specs; tests/test_scenarios_property.py re-runs the same metamorphics with
# hypothesis-randomized specs under the forced-multi-device CI step.
# ---------------------------------------------------------------------------

SPEC_PANEL = [
    (PauseCampaign(3),),
    (BoostCampaign(1, 1.7), BudgetPacing(4, start=128, stop=384)),
    (BidNoise(0.3), ParticipationJitter(0.8, campaign=2)),
    (BudgetPacing(0, start=65, stop=257), BidNoise(0.2, campaign=5),
     PauseCampaign(6)),
]


@pytest.mark.parametrize("spec", SPEC_PANEL)
@pytest.mark.parametrize("chunking", [(None, None), (64, 1), (128, 3)])
def test_crn_identical_specs_identical_lanes_any_chunking(spec, chunking):
    """The CRN contract's core metamorphic: the SAME intervention spec in
    two different lanes produces bitwise identical outcomes (draws depend
    on (event, campaign) identity, not the lane index), and the whole
    family is bitwise invariant under every aligned event/scenario chunk
    schedule."""
    eng = _engine()
    epc, spc = chunking
    fam = compile_family(eng.values, eng.budgets, eng.base_rule,
                         [spec, spec], key=jax.random.PRNGKey(5))
    ref = eng.sweep(fam)
    spend, caps = _spends_caps(ref)
    np.testing.assert_array_equal(spend[2], spend[1])
    np.testing.assert_array_equal(caps[2], caps[1])
    out = eng.sweep(fam, chunks=epc, scenario_chunks=spc)
    np.testing.assert_array_equal(np.asarray(out.results.final_spend),
                                  spend, err_msg=f"epc={epc} spc={spc}")
    np.testing.assert_array_equal(np.asarray(out.results.cap_times),
                                  caps, err_msg=f"epc={epc} spc={spc}")


@pytest.mark.parametrize("spec_a,spec_b",
                         list(zip(SPEC_PANEL, SPEC_PANEL[1:])))
def test_crn_delta_isolation_across_family_membership(spec_a, spec_b):
    """Adding a scenario to a family never changes any other lane's bits:
    lane outcomes depend only on (family key, own interventions), so
    deltas isolate the intervention by construction."""
    eng = _engine()
    key = jax.random.PRNGKey(5)
    fam_a = compile_family(eng.values, eng.budgets, eng.base_rule,
                           [spec_a], key=key)
    fam_ab = compile_family(eng.values, eng.budgets, eng.base_rule,
                            [spec_a, spec_b], key=key)
    sp_a, ct_a = _spends_caps(eng.sweep(fam_a))
    sp_ab, ct_ab = _spends_caps(eng.sweep(fam_ab))
    np.testing.assert_array_equal(sp_ab[:2], sp_a)
    np.testing.assert_array_equal(ct_ab[:2], ct_a)


@pytest.mark.parametrize("spec_a,spec_b",
                         list(zip(SPEC_PANEL, SPEC_PANEL[1:])))
def test_crn_scenario_order_independence(spec_a, spec_b):
    """Permuting the scenario list permutes the results bitwise — lane
    outcomes carry no trace of their scenario index."""
    eng = _engine()
    key = jax.random.PRNGKey(5)
    ab = compile_family(eng.values, eng.budgets, eng.base_rule,
                        [spec_a, spec_b], key=key)
    ba = compile_family(eng.values, eng.budgets, eng.base_rule,
                        [spec_b, spec_a], key=key)
    sp_ab, ct_ab = _spends_caps(eng.sweep(ab))
    sp_ba, ct_ba = _spends_caps(eng.sweep(ba))
    np.testing.assert_array_equal(sp_ab[1], sp_ba[2])
    np.testing.assert_array_equal(sp_ab[2], sp_ba[1])
    np.testing.assert_array_equal(ct_ab[1], ct_ba[2])


@pytest.mark.parametrize("c", [0, 5])
@pytest.mark.parametrize("extra", SPEC_PANEL[:3])
def test_pause_property(c, extra):
    """PauseCampaign(c) composed with other interventions: campaign c
    spends exactly 0 and never caps out."""
    eng = _engine()
    fam = compile_family(eng.values, eng.budgets, eng.base_rule,
                         [tuple(extra) + (PauseCampaign(c),)],
                         key=jax.random.PRNGKey(5))
    spend, caps = _spends_caps(eng.sweep(fam))
    assert spend[1, c] == 0.0
    assert caps[1, c] == N + 1


# ---------------------------------------------------------------------------
# compile_family contract
# ---------------------------------------------------------------------------

def test_design_only_family_compiles_overlay_free():
    eng = _engine()
    fam = compile_family(
        eng.values, eng.budgets, eng.base_rule,
        [BoostCampaign(1, 1.5), {"bid_scale": 1.2, "budget_scale": 0.5},
         MultiplierJitter(0.3, draw=1)],
        key=jax.random.PRNGKey(9))
    assert fam.overlay is None
    assert fam.labels[0] == "base"
    # and it runs on sort2aggregate, warm starts included
    swept = eng.sweep(fam, method="sort2aggregate",
                      warm_start="per_scenario")
    assert swept.results.final_spend.shape == (4, C)


def test_stochastic_family_requires_key():
    eng = _engine()
    with pytest.raises(ValueError, match="key"):
        compile_family(eng.values, eng.budgets, eng.base_rule,
                       [BidNoise(0.2)])


def test_campaign_bounds_checked():
    eng = _engine()
    with pytest.raises(ValueError, match="out of range"):
        compile_family(eng.values, eng.budgets, eng.base_rule,
                       [PauseCampaign(C)])


def test_entrant_slots_shared_by_label():
    """Two scenarios adding the SAME slot share one column (same CRN
    values); distinct slots get distinct columns."""
    eng = _engine()
    fam = compile_family(
        eng.values, eng.budgets, eng.base_rule,
        [AddEntrant(budget=3.0, slot="x"),
         [AddEntrant(budget=5.0, slot="x"), AddEntrant(budget=2.0,
                                                       slot="y")]],
        key=jax.random.PRNGKey(13))
    assert fam.num_entrants == 2
    assert fam.values.shape == (N, C + 2)
    # lane budgets reflect each scenario's own entrant budget
    b = np.asarray(fam.grid.budgets)
    assert b[1, C] == 3.0 and b[2, C] == 5.0 and b[2, C + 1] == 2.0
    # same family key => identical entrant value column across compiles
    fam2 = compile_family(eng.values, eng.budgets, eng.base_rule,
                          [AddEntrant(budget=1.0, slot="x")],
                          key=jax.random.PRNGKey(13))
    np.testing.assert_array_equal(np.asarray(fam.values[:, C]),
                                  np.asarray(fam2.values[:, C]))


def test_multiplier_jitter_draws_differ_but_replay_shared():
    eng = _engine()
    fam = compile_family(eng.values, eng.budgets, eng.base_rule,
                         [MultiplierJitter(0.2, draw=0),
                          MultiplierJitter(0.2, draw=1),
                          MultiplierJitter(0.2, draw=0)],
                         key=jax.random.PRNGKey(7))
    m = np.asarray(fam.grid.rules.multipliers)
    assert not np.array_equal(m[1], m[2])        # draws are i.i.d.
    np.testing.assert_array_equal(m[3], m[1])    # same draw = same design


# ---------------------------------------------------------------------------
# warm starts under the CRN jitter model (the re-measured satellite)
# ---------------------------------------------------------------------------

def test_warm_start_modes_on_crn_jitter_family():
    """All three warm-start modes converge to identical spends on a
    CRN-jittered design family, and the converged-base seed needs the
    fewest refine iterations per sweep (the re-measured ALGORITHMS.md
    recommendation; per_scenario's advantage is skipping the serial base
    pre-pass, not per-sweep iterations)."""
    eng = _engine()
    fam = compile_family(eng.values, eng.budgets, eng.base_rule,
                         [MultiplierJitter(1.0, draw=d) for d in range(6)],
                         key=jax.random.PRNGKey(7))
    assert fam.overlay is None
    runs = {ws: eng.sweep(fam, method="sort2aggregate", warm_start=ws,
                          refine_iters=24)
            for ws in ("base", "per_scenario", False)}
    base_spend = np.asarray(runs["base"].results.final_spend)
    for ws, swept in runs.items():
        np.testing.assert_array_equal(
            np.asarray(swept.results.final_spend), base_spend,
            err_msg=f"warm_start={ws} diverged")
        assert np.asarray(swept.consistency_gaps).max() == 0
    mean_iters = {ws: float(np.asarray(r.refine_iters).mean())
                  for ws, r in runs.items()}
    assert mean_iters["base"] <= mean_iters["per_scenario"]


# ---------------------------------------------------------------------------
# Algorithm 4 under overlays (CRN-keyed pi estimation)
# ---------------------------------------------------------------------------

def test_estimate_pi_sweep_with_overlay():
    """A paused campaign's pi goes to 1 (spends nothing, never caps); the
    estimate is deterministic given (key, overlay); the no-overlay path is
    untouched bitwise."""
    env = _env()
    S = 3
    budgets = jnp.broadcast_to(env.budgets, (S, C))
    rules = AuctionRule(multipliers=jnp.ones((S, C), jnp.float32),
                        reserve=jnp.zeros((S,), jnp.float32))
    ovl = ScenarioOverlay(
        live_start=jnp.zeros((S, C), jnp.int32),
        live_stop=jnp.full((S, C), N, jnp.int32).at[1, 0].set(0),
        bid_sigma=jnp.zeros((S, C), jnp.float32).at[2, 1].set(0.4),
        part_prob=None, key=jax.random.PRNGKey(2), time_varying=False)
    kw = dict(sample_size=64, num_iters=10, batch_size=16)
    est = vi.estimate_pi_sweep(env.values, budgets, rules,
                               jax.random.PRNGKey(0), overlay=ovl, **kw)
    pi = np.asarray(est.pi)
    assert pi.shape == (S, C)
    assert pi[1, 0] == 1.0                      # paused -> never caps
    est2 = vi.estimate_pi_sweep(env.values, budgets, rules,
                                jax.random.PRNGKey(0), overlay=ovl, **kw)
    np.testing.assert_array_equal(pi, np.asarray(est2.pi))
    # lanes 0 (no intervention) of overlay vs no-overlay runs agree bitwise
    est0 = vi.estimate_pi_sweep(env.values, budgets, rules,
                                jax.random.PRNGKey(0), **kw)
    np.testing.assert_array_equal(pi[0], np.asarray(est0.pi)[0])
