"""Equivalence harness for the always-on counterfactual service.

The lock this suite provides (ISSUE: service answers must be *provably* the
one-shot engine's): every exact-path answer — ask tickets, grid sweeps,
family sweeps, delegated engine sweeps — is asserted BITWISE equal to a
fresh ``CounterfactualEngine.sweep`` over the same full log, across append
partitions × executor plan cells; cache hits are asserted bitwise equal to
cache misses; admission order must not change any answer. The streaming
carry path is locked to its own contract: bitwise the batch run when the
log arrives in one fold, deterministic across services, and round-trippable
through pickle / host transfer.
"""
import pickle
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AuctionRule, CounterfactualEngine, ScenarioGrid,
                        execute_sweep, execute_sweep_resumable,
                        initial_carry, stack_rules)
from repro.core.executor import SweepPlan
from repro.data import make_synthetic_env
from repro.scenarios import (AddEntrant, BidNoise, PauseCampaign,
                             ScaleBudget, compile_family)
from repro.search import SearchSpace
from repro.serve import CounterfactualService

_N, _C = 512, 8
_EPC = 128  # service append granularity; all partitions below are multiples

PARTITIONS = [(_N,), (128, 384), (128, 128, 128, 128)]


@pytest.fixture(scope="module")
def env():
    return make_synthetic_env(jax.random.PRNGKey(2), n_events=_N,
                              n_campaigns=_C, emb_dim=6)


@pytest.fixture(scope="module")
def base():
    return AuctionRule.first_price(_C)


@pytest.fixture(scope="module")
def grid(env, base):
    rules = [base,
             base.with_multiplier(2, 1.7),
             base.with_multiplier(5, 0.4),
             AuctionRule(multipliers=jnp.full((_C,), 1.2, jnp.float32),
                         reserve=jnp.asarray(0.05, jnp.float32),
                         kind="first_price")]
    budgets = [env.budgets, env.budgets * 0.7, env.budgets * 1.3,
               env.budgets]
    return ScenarioGrid.from_scenarios(list(zip(rules, budgets)))


@pytest.fixture(scope="module")
def reference(env, base, grid):
    return CounterfactualEngine(env.values, env.budgets, base).sweep(
        grid, method="parallel")


def _splits(values, partition):
    out, start = [], 0
    for n in partition:
        out.append(values[start:start + n])
        start += n
    assert start == values.shape[0]
    return out


def _assert_bitwise(result, reference):
    np.testing.assert_array_equal(np.asarray(result.results.final_spend),
                                  np.asarray(reference.results.final_spend))
    np.testing.assert_array_equal(np.asarray(result.results.cap_times),
                                  np.asarray(reference.results.cap_times))


# ---------------------------------------------------------------------------
# incremental append: service == one-shot engine, bitwise, across plan cells
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("partition", PARTITIONS,
                         ids=["one", "uneven", "quarters"])
@pytest.mark.parametrize("plan_kwargs", [
    dict(),
    dict(resolve="fused", interpret=True),
    dict(scenario_chunks=2),
    dict(chunks=128),
], ids=["default", "fused", "schunk2", "echunk128"])
def test_incremental_append_matches_one_shot(env, base, grid, reference,
                                             partition, plan_kwargs):
    svc = CounterfactualService(env.budgets, base, events_per_chunk=_EPC,
                                **plan_kwargs)
    for slab in _splits(env.values, partition):
        svc.append(slab)
    _assert_bitwise(svc.sweep(grid), reference)


def test_mid_stream_ask_matches_prefix_sweep(env, base, grid):
    """Every intermediate log version answers exactly as a one-shot engine
    over the prefix — answers are pinned to the version they were admitted
    under."""
    svc = CounterfactualService(env.budgets, base, events_per_chunk=_EPC)
    start = 0
    for n in (128, 256, 128):
        svc.append(env.values[start:start + n])
        start += n
        prefix_ref = CounterfactualEngine(
            env.values[:start], env.budgets, base).sweep(grid)
        got = svc.sweep(grid)
        _assert_bitwise(got, prefix_ref)
        assert got.n_events == start


# ---------------------------------------------------------------------------
# delta-aware cache: hits are bitwise misses; counters account exactly
# ---------------------------------------------------------------------------

def test_cache_hit_bitwise_equals_miss(env, base, grid, reference):
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    first = svc.sweep(grid)
    assert svc.stats["misses"] == grid.num_scenarios
    assert svc.stats["batches"] == 1
    second = svc.sweep(grid)
    assert svc.stats["batches"] == 1, "cached sweep must not re-execute"
    assert svc.stats["hits"] == grid.num_scenarios
    _assert_bitwise(first, reference)
    _assert_bitwise(second, reference)


def test_append_invalidates_cache(env, base, grid):
    svc = CounterfactualService(env.budgets, base, events_per_chunk=_EPC)
    svc.append(env.values[:256])
    v1 = svc.sweep(grid)
    svc.append(env.values[256:])
    assert svc.stats["cached"] == 0, "append must drop stale entries"
    v2 = svc.sweep(grid)
    assert svc.stats["batches"] == 2
    # the two versions genuinely answer different questions
    assert not np.array_equal(np.asarray(v1.results.final_spend),
                              np.asarray(v2.results.final_spend))


def test_overlapping_grids_dedupe_through_cache(env, base, grid, reference):
    """A second grid sharing scenarios with the first only executes the
    novel lanes — the search()-over-overlapping-proposals access pattern."""
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    svc.sweep(grid)
    shifted = ScenarioGrid.from_scenarios(
        [grid.scenario(1), grid.scenario(2),
         (base.with_multiplier(0, 2.5), env.budgets)])
    got = svc.sweep(shifted)
    assert svc.stats["hits"] == 2 and svc.stats["misses"] == 5
    assert svc.stats["batches"] == 2
    np.testing.assert_array_equal(
        np.asarray(got.results.final_spend)[:2],
        np.asarray(reference.results.final_spend)[1:3])


# ---------------------------------------------------------------------------
# admission batching: FIFO routing, order independence, oversized batches
# ---------------------------------------------------------------------------

def test_admission_batch_answers_match_reference(env, base, grid, reference):
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    tickets = [svc.ask(*grid.scenario(s), label=f"s{s}")
               for s in range(grid.num_scenarios)]
    assert all(not t.done for t in tickets)
    answers = [t.result() for t in tickets]
    assert svc.stats["batches"] == 1, "one drain = one executor call"
    for s, ans in enumerate(answers):
        np.testing.assert_array_equal(
            ans.final_spend, np.asarray(reference.results.final_spend)[s])
        np.testing.assert_array_equal(
            ans.cap_times, np.asarray(reference.results.cap_times)[s])
        assert ans.log_version == 1


def test_admission_order_independence(env, base, grid):
    """Any admission order yields bitwise the same per-scenario answers —
    and the same answers as serial one-at-a-time asks."""
    orders = [list(range(grid.num_scenarios)),
              list(reversed(range(grid.num_scenarios)))]
    collected = []
    for order in orders:
        svc = CounterfactualService(env.budgets, base, events=env.values,
                                    events_per_chunk=_EPC)
        tickets = {s: svc.ask(*grid.scenario(s)) for s in order}
        svc.flush()
        collected.append({s: tickets[s].result() for s in order})
    serial = {}
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    for s in range(grid.num_scenarios):
        serial[s] = svc.ask(*grid.scenario(s)).result()
    for s in range(grid.num_scenarios):
        for got in collected:
            np.testing.assert_array_equal(got[s].final_spend,
                                          serial[s].final_spend)
            np.testing.assert_array_equal(got[s].cap_times,
                                          serial[s].cap_times)


def test_oversized_batch_is_scenario_chunked(env, base, grid, reference):
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC, max_batch=3)
    tickets = [svc.ask(*grid.scenario(s)) for s in range(4)]
    tickets += [svc.ask(budgets=env.budgets * (0.5 + 0.1 * i))
                for i in range(4)]
    answers = [t.result() for t in tickets]
    assert svc.stats["batches"] == 1, \
        "oversized drains run scenario-chunked, still one executor call"
    for s in range(4):
        np.testing.assert_array_equal(
            answers[s].final_spend,
            np.asarray(reference.results.final_spend)[s])


def test_duplicate_asks_count_hits_not_lanes(env, base):
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    a = svc.ask()
    b = svc.ask()           # same design admitted twice in one drain
    ra, rb = a.result(), b.result()
    assert svc.stats == {**svc.stats, "hits": 1, "misses": 1, "batches": 1}
    np.testing.assert_array_equal(ra.final_spend, rb.final_spend)
    c = svc.ask().result()  # and again, now pre-cached
    assert svc.stats["hits"] == 2 and svc.stats["batches"] == 1
    np.testing.assert_array_equal(c.final_spend, ra.final_spend)


def test_append_flushes_pending_under_admitted_version(env, base):
    svc = CounterfactualService(env.budgets, base, events_per_chunk=_EPC)
    svc.append(env.values[:256])
    ticket = svc.ask()
    svc.append(env.values[256:])   # must answer the ticket FIRST
    ans = ticket.result()
    assert ticket.done and ans.log_version == 1
    prefix = CounterfactualEngine(env.values[:256], env.budgets, base)
    ref = prefix.simulate(method="parallel")
    np.testing.assert_array_equal(ans.final_spend,
                                  np.asarray(ref.final_spend))


# ---------------------------------------------------------------------------
# service-bound engine: delegation is bitwise, search composes, stale raises
# ---------------------------------------------------------------------------

def test_engine_delegation_bitwise(env, base, grid, reference):
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    bound = svc.engine()
    _assert_bitwise(bound.sweep(grid, method="parallel"), reference)
    # repeat is served fully from cache
    batches = svc.stats["batches"]
    _assert_bitwise(bound.sweep(grid), reference)
    assert svc.stats["batches"] == batches


def test_engine_delegation_only_parallel(env, base, grid):
    """Non-parallel methods bypass the service (oracle/s2a paths keep
    their own semantics) and still answer as an unbound engine would."""
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    bound = svc.engine()
    seq = bound.sweep(grid, method="sequential")
    assert svc.stats["batches"] == 0, "sequential sweeps bypass the service"
    plain = CounterfactualEngine(env.values, env.budgets, base).sweep(
        grid, method="sequential")
    _assert_bitwise(seq, plain)


def test_search_through_service_matches_plain(env, base):
    space = SearchSpace(bid_scale=(0.6, 1.6), reserve=(0.0, 0.2))
    plain = CounterfactualEngine(env.values, env.budgets, base).search(
        space, budget=64)
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    routed = svc.engine().search(space, budget=64)
    assert routed.best_point == plain.best_point
    assert routed.best_value == plain.best_value
    assert routed.evaluations == plain.evaluations
    assert svc.stats["batches"] > 0, "search ran through the service"


def test_stale_engine_raises_after_append(env, base, grid):
    svc = CounterfactualService(env.budgets, base, events_per_chunk=_EPC)
    svc.append(env.values[:256])
    bound = svc.engine()
    svc.append(env.values[256:])
    with pytest.raises(ValueError, match="stale service-bound engine"):
        bound.sweep(grid)
    _assert_bitwise(
        svc.engine().sweep(grid),
        CounterfactualEngine(env.values, env.budgets, base).sweep(grid))


# ---------------------------------------------------------------------------
# scenario families through the service
# ---------------------------------------------------------------------------

def test_family_sweep_bitwise(env, base):
    fam = compile_family(env.values, env.budgets, base,
                         [[PauseCampaign(2)], [ScaleBudget(1, 0.5)]])
    ref = CounterfactualEngine(env.values, env.budgets, base).sweep(fam)
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    _assert_bitwise(svc.sweep(fam), ref)
    # delegated through a bound engine too, now fully cached
    batches = svc.stats["batches"]
    _assert_bitwise(svc.engine().sweep(fam), ref)
    assert svc.stats["batches"] == batches


def test_overlay_family_sweep_bitwise(env, base):
    fam = compile_family(env.values, env.budgets, base,
                         [[BidNoise(0.1)], [PauseCampaign(0)]],
                         key=jax.random.PRNGKey(7))
    ref = CounterfactualEngine(env.values, env.budgets, base).sweep(fam)
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    _assert_bitwise(svc.sweep(fam), ref)


def test_family_fingerprints_distinguish_scenarios(env, base):
    fam = compile_family(env.values, env.budgets, base,
                         [[PauseCampaign(2)], [ScaleBudget(1, 0.5)]])
    fam2 = compile_family(env.values, env.budgets, base,
                          [[PauseCampaign(2)], [ScaleBudget(1, 0.5)]])
    assert fam.fingerprints() == fam2.fingerprints(), \
        "fingerprints are canonical: identical designs hash identically"
    assert len(set(fam.fingerprints())) == fam.num_scenarios == 3, \
        "base lane + two distinct interventions, all distinct"
    assert fam.fingerprint() == fam2.fingerprint()


def test_entrant_family_rejected(env, base):
    fam = compile_family(env.values, env.budgets, base,
                         [[AddEntrant(budget=5.0, value_scale=0.8)]],
                         key=jax.random.PRNGKey(9))
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    with pytest.raises(ValueError, match="entrant"):
        svc.sweep(fam)


def test_stale_family_rejected(env, base):
    fam = compile_family(env.values[:256], env.budgets, base,
                         [[PauseCampaign(2)]])
    svc = CounterfactualService(env.budgets, base, events=env.values,
                                events_per_chunk=_EPC)
    with pytest.raises(ValueError, match="stale family"):
        svc.sweep(fam)


# ---------------------------------------------------------------------------
# streaming carry path (register / streaming)
# ---------------------------------------------------------------------------

def test_streaming_single_fold_bitwise_batch(env, base, grid, reference):
    svc = CounterfactualService(env.budgets, base, events_per_chunk=_EPC)
    for s in range(grid.num_scenarios):
        svc.register(f"s{s}", *grid.scenario(s))
    svc.append(env.values)
    for s in range(grid.num_scenarios):
        got = svc.streaming(f"s{s}")
        np.testing.assert_array_equal(
            got.final_spend, np.asarray(reference.results.final_spend)[s])
        np.testing.assert_array_equal(
            got.cap_times, np.asarray(reference.results.cap_times)[s])


def test_streaming_fold_deterministic_and_composable(env, base):
    """Same partition -> bitwise identical frontier, regardless of which
    service folded it or whether lanes were registered before or mid-log."""
    rule = base.with_multiplier(3, 1.4)

    def fold(partition, register_at=0):
        svc = CounterfactualService(env.budgets, base,
                                    events_per_chunk=_EPC)
        slabs = _splits(env.values, partition)
        for i, slab in enumerate(slabs):
            if i == register_at:
                svc.register("x", rule)
            svc.append(slab)
        if register_at >= len(slabs):
            svc.register("x", rule)
        return svc.streaming("x")

    a = fold((256, 256))
    b = fold((256, 256))
    np.testing.assert_array_equal(a.final_spend, b.final_spend)
    np.testing.assert_array_equal(a.cap_times, b.cap_times)
    # mid-log registration catches up over stored slabs, then folds forward:
    # identical to registering up front (each fold is the same program)
    c = fold((256, 256), register_at=1)
    np.testing.assert_array_equal(a.final_spend, c.final_spend)
    np.testing.assert_array_equal(a.cap_times, c.cap_times)
    # matches a manual resumable fold of the same partition
    carry = None
    for slab in _splits(env.values, (256, 256)):
        _, carry = execute_sweep_resumable(
            slab, env.budgets[None, :], stack_rules([rule]),
            SweepPlan(placement="batched"), carry=carry)
    np.testing.assert_array_equal(a.final_spend,
                                  np.asarray(carry.s_hat)[0])
    np.testing.assert_array_equal(a.cap_times,
                                  np.asarray(carry.cap_times)[0])


# ---------------------------------------------------------------------------
# carry round-trips (satellite: SweepCarry survives transfer + pickle)
# ---------------------------------------------------------------------------

def _one_fold(env, base, slab):
    return execute_sweep_resumable(
        slab, env.budgets[None, :], stack_rules([base]),
        SweepPlan(placement="batched"),
        carry=initial_carry(1, _C))


def test_carry_pickle_round_trip_bitwise(env, base):
    _, carry = _one_fold(env, base, env.values[:256])
    thawed = pickle.loads(pickle.dumps(jax.device_get(carry)))
    assert thawed.n_events_seen == 256
    plan = SweepPlan(placement="batched")
    rules = stack_rules([base])
    direct, _ = execute_sweep_resumable(env.values[256:],
                                        env.budgets[None, :], rules, plan,
                                        carry=carry)
    via_pickle, _ = execute_sweep_resumable(
        env.values[256:], env.budgets[None, :], rules, plan, carry=thawed)
    for a, b in zip(direct, via_pickle):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_carry_device_transfer_round_trip_bitwise(env, base):
    _, carry = _one_fold(env, base, env.values[:256])
    moved = jax.device_put(jax.device_get(carry))
    plan = SweepPlan(placement="batched")
    rules = stack_rules([base])
    direct, c1 = execute_sweep_resumable(env.values[256:],
                                         env.budgets[None, :], rules, plan,
                                         carry=carry)
    via_host, c2 = execute_sweep_resumable(
        env.values[256:], env.budgets[None, :], rules, plan, carry=moved)
    for a, b in zip(direct, via_host):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert c1.n_events_seen == c2.n_events_seen == _N


def test_resumable_single_fold_matches_execute_sweep(env, base, grid):
    plan = SweepPlan(placement="batched")
    ref = execute_sweep(env.values, grid.budgets, grid.rules, plan)
    got, carry = execute_sweep_resumable(env.values, grid.budgets,
                                         grid.rules, plan)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert carry.n_events_seen == _N
    assert carry.num_scenarios == grid.num_scenarios


# ---------------------------------------------------------------------------
# host-resident store + persistence
# ---------------------------------------------------------------------------

def _service_with_streams(env, base, partition, values=None, **kwargs):
    svc = CounterfactualService(env.budgets, base, events_per_chunk=_EPC,
                                **kwargs)
    svc.register("base")
    svc.register("hot2", rule=base.with_multiplier(2, 1.7))
    for slab in _splits(env.values if values is None else values,
                        partition):
        svc.append(slab)
    return svc


@pytest.mark.parametrize("partition", PARTITIONS,
                         ids=["one", "uneven", "quarters"])
def test_host_store_bitwise_device_store(env, base, grid, reference,
                                         partition):
    """store='host' keeps the log in host RAM (HostStream replays, host
    slab folds) yet answers — exact and streaming — bit-for-bit the
    device-store service across append partitions. The 'uneven' partition
    exercises fold totals where the canonical grid misaligns with any
    host chunking (the documented device-program fallback)."""
    dev = _service_with_streams(env, base, partition, store="device")
    host = _service_with_streams(env, base, partition, store="host")
    _assert_bitwise(host.sweep(grid), reference)
    for label in ("base", "hot2"):
        a, b = dev.streaming(label), host.streaming(label)
        np.testing.assert_array_equal(a.final_spend, b.final_spend)
        np.testing.assert_array_equal(a.cap_times, b.cap_times)
    a, b = dev.ask().result(), host.ask().result()
    np.testing.assert_array_equal(a.final_spend, b.final_spend)
    np.testing.assert_array_equal(a.cap_times, b.cap_times)


def test_host_store_never_concatenates(env, base):
    from repro.core.executor import HostStream
    svc = _service_with_streams(env, base, (128, 128, 128, 128),
                                store="host")
    stream = svc.values
    assert isinstance(stream, HostStream)
    assert stream.n_events == _N and len(stream._slabs) == 4


@pytest.mark.parametrize("store", ["device", "host"])
def test_save_load_append_cycle_bitwise_uninterrupted(env, base, grid,
                                                      store):
    """A service saved, restored, and appended-to answers bitwise a
    service that never stopped — exact asks, grid sweeps, and streaming
    frontiers alike."""
    svc = _service_with_streams(env, base, (128, 256),
                                values=env.values[:384], store=store)
    with tempfile.TemporaryDirectory() as d:
        ckpt_dir = svc.save(d)
        assert ckpt_dir.name == f"step_{svc.log_version:08d}"
        restored = CounterfactualService.load(d)
    assert restored.store == store
    assert restored.log_version == svc.log_version
    assert restored.n_events == svc.n_events
    assert restored.stats["registered"] == 2
    tail = env.values[384:]
    svc.append(tail)
    restored.append(tail)
    for label in ("base", "hot2"):
        a, b = svc.streaming(label), restored.streaming(label)
        np.testing.assert_array_equal(a.final_spend, b.final_spend,
                                      err_msg=label)
        np.testing.assert_array_equal(a.cap_times, b.cap_times,
                                      err_msg=label)
    _assert_bitwise(restored.sweep(grid), svc.sweep(grid))
    a = svc.ask(rule=base.with_multiplier(5, 0.4)).result()
    b = restored.ask(rule=base.with_multiplier(5, 0.4)).result()
    np.testing.assert_array_equal(a.final_spend, b.final_spend)
    np.testing.assert_array_equal(a.cap_times, b.cap_times)
    assert a.log_version == b.log_version


def test_save_load_roundtrip_full_log_answer(env, base, grid, reference):
    """A restored service's first answers replay the restored slabs —
    bitwise the one-shot engine sweep of the full log."""
    svc = _service_with_streams(env, base, (_N,), store="host")
    with tempfile.TemporaryDirectory() as d:
        svc.save(d)
        restored = CounterfactualService.load(d)
    _assert_bitwise(restored.sweep(grid), reference)


def test_load_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="no service checkpoints"):
        CounterfactualService.load(tmp_path)
