"""Golden equivalence for the batched scenario-sweep engine.

Two contracts:

* the device-resident Algorithm-2 driver is the SAME algorithm as the host
  reference driver — float32 arithmetic in the same order — so
  ``final_spend``/``cap_times`` must match bit-for-bit, on easy and tie-heavy
  logs, under both pricing rules;
* a batched sweep is just S independent replays fused into one program — each
  scenario must match its own independent ``sequential_replay`` within the
  Theorem-5.2-style tolerance the seed suite already enforces for the
  unbatched estimators.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AuctionRule, CounterfactualEngine, ScenarioGrid,
                        parallel_simulate, sequential_replay,
                        sweep_parallel, sweep_sequential, sweep_sharded,
                        sweep_sort2aggregate, sweep_state_machine,
                        stack_rules)
from repro.core.metrics import spend_weighted_relative_error
from repro.data import make_synthetic_env
from repro.launch.mesh import SweepMeshSpec

N_EVENTS = 4096
N_CAMPAIGNS = 16
# mean relative spend error allowed vs the exact oracle (cf.
# test_core_parallel.test_parallel_close_to_oracle's Thm-5.2-style budget)
ORACLE_TOL = 0.08


@pytest.fixture(scope="module")
def env():
    return make_synthetic_env(jax.random.PRNGKey(1), n_events=N_EVENTS,
                              n_campaigns=N_CAMPAIGNS, emb_dim=8)


def _configs(env):
    """(label, rule, budgets): both price rules plus a tie-heavy budget set
    (equal budgets -> many campaigns predicted to cap in the same round)."""
    ties = jnp.full((N_CAMPAIGNS,), float(env.budgets[N_CAMPAIGNS // 2]))
    return [
        ("first", AuctionRule.first_price(N_CAMPAIGNS), env.budgets),
        ("second", AuctionRule.second_price(N_CAMPAIGNS, reserve=0.05),
         env.budgets),
        ("first_ties", AuctionRule.first_price(N_CAMPAIGNS), ties),
        ("second_ties", AuctionRule.second_price(N_CAMPAIGNS), ties),
    ]


# ---------------------------------------------------------------------------
# (a) device driver == host driver, exactly
# ---------------------------------------------------------------------------

def test_device_driver_matches_host_bit_for_bit(env):
    for label, rule, budgets in _configs(env):
        host = parallel_simulate(env.values, budgets, rule, driver="host")
        dev = parallel_simulate(env.values, budgets, rule, driver="device")
        np.testing.assert_array_equal(
            np.asarray(host.final_spend), np.asarray(dev.final_spend),
            err_msg=f"final_spend diverged for {label}")
        np.testing.assert_array_equal(
            np.asarray(host.cap_times), np.asarray(dev.cap_times),
            err_msg=f"cap_times diverged for {label}")


def test_device_driver_reproduces_segments_and_trace(env):
    host, h_tr = parallel_simulate(env.values, env.budgets, env.rule,
                                   driver="host", return_trace=True)
    dev, d_tr = parallel_simulate(env.values, env.budgets, env.rule,
                                  driver="device", return_trace=True)
    assert h_tr.num_rounds == d_tr.num_rounds
    assert h_tr.capped_order == d_tr.capped_order
    assert h_tr.boundaries == d_tr.boundaries
    np.testing.assert_array_equal(np.asarray(host.segments.boundaries),
                                  np.asarray(dev.segments.boundaries))
    np.testing.assert_array_equal(np.asarray(host.segments.masks),
                                  np.asarray(dev.segments.masks))


def test_device_driver_infinite_budgets_single_round(env):
    inf_b = jnp.full_like(env.budgets, jnp.inf)
    res, trace = parallel_simulate(env.values, inf_b, env.rule,
                                   driver="device", return_trace=True)
    assert trace.num_rounds == 1
    assert int(res.num_capped(env.n_events)) == 0


def test_device_driver_rejects_custom_reductions(env):
    with pytest.raises(ValueError):
        parallel_simulate(env.values, env.budgets, env.rule,
                          driver="device", rate_fn=lambda a, lo: a)


# ---------------------------------------------------------------------------
# (b) batched sweeps == independent per-scenario replays
# ---------------------------------------------------------------------------

def _grid(env, kind):
    base = (AuctionRule.first_price(N_CAMPAIGNS) if kind == "first_price"
            else AuctionRule.second_price(N_CAMPAIGNS))
    return ScenarioGrid.product(
        base, env.budgets,
        bid_scales=[1.0, 0.9, 1.1, 1.3],
        reserves=[0.0, 0.05],
    )


@pytest.mark.parametrize("kind", ["first_price", "second_price"])
def test_sweep_parallel_matches_per_scenario_oracle(env, kind):
    grid = _grid(env, kind)
    assert grid.num_scenarios >= 8
    sw = sweep_parallel(env.values, grid.budgets, grid.rules)
    assert sw.final_spend.shape == (grid.num_scenarios, N_CAMPAIGNS)
    for s in range(grid.num_scenarios):
        rule, budgets = grid.scenario(s)
        ref = sequential_replay(env.values, budgets, rule,
                                record_events=False)
        rel = np.abs(np.asarray(sw.final_spend[s])
                     - np.asarray(ref.final_spend)) \
            / np.maximum(np.asarray(ref.final_spend), 1e-9)
        assert rel.mean() < ORACLE_TOL, (grid.labels[s], rel.mean())


def test_sweep_parallel_equals_unbatched_device_driver(env):
    """vmapping the state machine must not change any scenario's outcome."""
    grid = _grid(env, "first_price")
    sw = sweep_parallel(env.values, grid.budgets, grid.rules)
    for s in range(grid.num_scenarios):
        rule, budgets = grid.scenario(s)
        solo = parallel_simulate(env.values, budgets, rule, driver="device")
        np.testing.assert_array_equal(np.asarray(sw.final_spend[s]),
                                      np.asarray(solo.final_spend),
                                      err_msg=grid.labels[s])
        np.testing.assert_array_equal(np.asarray(sw.cap_times[s]),
                                      np.asarray(solo.cap_times),
                                      err_msg=grid.labels[s])


def test_sweep_sequential_is_the_batched_oracle(env):
    grid = _grid(env, "second_price")
    sw = sweep_sequential(env.values, grid.budgets, grid.rules)
    for s in (0, 3, grid.num_scenarios - 1):
        rule, budgets = grid.scenario(s)
        ref = sequential_replay(env.values, budgets, rule,
                                record_events=False)
        np.testing.assert_allclose(np.asarray(sw.final_spend[s]),
                                   np.asarray(ref.final_spend),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(sw.cap_times[s]),
                                      np.asarray(ref.cap_times))


def test_sweep_sort2aggregate_close_to_oracle_with_ties(env):
    """Warm-started s2a sweep over a tie-heavy grid (equal budgets + budget
    scalings -> shared cap rounds) stays within tolerance per scenario."""
    base = AuctionRule.first_price(N_CAMPAIGNS)
    ties = jnp.full((N_CAMPAIGNS,), float(env.budgets[N_CAMPAIGNS // 2]))
    grid = ScenarioGrid.product(base, ties,
                                bid_scales=[1.0, 0.9, 1.1, 1.2],
                                budget_scales=[1.0, 0.8])
    assert grid.num_scenarios >= 8
    warm = sequential_replay(env.values, ties, base,
                             record_events=False).cap_times
    sw, gaps, iters = sweep_sort2aggregate(env.values, grid.budgets,
                                           grid.rules, cap_times_init=warm,
                                           refine_iters=8)
    assert gaps.shape == (grid.num_scenarios,)
    assert iters.shape == (grid.num_scenarios,)
    # the warm start IS scenario 0's fixed point: refinement must not move it
    assert int(iters[0]) == 0
    for s in range(grid.num_scenarios):
        rule, budgets = grid.scenario(s)
        ref = sequential_replay(env.values, budgets, rule,
                                record_events=False)
        err = float(spend_weighted_relative_error(sw.final_spend[s],
                                                  ref.final_spend))
        assert err < ORACLE_TOL, (grid.labels[s], err, float(gaps[s]))


# ---------------------------------------------------------------------------
# (c) resolve back-ends: batched Pallas kernel == vmapped jnp path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["first_price", "second_price"])
def test_sweep_parallel_pallas_matches_jnp(env, kind):
    """resolve="pallas" (interpret mode on CPU) must reproduce the vmapped
    jnp sweep: cap times exactly, final spend within 1e-5 (bitwise, in
    practice, since the kernel emits identical winners/prices)."""
    grid = _grid(env, kind)
    ref = sweep_parallel(env.values, grid.budgets, grid.rules, resolve="jnp")
    pal = sweep_parallel(env.values, grid.budgets, grid.rules,
                         resolve="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(pal.final_spend),
                               np.asarray(ref.final_spend),
                               rtol=1e-5, atol=1e-5, err_msg=kind)
    np.testing.assert_array_equal(np.asarray(pal.cap_times),
                                  np.asarray(ref.cap_times), err_msg=kind)


def test_sweep_state_machine_matches_vmapped_loop(env):
    """The explicitly batched while_loop (jnp resolve) is bit-for-bit the
    vmapped single-scenario state machine — lane freezing included (the grid
    mixes early- and never-capping scenarios so lanes finish at different
    rounds)."""
    base = AuctionRule.first_price(N_CAMPAIGNS)
    grid = ScenarioGrid.product(base, env.budgets,
                                bid_scales=[1.0, 1.2],
                                budget_scales=[1.0, 0.25, 1e6])
    ref = sweep_parallel(env.values, grid.budgets, grid.rules, resolve="jnp")
    s_hat, caps, retired, bnds, rounds, n_hat = sweep_state_machine(
        env.values, grid.budgets, grid.rules, resolve="jnp")
    np.testing.assert_array_equal(np.asarray(s_hat),
                                  np.asarray(ref.final_spend))
    np.testing.assert_array_equal(np.asarray(caps), np.asarray(ref.cap_times))
    # round logs must match the per-scenario device driver too
    for s in range(grid.num_scenarios):
        rule, budgets = grid.scenario(s)
        _, solo_tr = parallel_simulate(env.values, budgets, rule,
                                       driver="device", return_trace=True)
        assert int(rounds[s]) == solo_tr.num_rounds, grid.labels[s]


def test_sweep_pallas_winners_match_jnp_resolve(env):
    """Per-round winners parity on the exact activation sets the sweep
    visits: replay the pallas sweep's segment evolution via the S=1 driver."""
    from repro.core import auction
    from repro.kernels.auction_resolve import sweep_resolve
    grid = _grid(env, "second_price")
    act = jnp.ones((grid.num_scenarios, N_CAMPAIGNS), bool)
    w_ref, p_ref = jax.vmap(
        lambda a, r: auction.resolve(env.values, a, r),
        in_axes=(0, 0))(act, grid.rules)
    w, p, _ = sweep_resolve(env.values, grid.rules.multipliers, act,
                            grid.rules.reserve, second_price=True,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_ref))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))


def test_engine_sweep_resolve_option(env):
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=[1.0, 1.1], reserves=[0.0, 0.02])
    ref = engine.sweep(grid, method="parallel", resolve="jnp")
    pal = engine.sweep(grid, method="parallel", resolve="pallas")
    np.testing.assert_allclose(np.asarray(pal.results.final_spend),
                               np.asarray(ref.results.final_spend),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(pal.results.cap_times),
                                  np.asarray(ref.results.cap_times))
    assert pal.delta_table() == ref.delta_table()


def test_sweep_rejects_unknown_resolve(env):
    grid = _grid(env, "first_price")
    with pytest.raises(ValueError):
        sweep_state_machine(env.values, grid.budgets, grid.rules,
                            resolve="cuda")


# ---------------------------------------------------------------------------
# (c2) fused round: one launch per round == the jnp loop, bit-for-bit
# ---------------------------------------------------------------------------

def _skewed_grid(env):
    """Mixes early-retiring, normal, and never-capping scenarios, so lanes
    freeze at very different rounds — the converged-lane-skipping regime."""
    base = AuctionRule.first_price(N_CAMPAIGNS)
    return ScenarioGrid.product(base, env.budgets,
                                bid_scales=[1.0, 1.2],
                                budget_scales=[1.0, 0.25, 1e6])


def test_sweep_fused_oracle_is_bitwise_the_jnp_loop(env):
    """resolve="fused" on CPU (jnp oracle composition) must be bit-for-bit
    the vmapped jnp sweep — every output of the batched loop."""
    for grid in (_grid(env, "first_price"), _grid(env, "second_price"),
                 _skewed_grid(env)):
        ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                                  resolve="jnp")
        out = sweep_state_machine(env.values, grid.budgets, grid.rules,
                                  resolve="fused")
        for name, a, b in zip(("final_spend", "cap_times", "retired",
                               "boundaries", "num_rounds", "n_hat"),
                              out, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)


@pytest.mark.parametrize("kind", ["first_price", "second_price"])
def test_sweep_fused_kernel_matches_jnp(env, kind):
    """The fused round KERNEL (interpret mode on CPU) end-to-end: partials
    accumulated in-kernel reproduce the jnp loop's spends and cap times."""
    grid = _grid(env, kind)
    ref = sweep_parallel(env.values, grid.budgets, grid.rules, resolve="jnp")
    fus = sweep_parallel(env.values, grid.budgets, grid.rules,
                         resolve="fused", interpret=True)
    np.testing.assert_allclose(np.asarray(fus.final_spend),
                               np.asarray(ref.final_spend),
                               rtol=1e-5, atol=1e-5, err_msg=kind)
    np.testing.assert_array_equal(np.asarray(fus.cap_times),
                                  np.asarray(ref.cap_times), err_msg=kind)


def test_fused_skip_retired_bit_identical(env):
    """Converged-lane skipping is a pure wall-clock optimisation: a lane
    that retires at round k has bit-identical results whether the remaining
    rounds run masked or unmasked — kernel (interpret) and oracle back-ends,
    single device. (The 4-device half of this contract runs in
    test_fused_sharded_retired_lanes_4dev below and in
    tests/test_sharded_sweep.py.)"""
    grid = _skewed_grid(env)
    outs = {}
    for skip in (True, False):
        outs[skip] = sweep_state_machine(
            env.values, grid.budgets, grid.rules, resolve="fused",
            interpret=True, skip_retired=skip)
    for name, a, b in zip(("final_spend", "cap_times", "retired",
                           "boundaries", "num_rounds", "n_hat"),
                          outs[True], outs[False]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"masked vs unmasked: {name}")
    # and both equal the jnp loop
    ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="jnp")
    np.testing.assert_array_equal(np.asarray(outs[True][0]),
                                  np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(outs[True][1]),
                                  np.asarray(ref[1]))


@pytest.mark.slow
def test_fused_sharded_retired_lanes_4dev():
    """The masked-vs-unmasked contract at 4 forced host devices: the fused
    sharded sweep (oracle and interpret-kernel back-ends) is bit-identical
    with lane skipping on and off, and equal to the jnp loop."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        assert len(jax.devices()) == 4
        from repro.core import AuctionRule, ScenarioGrid, sweep_state_machine
        from repro.core.sharded import sweep_sharded
        from repro.data import make_synthetic_env
        from repro.launch.mesh import SweepMeshSpec
        env = make_synthetic_env(jax.random.PRNGKey(1), n_events=4096,
                                 n_campaigns=16, emb_dim=8)
        base = AuctionRule.first_price(16)
        grid = ScenarioGrid.product(base, env.budgets,
                                    bid_scales=[1.0, 1.2],
                                    budget_scales=[1.0, 0.25, 1e6])
        ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                                  resolve="jnp")
        spec = SweepMeshSpec.for_devices(num_event_devices=4)
        names = ("final_spend", "cap_times", "retired", "boundaries",
                 "num_rounds", "n_hat")
        for interpret in (None, True):
            for skip in (True, False):
                out = sweep_sharded(env.values, grid.budgets, grid.rules,
                                    spec, resolve="fused",
                                    interpret=interpret, skip_retired=skip)
                for name, a, b in zip(names, out, ref):
                    assert np.array_equal(np.asarray(a), np.asarray(b)), \\
                        (interpret, skip, name)
        print("FUSED_SHARDED_4DEV_OK")
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # fake host devices, never a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FUSED_SHARDED_4DEV_OK" in out.stdout


def test_one_launch_vmem_fallback(env, monkeypatch):
    """The one-launch fused round is only selected when its resident state
    fits the VMEM budget (docs/ALGORITHMS.md: S=32 fits at C=1024, S=64
    does not); past it the executor falls back to the two-pass
    sweep_partials shape, which must stay bit-identical — forced here by
    shrinking the budget so the fallback triggers at test sizes."""
    from repro.core import executor
    assert executor.round_fused_fits(32, 1024)
    assert not executor.round_fused_fits(64, 1024)
    grid = _grid(env, "first_price")
    ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="jnp")
    monkeypatch.setattr(executor, "ONE_LAUNCH_VMEM_BYTES", 1)
    out = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="fused", interpret=True,
                              block_t=128)   # fresh jit key -> retrace
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref[1]))


def test_auto_resolve_never_selects_interpret_pallas(env):
    """Satellite regression: BENCH_sweep.json shows interpret-mode pallas
    several times slower than vmapped jnp at the sweep layer on CPU, so
    "auto" must route around it — fused-on-TPU, jnp-on-CPU, and the program
    "auto" builds off TPU must contain no pallas_call at all."""
    from repro.core import fused_runs_kernel, pick_resolve
    assert pick_resolve("auto", on_tpu=False) == "jnp"
    assert pick_resolve("auto", on_tpu=True) == "fused"
    with pytest.raises(ValueError, match="unknown resolve"):
        pick_resolve("cuda")
    # "fused" off TPU runs its jnp oracle unless interpret is forced
    assert fused_runs_kernel(None) == resolve_on_tpu()
    assert fused_runs_kernel(True)
    # the traced "auto" sweep on CPU contains no pallas_call primitive
    if not resolve_on_tpu():
        grid = _grid(env, "first_price")
        jaxpr = jax.make_jaxpr(
            lambda v, b: sweep_parallel(v, b, grid.rules, resolve="auto")
        )(env.values, grid.budgets)
        assert "pallas_call" not in str(jaxpr)
        # ... and the same holds for the fused back-end's CPU realization
        jaxpr = jax.make_jaxpr(
            lambda v, b: sweep_parallel(v, b, grid.rules, resolve="fused")
        )(env.values, grid.budgets)
        assert "pallas_call" not in str(jaxpr)


def resolve_on_tpu():
    from repro.kernels import on_tpu
    return on_tpu()


def test_engine_sweep_fused_resolve_option(env):
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=[1.0, 1.1], reserves=[0.0, 0.02])
    ref = engine.sweep(grid, method="parallel", resolve="jnp")
    fus = engine.sweep(grid, method="parallel", resolve="fused")
    np.testing.assert_array_equal(np.asarray(fus.results.final_spend),
                                  np.asarray(ref.results.final_spend))
    np.testing.assert_array_equal(np.asarray(fus.results.cap_times),
                                  np.asarray(ref.results.cap_times))
    assert fus.delta_table() == ref.delta_table()


# ---------------------------------------------------------------------------
# (d) sharded driver: 1×1 mesh == the single-device batched loop, exactly
# ---------------------------------------------------------------------------

def test_sweep_sharded_1x1_mesh_bit_for_bit(env):
    """On a trivial mesh the sharded driver IS the batched state machine —
    every output bitwise equal (the base case of the mesh-invariance
    contract asserted at 4+ devices in test_sharded_sweep.py /
    test_sharded_core.py)."""
    grid = _grid(env, "first_price")
    ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="jnp")
    spec = SweepMeshSpec.for_devices(num_event_devices=1)
    out = sweep_sharded(env.values, grid.budgets, grid.rules, spec)
    for name, a, b in zip(("final_spend", "cap_times", "retired",
                           "boundaries", "num_rounds", "n_hat"), out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_engine_sweep_sharded_auto_smoke(env):
    """driver="sharded" × resolve="auto" through the engine API: runs on
    whatever mesh fits the local devices and matches the batched driver."""
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=[1.0, 1.1], reserves=[0.0, 0.02])
    spec = SweepMeshSpec.for_devices()
    ref = engine.sweep(grid, method="parallel")
    out = engine.sweep(grid, method="parallel", driver="sharded", mesh=spec,
                       resolve="auto")
    np.testing.assert_array_equal(np.asarray(out.results.final_spend),
                                  np.asarray(ref.results.final_spend))
    np.testing.assert_array_equal(np.asarray(out.results.cap_times),
                                  np.asarray(ref.results.cap_times))
    assert out.delta_table() == ref.delta_table()


def test_sweep_sharded_driver_requires_mesh(env):
    grid = _grid(env, "first_price")
    with pytest.raises(ValueError, match="needs mesh"):
        sweep_parallel(env.values, grid.budgets, grid.rules,
                       driver="sharded")


def test_sweep_rejects_unknown_driver(env):
    grid = _grid(env, "first_price")
    with pytest.raises(ValueError, match="unknown sweep driver"):
        sweep_parallel(env.values, grid.budgets, grid.rules, driver="mpi")


# ---------------------------------------------------------------------------
# (e) event-chunked streaming: chunked == in-memory, bit-for-bit
# ---------------------------------------------------------------------------

ALIGNED_CHUNKS = (128, 512, 2048, N_EVENTS)   # reduce block @ N=4096 is 128


def test_chunked_sweep_bitwise_aligned_sizes(env):
    """The streaming sweep (per-round chunk scan accumulating canonical
    partials via index_offset) is bit-for-bit the in-memory batched driver
    on EVERY loop output, for several aligned chunk sizes, on both the jnp
    and the fused-oracle back-ends."""
    grid = _skewed_grid(env)
    ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="jnp")
    names = ("final_spend", "cap_times", "retired", "boundaries",
             "num_rounds", "n_hat")
    for resolve in ("jnp", "fused"):
        for epc in ALIGNED_CHUNKS:
            out = sweep_state_machine(env.values, grid.budgets, grid.rules,
                                      resolve=resolve, chunks=epc)
            for name, a, b in zip(names, out, ref):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"chunks={epc} resolve={resolve}: {name}")


def test_chunked_sweep_parallel_and_engine(env):
    """chunks= through the public wrappers: sweep_parallel and
    engine.sweep produce the identical SimResult / delta table."""
    from repro.core import ChunkSpec
    grid = _grid(env, "second_price")
    ref = sweep_parallel(env.values, grid.budgets, grid.rules)
    out = sweep_parallel(env.values, grid.budgets, grid.rules,
                         chunks=ChunkSpec(events_per_chunk=256))
    np.testing.assert_array_equal(np.asarray(out.final_spend),
                                  np.asarray(ref.final_spend))
    np.testing.assert_array_equal(np.asarray(out.cap_times),
                                  np.asarray(ref.cap_times))
    engine = CounterfactualEngine(env.values, env.budgets)
    egrid = engine.grid(bid_scales=[1.0, 1.1])
    np.testing.assert_array_equal(
        np.asarray(engine.sweep(egrid, chunks=512).results.final_spend),
        np.asarray(engine.sweep(egrid).results.final_spend))


def test_chunked_pallas_kernel_matches_jnp(env):
    """Chunked + resolve="pallas" (interpret-mode kernel per chunk): cap
    times exact, spends within kernel tolerance of the unchunked jnp
    sweep."""
    grid = _grid(env, "first_price")
    ref = sweep_parallel(env.values, grid.budgets, grid.rules,
                         resolve="jnp")
    out = sweep_parallel(env.values, grid.budgets, grid.rules,
                         resolve="pallas", interpret=True, chunks=512)
    np.testing.assert_allclose(np.asarray(out.final_spend),
                               np.asarray(ref.final_spend),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out.cap_times),
                                  np.asarray(ref.cap_times))


def test_chunked_sharded_1dev_bitwise(env):
    """chunking × sharding on the trivial mesh (the 4-device half runs in
    test_chunked_sharded_4dev / tests/test_sharded_sweep.py): still the
    in-memory bits."""
    grid = _grid(env, "first_price")
    ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="jnp")
    spec = SweepMeshSpec.for_devices(num_event_devices=1)
    out = sweep_sharded(env.values, grid.budgets, grid.rules, spec,
                        chunks=512)
    for name, a, b in zip(("final_spend", "cap_times", "retired",
                           "boundaries", "num_rounds", "n_hat"), out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.slow
def test_chunked_sharded_4dev_bitwise():
    """Acceptance: chunked == in-memory batched, bit-for-bit, composed with
    driver="sharded" at 4 forced host devices (several aligned chunk
    sizes), via the public sweep_parallel driver axis."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        assert len(jax.devices()) == 4
        from repro.core import AuctionRule, ScenarioGrid, sweep_parallel
        from repro.data import make_synthetic_env
        from repro.launch.mesh import SweepMeshSpec
        env = make_synthetic_env(jax.random.PRNGKey(1), n_events=4096,
                                 n_campaigns=16, emb_dim=8)
        base = AuctionRule.first_price(16)
        grid = ScenarioGrid.product(base, env.budgets,
                                    bid_scales=[1.0, 1.2],
                                    budget_scales=[1.0, 0.25, 1e6])
        ref = sweep_parallel(env.values, grid.budgets, grid.rules)
        spec = SweepMeshSpec.for_devices(num_event_devices=4)
        for epc in (128, 512, 1024):   # local_n = 1024
            out = sweep_parallel(env.values, grid.budgets, grid.rules,
                                 driver="sharded", mesh=spec, chunks=epc)
            assert np.array_equal(np.asarray(out.final_spend),
                                  np.asarray(ref.final_spend)), epc
            assert np.array_equal(np.asarray(out.cap_times),
                                  np.asarray(ref.cap_times)), epc
        print("CHUNKED_SHARDED_4DEV_OK")
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # fake host devices, never a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "CHUNKED_SHARDED_4DEV_OK" in out.stdout


def test_misaligned_chunk_sizes_raise(env):
    """The mesh's pad-or-error contract, on the chunk axis: chunks not
    holding whole canonical blocks, or not dividing the event count."""
    grid = _grid(env, "first_price")
    with pytest.raises(ValueError, match="chunk/grid misalignment"):
        sweep_parallel(env.values, grid.budgets, grid.rules, chunks=100)
    with pytest.raises(ValueError, match="ragged chunk"):
        # holds whole 128-blocks but does not divide N=4096
        sweep_parallel(env.values, grid.budgets, grid.rules, chunks=1536)
    with pytest.raises(ValueError, match="events_per_chunk"):
        sweep_parallel(env.values, grid.budgets, grid.rules, chunks=0)


def test_misaligned_append_chunks_same_error_as_sweep(env):
    """The service's append alignment speaks the executor's pad-or-error
    contract VERBATIM: a slab that does not divide into whole chunks
    raises the identical "ragged chunk" message sweep_parallel(chunks=...)
    raises for the same misalignment."""
    from repro.serve.counterfactual import CounterfactualService
    grid = _grid(env, "first_price")

    def msg(fn):
        with pytest.raises(ValueError) as e:
            fn()
        return str(e.value)

    msgs = {
        msg(lambda: sweep_parallel(env.values, grid.budgets, grid.rules,
                                   chunks=1536)),
        msg(lambda: CounterfactualService(
            env.budgets, events_per_chunk=1536).append(env.values)),
        msg(lambda: CounterfactualService(
            env.budgets, events_per_chunk=1536, events=env.values)),
    }
    assert len(msgs) == 1, msgs
    assert "ragged chunk" in next(iter(msgs))


def test_engine_chunks_require_parallel_method(env):
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=[1.0, 1.1])
    with pytest.raises(ValueError, match="chunks"):
        engine.sweep(grid, method="sort2aggregate", chunks=256)


def test_unknown_driver_and_resolve_errors_are_consistent(env):
    """Satellite: every entry point raises the SAME ValueError text for a
    bad driver/resolve string (the executor owns validation)."""
    grid = _grid(env, "first_price")
    engine = CounterfactualEngine(env.values, env.budgets)

    def msg(fn):
        with pytest.raises(ValueError) as e:
            fn()
        return str(e.value)

    driver_msgs = {
        msg(lambda: sweep_parallel(env.values, grid.budgets, grid.rules,
                                   driver="mpi")),
        msg(lambda: engine.sweep(engine.grid(bid_scales=[1.0]),
                                 driver="mpi")),
    }
    assert len(driver_msgs) == 1
    assert "unknown sweep driver: 'mpi'" in driver_msgs.pop()

    resolve_msgs = {
        msg(lambda: sweep_parallel(env.values, grid.budgets, grid.rules,
                                   resolve="cuda")),
        msg(lambda: sweep_state_machine(env.values, grid.budgets,
                                        grid.rules, resolve="cuda")),
        msg(lambda: parallel_simulate(env.values, env.budgets,
                                      AuctionRule.first_price(N_CAMPAIGNS),
                                      driver="device", resolve="cuda")),
    }
    assert len(resolve_msgs) == 1
    assert "unknown resolve back-end: 'cuda'" in resolve_msgs.pop()

    assert "unknown driver: 'mpi'" in msg(
        lambda: parallel_simulate(env.values, env.budgets,
                                  AuctionRule.first_price(N_CAMPAIGNS),
                                  driver="mpi"))


# ---------------------------------------------------------------------------
# scenario-chunked execution (the S-axis analogue of chunks=)
# ---------------------------------------------------------------------------

ALIGNED_SCENARIO_CHUNKS = (1, 2, 4, 8)        # _grid has S = 4 bids x 2 res


def test_scenario_chunked_bitwise_aligned_sizes(env):
    """Scenario-chunked execution (lax.map over fixed S-slices of the grid)
    is bit-for-bit the unchunked batched driver on EVERY loop output, for
    every aligned chunk size, on the jnp and fused back-ends — lanes never
    exchange data, so slicing the S axis cannot move a single bit."""
    grid = _grid(env, "first_price")
    ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="jnp")
    names = ("final_spend", "cap_times", "retired", "boundaries",
             "num_rounds", "n_hat")
    for resolve, interpret in (("jnp", None), ("fused", True)):
        for spc in ALIGNED_SCENARIO_CHUNKS:
            out = sweep_state_machine(env.values, grid.budgets, grid.rules,
                                      resolve=resolve, interpret=interpret,
                                      scenario_chunks=spc)
            for name, a, b in zip(names, out, ref):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"scenario_chunks={spc} resolve={resolve}: "
                            f"{name}")


def test_scenario_chunks_compose_with_event_chunks(env):
    """Both chunk axes at once (scan S-slices, each streaming event chunks)
    through the public wrappers: sweep_parallel and engine.sweep still
    reproduce the unchunked bits."""
    from repro.core import ScenarioChunkSpec
    grid = _grid(env, "second_price")
    ref = sweep_parallel(env.values, grid.budgets, grid.rules)
    out = sweep_parallel(env.values, grid.budgets, grid.rules, chunks=512,
                         scenario_chunks=ScenarioChunkSpec(
                             scenarios_per_chunk=2))
    np.testing.assert_array_equal(np.asarray(out.final_spend),
                                  np.asarray(ref.final_spend))
    np.testing.assert_array_equal(np.asarray(out.cap_times),
                                  np.asarray(ref.cap_times))
    engine = CounterfactualEngine(env.values, env.budgets)
    egrid = engine.grid(bid_scales=[1.0, 1.1, 1.2])
    np.testing.assert_array_equal(
        np.asarray(engine.sweep(egrid, chunks=256,
                                scenario_chunks=3).results.final_spend),
        np.asarray(engine.sweep(egrid).results.final_spend))


def test_scenario_chunked_sharded_1dev_bitwise(env):
    """scenario_chunks × driver="sharded" on the trivial mesh (the 4-device
    half runs in test_scenario_chunked_sharded_4dev_bitwise): each device
    slice scans its own scenario chunks, still the in-memory bits."""
    grid = _grid(env, "first_price")
    ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="jnp")
    spec = SweepMeshSpec.for_devices(num_event_devices=1)
    out = sweep_sharded(env.values, grid.budgets, grid.rules, spec,
                        scenario_chunks=4, chunks=512)
    for name, a, b in zip(("final_spend", "cap_times", "retired",
                           "boundaries", "num_rounds", "n_hat"), out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.slow
def test_scenario_chunked_sharded_4dev_bitwise():
    """Acceptance: scenario-chunked == unchunked, bit-for-bit, at 4 forced
    host devices — on the all-event mesh (S vmapped per device) AND the
    2×2 event×scenario mesh (chunk sizes dividing the per-device S)."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        assert len(jax.devices()) == 4
        from repro.core import AuctionRule, ScenarioGrid, sweep_parallel
        from repro.data import make_synthetic_env
        from repro.launch.mesh import SweepMeshSpec
        env = make_synthetic_env(jax.random.PRNGKey(1), n_events=4096,
                                 n_campaigns=16, emb_dim=8)
        base = AuctionRule.first_price(16)
        grid = ScenarioGrid.product(base, env.budgets,
                                    bid_scales=[1.0, 1.2],
                                    budget_scales=[1.0, 0.25, 1e6])
        ref = sweep_parallel(env.values, grid.budgets, grid.rules)
        cells = [(SweepMeshSpec.for_devices(num_event_devices=4),
                  (1, 2, 3, 6), None),          # S=6 vmapped per device
                 (SweepMeshSpec.for_devices(2, 2),
                  (1, 3), 512)]                 # local S=3, + event chunks
        for spec, spcs, epc in cells:
            for spc in spcs:
                out = sweep_parallel(env.values, grid.budgets, grid.rules,
                                     driver="sharded", mesh=spec,
                                     chunks=epc, scenario_chunks=spc)
                assert np.array_equal(np.asarray(out.final_spend),
                                      np.asarray(ref.final_spend)), spc
                assert np.array_equal(np.asarray(out.cap_times),
                                      np.asarray(ref.cap_times)), spc
        print("SCENARIO_CHUNKED_SHARDED_4DEV_OK")
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # fake host devices, never a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SCENARIO_CHUNKED_SHARDED_4DEV_OK" in out.stdout


def test_misaligned_scenario_chunks_one_error_everywhere(env):
    """Satellite: the ONE pad-or-error contract on the S axis — every
    entry point (sweep_parallel, sweep_state_machine, engine.sweep,
    engine.search) raises the identical ValueError for a chunk size that
    does not divide the scenario count (the executor owns validation)."""
    from repro.search import SearchSpace
    grid = _grid(env, "first_price")              # S = 8; 3 is ragged
    engine = CounterfactualEngine(env.values, env.budgets)

    def msg(fn):
        with pytest.raises(ValueError) as e:
            fn()
        return str(e.value)

    msgs = {
        msg(lambda: sweep_parallel(env.values, grid.budgets, grid.rules,
                                   scenario_chunks=3)),
        msg(lambda: sweep_state_machine(env.values, grid.budgets,
                                        grid.rules, scenario_chunks=3)),
        msg(lambda: engine.sweep(engine.grid(bid_scales=[1.0, 0.9, 1.1, 1.3],
                                             reserves=[0.0, 0.05]),
                                 scenario_chunks=3)),
        # halving's first rung evaluates num_candidates=8 points at once
        msg(lambda: engine.search(SearchSpace(reserve=(0.0, 0.2)),
                                  method="halving", num_candidates=8,
                                  scenario_chunks=3)),
    }
    assert len(msgs) == 1, msgs
    assert "ragged scenario chunk" in next(iter(msgs))
    with pytest.raises(ValueError, match="scenarios_per_chunk"):
        sweep_parallel(env.values, grid.budgets, grid.rules,
                       scenario_chunks=0)


def test_vmem_gate_picks_fitting_scenario_chunk(env, monkeypatch):
    """Satellite regression: past the one-launch VMEM budget the executor
    now CHOOSES a fitting scenario chunk (largest divisor whose resident
    state fits) instead of silently degrading to the two-pass shape — at
    the documented S=64/C=1024 point and, with a shrunk budget, on a real
    run that must stay bit-identical to the unchunked fused kernel."""
    from repro.core import executor
    from repro.core.executor import SweepPlan, planned_scenario_chunk

    # docs/ALGORITHMS.md case: S=64 over-fills VMEM at C=1024, S=32 fits
    plan = SweepPlan(resolve="fused", interpret=True)
    assert not executor.round_fused_fits(64, 1024)
    assert planned_scenario_chunk(plan, 64, 1024) == 32
    # an explicit spec always wins over the auto gate
    assert planned_scenario_chunk(
        SweepPlan(resolve="fused", interpret=True, scenario_chunks=16),
        64, 1024) == 16

    grid = _grid(env, "first_price")              # S=8, C=16
    ref = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="fused", interpret=True, block_t=64)
    # budget where S=8 resident state over-fills but an S-slice fits
    fits8 = executor.round_fused_bytes(8, N_CAMPAIGNS, 64)
    fits1 = executor.round_fused_bytes(1, N_CAMPAIGNS, 64)
    monkeypatch.setattr(executor, "ONE_LAUNCH_VMEM_BYTES",
                        (fits8 + fits1) // 2)
    auto = planned_scenario_chunk(
        SweepPlan(resolve="fused", interpret=True, block_t=64), 8,
        N_CAMPAIGNS)
    assert auto is not None and auto < 8 and 8 % auto == 0
    assert executor.round_fused_fits(auto, N_CAMPAIGNS, 64)
    sweep_state_machine.clear_cache()   # same statics must re-plan
    out = sweep_state_machine(env.values, grid.budgets, grid.rules,
                              resolve="fused", interpret=True, block_t=64)
    for name, a, b in zip(("final_spend", "cap_times", "retired",
                           "boundaries", "num_rounds", "n_hat"), out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# engine-level API
# ---------------------------------------------------------------------------

def test_engine_sweep_and_delta_table(env):
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=[1.0, 1.1], reserves=[0.0, 0.02],
                       budget_scales=[1.0, 0.5])
    sweep = engine.sweep(grid, method="parallel")
    rows = sweep.delta_table()
    assert len(rows) == grid.num_scenarios == 8
    assert rows[0]["revenue_lift"] == 0.0          # base vs itself
    assert rows[0]["spend_delta"] == 0.0
    # halving budgets must not increase spend
    by_label = {r["scenario"]: r for r in rows}
    for bid, res in [(1.0, 0.0), (1.1, 0.02)]:
        full = by_label[f"bid×{bid:g} res={res:g} bud×1"]
        half = by_label[f"bid×{bid:g} res={res:g} bud×0.5"]
        assert half["spend_total"] <= full["spend_total"] + 1e-3
    assert len(sweep.format_delta_table().splitlines()) == \
        grid.num_scenarios + 2


def test_engine_sweep_sort2aggregate_warm_start(env):
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=[1.0, 1.15])
    sweep = engine.sweep(grid, method="sort2aggregate")
    assert sweep.consistency_gaps is not None
    base = sweep.results.scenario(0)
    ref = sequential_replay(env.values, env.budgets, engine.base_rule,
                            record_events=False)
    err = float(spend_weighted_relative_error(base.final_spend,
                                              ref.final_spend))
    assert err < ORACLE_TOL


def test_engine_sweep_per_scenario_warm_start(env):
    """warm_start="per_scenario": Algorithm 4 vmapped over the grid seeds
    every scenario from its OWN design; accuracy stays within the oracle
    tolerance and the per-scenario refine-iteration counts are reported."""
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=[1.0, 1.3], budget_scales=[1.0, 0.4])
    sweep = engine.sweep(grid, method="sort2aggregate",
                         warm_start="per_scenario")
    assert sweep.refine_iters is not None
    assert sweep.refine_iters.shape == (grid.num_scenarios,)
    assert sweep.consistency_gaps.shape == (grid.num_scenarios,)
    for s in range(grid.num_scenarios):
        rule, budgets = grid.scenario(s)
        ref = sequential_replay(env.values, budgets, rule,
                                record_events=False)
        err = float(spend_weighted_relative_error(
            sweep.results.final_spend[s], ref.final_spend))
        assert err < ORACLE_TOL, (grid.labels[s], err)


def test_engine_sweep_warm_start_modes(env):
    """True aliases "base", False is a cold start, junk raises; all report
    refine_iters."""
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=[1.0, 1.1])
    legacy = engine.sweep(grid, method="sort2aggregate", warm_start=True)
    base = engine.sweep(grid, method="sort2aggregate", warm_start="base")
    np.testing.assert_array_equal(np.asarray(legacy.results.cap_times),
                                  np.asarray(base.results.cap_times))
    cold = engine.sweep(grid, method="sort2aggregate", warm_start=False)
    assert cold.refine_iters is not None
    # the cold start pays refine iterations the converged base seed skips
    assert int(cold.refine_iters[0]) >= int(base.refine_iters[0])
    with pytest.raises(ValueError, match="warm_start"):
        engine.sweep(grid, method="sort2aggregate", warm_start="yesterday")


def test_estimate_pi_sweep_matches_per_scenario_estimates(env):
    """The vmapped VI is estimate_pi per scenario with shared draws: each
    lane must equal the single-scenario call with the same key."""
    from repro.core import estimate_pi, estimate_pi_sweep
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=[1.0, 1.4])
    key = jax.random.PRNGKey(7)
    est = estimate_pi_sweep(env.values, grid.budgets, grid.rules, key,
                            sample_size=128, num_iters=10, batch_size=32)
    assert est.pi.shape == (grid.num_scenarios, N_CAMPAIGNS)
    for s in range(grid.num_scenarios):
        rule, budgets = grid.scenario(s)
        solo = estimate_pi(env.values, budgets, rule, key, sample_size=128,
                           num_iters=10, batch_size=32)
        np.testing.assert_allclose(np.asarray(est.pi[s]),
                                   np.asarray(solo.pi), rtol=1e-6, atol=1e-6)


def test_stack_rules_rejects_mixed_kinds():
    with pytest.raises(ValueError):
        stack_rules([AuctionRule.first_price(4),
                     AuctionRule.second_price(4)])


def test_sweep_rejects_unbatched_inputs(env):
    with pytest.raises(ValueError):
        sweep_parallel(env.values, env.budgets,
                       AuctionRule.first_price(N_CAMPAIGNS))
