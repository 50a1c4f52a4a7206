"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret=True)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.auction_resolve import (auction_resolve,
                                           auction_resolve_ref, block_tiles,
                                           fused_partials_ref, round_fused,
                                           round_fused_ref, round_fused_tiles,
                                           sweep_partials,
                                           sweep_partials_tiles,
                                           sweep_resolve, sweep_resolve_ref)
from repro.kernels.capped_scan import capped_scan, capped_scan_ref

# the module, which the package's ``round_fused`` (the jitted entry) hides
round_fused_kernels = importlib.import_module(
    "repro.kernels.auction_resolve.round_fused")


@pytest.mark.parametrize("n,c,d,sp,per_event", [
    (512, 40, 10, False, False),
    (500, 100, 16, True, False),     # ragged N, second price
    (300, 33, 8, False, True),       # ragged everything, per-event mask
    (1024, 128, 128, True, True),    # MXU-aligned
    (256, 7, 4, False, False),       # tiny C
])
def test_auction_resolve_matches_ref(n, c, d, sp, per_event):
    key = jax.random.PRNGKey(n + c)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    e = jax.random.normal(k1, (n, d))
    r = jax.random.normal(k2, (c, d))
    mult = jnp.exp(jax.random.normal(k3, (c,)) * 0.1)
    act = jax.random.bernoulli(k4, 0.8, (n, c) if per_event else (c,))
    res = jnp.float32(0.02)
    w1, p1, s1 = auction_resolve(e, r, mult, act, res, second_price=sp)
    w2, p2, s2 = auction_resolve_ref(e, r, mult, act, res, second_price=sp)
    assert np.array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_auction_resolve_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    e = jax.random.normal(k1, (256, 16), dtype)
    r = jax.random.normal(k2, (32, 16), dtype)
    mult = jnp.ones((32,), jnp.float32)
    act = jnp.ones((32,), bool)
    w1, p1, s1 = auction_resolve(e, r, mult, act)
    w2, p2, s2 = auction_resolve_ref(e, r, mult, act, jnp.float32(0.0))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("s,n,c,sp,per_event,blk", [
    (1, 512, 40, False, False, 256),
    (8, 500, 33, True, False, 128),      # ragged N and C, second price
    (4, 300, 17, True, True, 128),       # ragged everything, per-event mask
    (8, 1000, 100, False, True, 256),    # per-event mask, first price
    (32, 256, 128, False, False, 128),   # wide scenario batch, aligned C
    (3, 384, 7, True, False, 128),       # tiny C
])
def test_sweep_resolve_matches_ref(s, n, c, sp, per_event, blk):
    """Interpret-mode parity of the scenario-batched kernel vs its oracle."""
    key = jax.random.PRNGKey(s * 1000 + n + c)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    v = jax.random.uniform(k1, (n, c))
    mult = jnp.exp(jax.random.normal(k2, (s, c)) * 0.1)
    act = jax.random.bernoulli(k3, 0.8, (s, n, c) if per_event else (s, c))
    res = jax.random.uniform(k4, (s,), maxval=0.1)
    w1, p1, s1 = sweep_resolve(v, mult, act, res, second_price=sp,
                               block_t=blk, interpret=True)
    w2, p2, s2 = sweep_resolve_ref(v, mult, act, res, second_price=sp)
    assert np.array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("kind", ["first_price", "second_price"])
def test_sweep_resolve_bitwise_vs_core_resolve(kind):
    """The contract the sweep state machine relies on: winners exact, prices
    bit-identical to the vmapped ``repro.core.auction.resolve`` path."""
    from repro.core import AuctionRule, auction
    key = jax.random.PRNGKey(11)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s, n, c = 6, 1000, 33
    v = jax.random.uniform(k1, (n, c))
    mult = jnp.exp(jax.random.normal(k2, (s, c)) * 0.1)
    act = jax.random.bernoulli(k3, 0.7, (s, c))
    res = jax.random.uniform(k4, (s,), maxval=0.1)
    rules = AuctionRule(multipliers=mult, reserve=res, kind=kind)
    w_ref, p_ref = jax.vmap(
        lambda a, r: auction.resolve(v, a, r), in_axes=(0, 0))(act, rules)
    w, p, _ = sweep_resolve(v, mult, act, res,
                            second_price=(kind == "second_price"),
                            block_t=128, interpret=True)
    assert np.array_equal(np.asarray(w), np.asarray(w_ref))
    assert np.array_equal(np.asarray(p), np.asarray(p_ref))


def test_sweep_resolve_single_scenario_matches_tilewise():
    """S=1 batched resolve == per-scenario slice of an S=4 batch (the tile
    loop must not leak state across scenarios)."""
    key = jax.random.PRNGKey(7)
    k1, k2, k3 = jax.random.split(key, 3)
    n, c = 640, 24
    v = jax.random.uniform(k1, (n, c))
    mult = jnp.exp(jax.random.normal(k2, (4, c)) * 0.2)
    act = jax.random.bernoulli(k3, 0.75, (4, c))
    res = jnp.asarray([0.0, 0.02, 0.05, 0.01])
    wb, pb, sb = sweep_resolve(v, mult, act, res, second_price=True,
                               block_t=128, interpret=True)
    for i in range(4):
        w1, p1, s1 = sweep_resolve(v, mult[i:i + 1], act[i:i + 1],
                                   res[i:i + 1], second_price=True,
                                   block_t=128, interpret=True)
        assert np.array_equal(np.asarray(wb[i]), np.asarray(w1[0]))
        np.testing.assert_array_equal(np.asarray(pb[i]), np.asarray(p1[0]))
        np.testing.assert_allclose(np.asarray(sb[i]), np.asarray(s1[0]),
                                   rtol=1e-6)


def _fused_inputs(s, n, c, seed=0):
    key = jax.random.PRNGKey(seed + s * 1000 + n + c)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    v = jax.random.uniform(k1, (n, c))
    mult = jnp.exp(jax.random.normal(k2, (s, c)) * 0.1)
    act = jax.random.bernoulli(k3, 0.8, (s, c))
    res = jax.random.uniform(k4, (s,), maxval=0.05)
    b = jax.random.uniform(k5, (s, c), minval=2.0, maxval=20.0)
    s_hat = jnp.zeros((s, c), jnp.float32)
    n_hat = (jnp.arange(s, dtype=jnp.int32) * (n // (2 * s)))
    return v, mult, act, res, b, s_hat, n_hat


@pytest.mark.parametrize("s,n,c,sp,blk", [
    (1, 512, 40, False, 256),
    (5, 1000, 33, True, 128),        # ragged N and C
    (8, 768, 17, False, 128),
    (4, 300, 7, True, 128),          # N < canonical grid coverage
])
def test_round_fused_matches_ref(s, n, c, sp, blk):
    """Interpret-mode parity of the one-pass fused round vs its jnp oracle:
    same canonical partials, same cap-out predictions."""
    v, mult, act, res, b, s_hat, n_hat = _fused_inputs(s, n, c)
    block_size = -(-n // 32)
    rp1, bp1, cn1, nc1, nn1 = round_fused(
        v, mult, act, res, b, s_hat, n_hat, jnp.ones((s,), bool),
        reduce_blocks=32, second_price=sp, block_t=blk, interpret=True)
    rp2, bp2, cn2, nc2, nn2 = round_fused_ref(
        v, mult, act, res, b, s_hat, n_hat, block_size=block_size,
        reduce_blocks=32, second_price=sp)
    np.testing.assert_allclose(np.asarray(rp1), np.asarray(rp2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(bp1), np.asarray(bp2),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(cn1), np.asarray(cn2))
    assert np.array_equal(np.asarray(nc1), np.asarray(nc2))
    assert np.array_equal(np.asarray(nn1), np.asarray(nn2))


def test_round_fused_skip_retired_leaves_live_lanes_untouched():
    """Predicating retired lanes off must not change any live lane's outputs
    (frozen lanes' rows are whatever the zero-init left — discarded by the
    drivers)."""
    s, n, c = 6, 640, 24
    v, mult, act, res, b, s_hat, n_hat = _fused_inputs(s, n, c, seed=3)
    alive = jnp.asarray([True, False, True, True, False, True])
    out_skip = round_fused(v, mult, act, res, b, s_hat, n_hat, alive,
                           reduce_blocks=32, skip_retired=True,
                           block_t=128, interpret=True)
    out_full = round_fused(v, mult, act, res, b, s_hat, n_hat, alive,
                           reduce_blocks=32, skip_retired=False,
                           block_t=128, interpret=True)
    live = np.asarray(alive)
    for a, bb in zip(out_skip, out_full):
        np.testing.assert_array_equal(np.asarray(a)[live],
                                      np.asarray(bb)[live])
    # skipped lanes did no tile work: their partials rows stayed zero
    assert float(np.abs(np.asarray(out_skip[0])[~live]).max()) == 0.0
    assert float(np.abs(np.asarray(out_skip[1])[~live]).max()) == 0.0


@pytest.mark.parametrize("offset,ndev", [(0, 1), (512, 4), (1536, 4)])
def test_sweep_partials_matches_ref_with_offset(offset, ndev):
    """The sharded fused pass: a shard's partials land on the GLOBAL
    canonical grid exactly as the oracle's (the psum-operand contract)."""
    s, n_global, c = 4, 2048, 20
    local_n = n_global // ndev
    v, mult, act, res, b, s_hat, n_hat = _fused_inputs(s, n_global, c)
    v_local = v[offset:offset + local_n]
    lo = n_hat
    hi = jnp.full_like(n_hat, n_global)
    block_size = -(-n_global // 32)
    parts_k = sweep_partials(
        v_local, mult, act, res, lo, hi, jnp.ones((s,), bool),
        jnp.int32(offset), n_events_global=n_global, reduce_blocks=32,
        block_t=256, interpret=True)
    parts_r = fused_partials_ref(
        v_local, mult, act, res, lo, hi, block_size=block_size,
        reduce_blocks=32, index_offset=offset)
    np.testing.assert_allclose(np.asarray(parts_k), np.asarray(parts_r),
                               rtol=1e-5, atol=1e-5)
    # rows outside the shard's canonical blocks are exact zeros
    g_lo, g_hi = offset // block_size, (offset + local_n - 1) // block_size
    outside = np.ones(32, bool)
    outside[g_lo:g_hi + 1] = False
    if outside.any():
        assert float(np.abs(np.asarray(parts_k)[:, outside]).max()) == 0.0


@pytest.mark.parametrize("s,n,c,sp,blk", [
    (1, 512, 40, False, 256),
    (5, 1000, 33, True, 128),        # ragged N and C
    (4, 300, 7, False, 64),          # blocks smaller than a tile
])
def test_round_fused_tiles_is_the_values_entry(s, n, c, sp, blk):
    """The tile entry a round loop calls, given ``block_tiles`` output, is
    bitwise the values entry that lays the log out on every call."""
    v, mult, act, res, b, s_hat, n_hat = _fused_inputs(s, n, c, seed=5)
    alive = jnp.arange(s) % 3 != 1
    tiles, t, tpb = block_tiles(v, block_size=-(-n // 32), block_t=blk)
    got = round_fused_tiles(tiles, mult, act, res, b, s_hat, n_hat, alive,
                            n_events=n, t=t, tiles_per_block=tpb,
                            reduce_blocks=32, second_price=sp,
                            interpret=True)
    want = round_fused(v, mult, act, res, b, s_hat, n_hat, alive,
                       reduce_blocks=32, second_price=sp, block_t=blk,
                       interpret=True)
    for a, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


@pytest.fixture
def poison_zero_weight_steps():
    """Calling it makes every fused-kernel grid step that does tile work
    with every weight 0 — a tile holding no row of its lane's window —
    add NaN to the partials. (A log value cannot show such a step: a
    weight of 0 multiplies the spends, and XLA folds that product into a
    select, so even an infinite price adds 0.)"""
    original = round_fused_kernels._tile_partials

    def poisoned(v, mult, reserve, act, weight, g, **kw):
        part = original(v, mult, reserve, act, weight, g, **kw)
        return jnp.where(jnp.any(weight != 0), part, jnp.nan)

    def poison():
        round_fused_kernels._tile_partials = poisoned
        jax.clear_caches()

    yield poison
    round_fused_kernels._tile_partials = original
    jax.clear_caches()


def _budgets_capping_at(v, mult, act, res, b, s_hat, n_hat, lane, row, *,
                        block_size):
    """Budgets under which ``lane``'s next cap-out is predicted at ``row``:
    its top-spending campaign's budget sits half an event past ``row`` at
    the lane's remaining rate, every other budget is out of reach."""
    n = v.shape[0]
    rate_parts = round_fused_ref(v, mult, act, res, b, s_hat, n_hat,
                                 block_size=block_size, reduce_blocks=32)[0]
    sums = np.asarray(rate_parts[lane]).sum(axis=0)
    top = int(np.argmax(sums))
    nh = int(n_hat[lane])
    b = np.full(b.shape, 1e9, np.float32)
    b[lane, top] = float(s_hat[lane, top]) \
        + (row - nh + 0.5) * sums[top] / (n - nh)
    return jnp.asarray(b)


@pytest.mark.parametrize("n,blk,n_hat,alive,n_next_at", [
    (3200, 64, [364, 500, 1500], [1, 1, 1], None),    # on a tile boundary
    (3200, 64, [330, 1250, 0], [1, 1, 0], None),      # mid-tile; a retired
    #                                                   lane at row 0
    (3200, 64, [395, 450, 2000], [1, 1, 1], None),    # in a block's last
    #                                                   tile, before padding
    (3200, 64, [400, 617, 3000], [1, 1, 1], None),    # a canonical block
    (3200, 64, [3200, 3200, 3200], [1, 1, 1], None),  # empty windows
    (3200, 64, [364, 500, 1500], [1, 1, 1], 564),     # phase 1's n_next at
    #                                                   a tile's first row
    (300, 64, [37, 150, 299], [1, 1, 1], None),       # blocks under a tile
], ids=["tile_boundary", "mid_tile", "block_last_tile", "block_boundary",
        "empty", "n_next_at_tile", "small_blocks"])
def test_round_fused_skips_steps_outside_every_window(
        poison_zero_weight_steps, n, blk, n_hat, alive, n_next_at):
    """The fused round does tile work only where the tile holds a row of
    its alive lane's window: with every other step poisoned, the outputs
    are finite and bitwise the unpoisoned kernel's, which are the
    oracle's."""
    s, c = len(n_hat), 40
    v, mult, act, res, b, s_hat, _ = _fused_inputs(s, n, c, seed=9)
    n_hat = jnp.asarray(n_hat, jnp.int32)
    alive = jnp.asarray(alive, bool)
    block_size = -(-n // 32)
    if n_next_at is not None:
        b = _budgets_capping_at(v, mult, act, res, b, s_hat, n_hat, 0,
                                n_next_at, block_size=block_size)

    def run():
        return [np.asarray(x) for x in round_fused(
            v, mult, act, res, b, s_hat, n_hat, alive, reduce_blocks=32,
            block_t=blk, interpret=True)]

    clean = run()
    poison_zero_weight_steps()
    for got, want in zip(run(), clean):
        assert np.isfinite(got.astype(np.float64)).all()
        np.testing.assert_array_equal(got, want)
    if n_next_at is not None:
        assert int(clean[4][0]) == n_next_at
    live = np.asarray(alive)
    ref = round_fused_ref(v, mult, act, res, b, s_hat, n_hat,
                          block_size=block_size, reduce_blocks=32)
    for k in (0, 1):
        np.testing.assert_allclose(clean[k][live], np.asarray(ref[k])[live],
                                   rtol=1e-5, atol=1e-5)
    for k in (2, 3, 4):
        assert np.array_equal(clean[k][live], np.asarray(ref[k])[live])


@pytest.mark.parametrize("offset,local_n,blk,lo,hi,alive", [
    (0, 2048, 16, [528, 700, 1300], [1500, 1200, 1700], [1, 1, 1]),
    #                                  the whole log; on a tile boundary
    (512, 512, 16, [600, 650, 0], [900, 1000, 2048], [1, 1, 0]),
    #                                  a shard; mid-tile; a retired lane
    (1536, 512, 64, [1600, 1700, 1800], [2048] * 3, [1, 1, 1]),
    #                                  a shard; on a canonical block
    (512, 512, 16, [1024, 1100, 2000], [2048] * 3, [1, 1, 1]),
    #                                  a shard wholly behind lo
    (100, 300, 16, [150, 170, 390], [300, 395, 400], [1, 1, 1]),
    #                                  a resumable fold: starts mid-block
    (0, 2048, 16, [1000] * 3, [1000] * 3, [1, 1, 1]),
    #                                  empty windows
], ids=["tile_boundary", "mid_tile", "block_boundary", "shard_behind_lo",
        "mid_block_fold", "empty"])
def test_sweep_partials_skips_steps_outside_every_window(
        poison_zero_weight_steps, offset, local_n, blk, lo, hi, alive):
    """The weighted partials pass does tile work only where the tile holds
    a row of its alive lane's window within the slice: with every other
    step poisoned, the partials are finite and bitwise the unpoisoned
    kernel's, which are the oracle's."""
    s, n_global, c = len(lo), 2048, 20
    v, mult, act, res, _, _, _ = _fused_inputs(s, n_global, c)
    v_local = v[offset:offset + local_n]
    block_size = -(-n_global // 32)
    lo, hi = jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32)
    alive = jnp.asarray(alive, bool)

    def run():
        return np.asarray(sweep_partials(
            v_local, mult, act, res, lo, hi, alive, jnp.int32(offset),
            n_events_global=n_global, reduce_blocks=32,
            offset_in_block=offset % block_size, block_t=blk,
            interpret=True))

    clean = run()
    poison_zero_weight_steps()
    got = run()
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    live = np.asarray(alive)
    ref = fused_partials_ref(v_local, mult, act, res, lo, hi,
                             block_size=block_size, reduce_blocks=32,
                             index_offset=offset)
    np.testing.assert_allclose(clean[live], np.asarray(ref)[live],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("offset,local_n,blk", [
    (0, 2048, 256),                  # the whole log, one device
    (512, 512, 128),                 # a shard on block boundaries
    (100, 300, 64),                  # a resumable fold: starts mid-block
    (1900, 148, 256),                # a fold's tail, mid-block to the end
])
def test_sweep_partials_tiles_is_the_values_entry(offset, local_n, blk):
    """The tile entry, given ``block_tiles`` output at the slice's
    ``offset_in_block``, is bitwise the values entry, and both place the
    slice's partials on the global grid as the oracle does."""
    s, n_global, c = 4, 2048, 20
    v, mult, act, res, b, s_hat, n_hat = _fused_inputs(s, n_global, c)
    v_local = v[offset:offset + local_n]
    block_size = -(-n_global // 32)
    lo, hi = n_hat, jnp.full_like(n_hat, n_global)
    alive = jnp.ones((s,), bool)
    tiles, t, tpb = block_tiles(v_local, block_size=block_size, block_t=blk,
                                offset_in_block=offset % block_size)
    got = sweep_partials_tiles(
        tiles, mult, act, res, lo, hi, alive, jnp.int32(offset),
        n_rows=local_n, n_events_global=n_global, t=t, tiles_per_block=tpb,
        reduce_blocks=32, interpret=True)
    want = sweep_partials(
        v_local, mult, act, res, lo, hi, alive, jnp.int32(offset),
        n_events_global=n_global, reduce_blocks=32,
        offset_in_block=offset % block_size, block_t=blk, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ref = fused_partials_ref(v_local, mult, act, res, lo, hi,
                             block_size=block_size, reduce_blocks=32,
                             index_offset=offset)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("n,c,blk", [
    (1024, 40, 256), (1000, 33, 128), (2048, 128, 512), (640, 5, 64),
])
def test_capped_scan_matches_ref(n, c, blk):
    key = jax.random.fold_in(jax.random.PRNGKey(0), n)
    k1, k2 = jax.random.split(key)
    v = jax.random.uniform(k1, (n, c))
    budgets = jax.random.uniform(k2, (c,), minval=1.0, maxval=30.0)
    w1, p1, s1, c1 = capped_scan(v, budgets, block_t=blk)
    w2, p2, s2, c2 = capped_scan_ref(v, budgets, jnp.ones((c,)),
                                     jnp.float32(0.0))
    assert np.array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4)
    assert np.array_equal(np.asarray(c1), np.asarray(c2))


def test_capped_scan_equals_core_oracle():
    """The kernel is an exact implementation of core.sequential_replay."""
    from repro.core import sequential_replay
    from repro.data import make_synthetic_env
    env = make_synthetic_env(jax.random.PRNGKey(5), n_events=2048,
                             n_campaigns=24, emb_dim=8)
    ref = sequential_replay(env.values, env.budgets, env.rule)
    w, p, s, cap = capped_scan(env.values, env.budgets)
    assert np.array_equal(np.asarray(w), np.asarray(ref.winners))
    np.testing.assert_allclose(np.asarray(s), np.asarray(ref.final_spend),
                               rtol=1e-4)
    assert np.array_equal(np.asarray(cap), np.asarray(ref.cap_times))
