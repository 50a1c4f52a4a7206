"""Event-sharded (multi-device) drivers for the paper's algorithms.

This is the TPU realization of the paper's MapReduce framing: the event log is
sharded along the mesh's event axes (``("data",)`` per pod, ``("pod","data")``
across pods); campaign state (pi, spends, budgets — all O(|C|)) is replicated.
Every algorithm below is the single-process version with its reductions
replaced by ``psum`` over the event axes:

* :func:`make_sharded_kernels` — map + all-reduce closures for the
  single-scenario Algorithm-2 host driver;
* :func:`sweep_sharded` — the mesh-batched scenario sweep: the whole batched
  Algorithm-2 ``while_loop`` runs under ``shard_map``, events sharded,
  scenarios vmapped per device or sharded along a second mesh axis
  (:class:`repro.launch.mesh.SweepMeshSpec`). It is a thin wrapper over the
  unified executor layer (``placement="sharded"`` of
  :mod:`repro.core.executor`, which builds the per-round resolve+reduce
  closures for every placement from one round body — see
  docs/ARCHITECTURE.md), and composes with event-chunked streaming
  (``chunks=``: each device scans its shard in fixed chunks per round);
* :func:`sharded_aggregate` — SORT2AGGREGATE Step 3 (one pass, one psum);
* :func:`sharded_first_crossing` / :func:`sweep_first_crossing_sharded` —
  two-pass distributed prefix: per-device partial sums are all-gathered
  (exclusive prefix), then each device scans its local block with the correct
  starting state;
* :func:`sweep_sort2aggregate_sharded` — the SORT2AGGREGATE scenario sweep
  (refine + aggregate) with both passes sharded;
* :func:`estimate_pi_sharded` — Algorithm 4 with the residual averaged across
  all devices each step (global-batch stochastic iteration); pi stays
  replicated because every device applies the identical psum'd update.

**``event_axes`` ordering contract.** Every function takes the event mesh
axes as an *ordered* sequence: a device's shard covers the contiguous global
index range ``[rank * local_n, (rank + 1) * local_n)`` where ``rank`` is the
row-major rank over ``event_axes`` in the given order (first axis slowest,
exactly :func:`_global_offset`). ``shard_events`` places ``values`` with that
layout; passing the same axes in a different order silently permutes the
event log, so callers must use one ordering end-to-end (``("data",)`` per
pod, ``("pod", "data")`` across pods).

All functions assume ``values`` is already placed (or placeable by jit) with
its event (leading) dimension sharded over ``event_axes`` and campaigns
replicated. The scenario-sweep entry points additionally keep bit-for-bit
agreement with the single-device drivers on any aligned mesh — see
docs/SCALING.md for the determinism model and the per-round communication
cost.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import axis_size as compat_axis_size, shard_map
from repro.core import auction
from repro.core import segments as seg_lib
from repro.core.executor import (SweepPlan, as_chunk_spec,
                                 as_scenario_chunk_spec,
                                 check_sharded_shapes as _check_sweep_shapes,
                                 execute_sweep,
                                 global_event_offset as _global_offset)
from repro.core.types import AuctionRule, Segments, SimResult, never_capped
from repro.launch.mesh import SweepMeshSpec


def event_sharding(mesh: Mesh, event_axes: Sequence[str]) -> NamedSharding:
    """The sharding of a 1-D per-event array: split over ``event_axes`` (in
    the module's row-major ordering contract), replicated elsewhere."""
    return NamedSharding(mesh, P(tuple(event_axes)))


def shard_events(values: jax.Array, mesh: Mesh,
                 event_axes: Sequence[str] = ("data",)) -> jax.Array:
    """Place (N, C) values with events sharded, campaigns replicated."""
    return jax.device_put(
        values, NamedSharding(mesh, P(tuple(event_axes), None)))


def make_sharded_kernels(mesh: Mesh, rule: AuctionRule,
                         event_axes: Sequence[str] = ("data",)):
    """Build (rate_fn, block_fn) closures for the Algorithm-2 host driver.

    Each is a ``shard_map``-ped program: local masked resolve, canonical
    block partials (:func:`repro.core.segments.partial_spend_sums`), then
    one float32 psum — the only cross-device traffic per Algorithm-2 round.
    Using the canonical grid makes the psum exact on aligned meshes (shards
    holding whole blocks), so the host driver fed these closures matches the
    single-process drivers bit-for-bit, same as :func:`sweep_sharded` — see
    docs/SCALING.md.
    """
    axes = tuple(event_axes)
    spec_vals = P(axes, None)
    ndev = 1
    for ax in axes:
        ndev *= mesh.shape[ax]

    def _resolve_partials(values_local, active, weight_of):
        local_n, n_campaigns = values_local.shape
        n_events = local_n * ndev
        offset = _global_offset(axes, local_n)
        gidx = offset + jnp.arange(local_n, dtype=jnp.int32)
        winners, prices = auction.resolve(values_local, active, rule)
        parts = seg_lib.partial_spend_sums(
            winners, prices, n_campaigns, weight_of(gidx).astype(prices.dtype),
            block_size=seg_lib.reduce_block_size(n_events),
            index_offset=offset)
        return jax.lax.psum(parts, axes), n_events

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec_vals, P(), P()), out_specs=P())
    def _rate_kernel(values_local, active, lo):
        parts, n_events = _resolve_partials(values_local, active,
                                            lambda g: g >= lo)
        sums = seg_lib.sum_blocks(parts)
        denom = jnp.maximum(n_events - lo, 1).astype(sums.dtype)
        return sums / denom

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec_vals, P(), P(), P()), out_specs=P())
    def _block_kernel(values_local, active, lo, hi):
        parts, _ = _resolve_partials(values_local, active,
                                     lambda g: (g >= lo) & (g < hi))
        return seg_lib.sum_blocks(parts)

    rate_jit = jax.jit(_rate_kernel)
    block_jit = jax.jit(_block_kernel)

    def rate_fn(values):
        def f(active, lo):
            return rate_jit(values, active, jnp.int32(lo))
        return f

    def block_fn(values):
        def f(active, lo, hi):
            return block_jit(values, active, jnp.int32(lo), jnp.int32(hi))
        return f

    return rate_fn, block_fn


def sharded_aggregate(
    mesh: Mesh,
    values: jax.Array,            # sharded (N, C)
    segments: Segments,
    budgets: jax.Array,           # (C,) — replicated campaign state
    rule: AuctionRule,
    event_axes: Sequence[str] = ("data",),
) -> SimResult:
    """SORT2AGGREGATE Step 3 on the mesh: one parallel pass + one psum, plus
    the distributed first-crossing diagnosis (one all-gather of per-device
    partials).

    ``values`` must be event-sharded over ``event_axes`` (see the module's
    ordering contract); ``segments``/``budgets``/``rule`` are replicated —
    every device reconstructs each local event's activation mask from the
    global boundary table, so no per-event mask array ever crosses the
    interconnect. The returned ``SimResult`` carries the psum'd (C,) spends
    and the pmin'd diagnosed cap times; ``winners``/``prices`` stay ``None``
    (materialising them would be an (N,)-sized gather).
    """
    axes = tuple(event_axes)
    n_events, n_campaigns = values.shape
    boundaries, masks = segments.boundaries, segments.masks

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axes, None), P(), P(), P()), out_specs=(P(), P()))
    def _agg(values_local, bnds, msks, b):
        local_n = values_local.shape[0]
        offset = _global_offset(axes, local_n)
        gidx = offset + jnp.arange(local_n, dtype=jnp.int32)
        seg_ids = jnp.searchsorted(bnds[1:-1], gidx, side="right").astype(jnp.int32)
        act = msks[seg_ids]
        winners, prices = auction.resolve(values_local, act, rule)
        local_sum = auction.spend_sums(winners, prices, n_campaigns)
        total = jax.lax.psum(local_sum, axes)
        cap = _local_first_crossing(winners, prices, local_sum, b,
                                    n_campaigns, offset, axes, n_events)
        return total, cap

    total, cap = jax.jit(_agg)(values, boundaries, masks, budgets)
    return SimResult(final_spend=total, cap_times=cap, winners=None,
                     prices=None, segments=segments)


def _local_first_crossing(winners, prices, local_sum, budgets, n_campaigns,
                          offset, axes, n_events):
    """Distributed budget-crossing detection (runs inside shard_map).

    Pass 1 (already done): local per-campaign sums. All-gather them to build
    each device's exclusive prefix; pass 2: local scan for the first crossing
    with that starting state. min-psum of candidate times gives the global
    first crossing.
    """
    local_n = winners.shape[0]
    # exclusive prefix of this device's events: sum of sums on devices before
    # this one in the row-major event order.
    all_sums = jax.lax.all_gather(local_sum, axes, tiled=False)  # (ndev, C)
    ndev = all_sums.shape[0]
    my_rank = offset // local_n
    before = (jnp.arange(ndev, dtype=jnp.int32) < my_rank).astype(local_sum.dtype)
    s0 = (all_sums * before[:, None]).sum(axis=0)
    # local cumulative + crossing search (blockwise to bound memory)
    sm = auction.spend_matrix(winners, prices, n_campaigns)
    cum = s0[None, :] + jnp.cumsum(sm, axis=0)
    crossed = cum >= budgets[None, :]
    any_cross = crossed.any(axis=0)
    t_first = jnp.argmax(crossed, axis=0)
    sentinel = jnp.int32(never_capped(n_events))
    cand = jnp.where(any_cross,
                     (offset + t_first + 1).astype(jnp.int32), sentinel)
    return jax.lax.pmin(cand, axes)


def sharded_first_crossing(mesh, values, segments, budgets, rule,
                           event_axes=("data",)):
    """Convenience wrapper returning only the cap times."""
    return sharded_aggregate(mesh, values, segments, budgets, rule,
                             event_axes).cap_times


def estimate_pi_sharded(
    mesh: Mesh,
    values: jax.Array,             # sharded (N, C) — full log; sampling is local
    budgets: jax.Array,
    rule: AuctionRule,
    key: jax.Array,
    *,
    num_iters: int = 200,
    local_batch: int = 64,
    eta: float = 0.5,
    eta_decay: float = 0.0,
    pi0: jax.Array | None = None,
    event_axes: Sequence[str] = ("data",),
    coupling: str = "shared",
) -> jax.Array:
    """Algorithm 4 at scale: every device contributes a local minibatch
    residual each step; one (C,)-psum per step; pi replicated.

    The per-event drift matches the paper's B=1 iteration: the update is
    ``eta * global_batch * (b/N - mean_spend)``.

    Argument semantics:

    * ``values`` — the FULL event-sharded (N, C) log; each device samples its
      minibatches from its own shard only (indices are local), so the
      stochastic iteration sees the global distribution through the psum'd
      residual, not through cross-device shuffling;
    * ``key`` — one PRNG key, replicated; every device folds in its row-major
      event-axis rank, so draws are device-distinct but reproducible for a
      fixed mesh shape (resharding the same log over a different device count
      changes the sample sequence and hence the returned pi);
    * ``num_iters`` / ``local_batch`` — iteration count and PER-DEVICE batch;
      the effective global batch is ``local_batch * num_devices``, and the
      update is scaled by it, so growing the mesh tightens the residual
      estimate without retuning ``eta``;
    * ``eta`` / ``eta_decay`` — step size ``eta / (1 + eta_decay * t)``;
    * ``pi0`` — optional warm start (defaults to all-ones = nobody capped);
    * ``coupling`` — ``"shared"`` draws ONE uniform per sampled event
      (campaign activations comonotone, the paper's default); ``"independent"``
      draws per-(event, campaign);
    * ``event_axes`` — ordering contract as per the module docstring.

    Returns the replicated (C,) pi estimate (identical on every device).
    """
    axes = tuple(event_axes)
    n_events, n_campaigns = values.shape
    btilde = budgets.astype(jnp.float32) / n_events
    pi_init = (jnp.ones((n_campaigns,), jnp.float32) if pi0 is None
               else pi0.astype(jnp.float32))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axes, None), P(), P()), out_specs=P())
    def _vi(values_local, pi0_in, key_in):
        local_n = values_local.shape[0]
        offset = _global_offset(axes, local_n)
        dev_key = jax.random.fold_in(key_in, offset)
        ndev = 1
        for ax in axes:
            ndev *= compat_axis_size(ax)
        global_batch = jnp.float32(local_batch * ndev)

        def body(carry, k):
            pi, step = carry
            k_idx, k_u = jax.random.split(k)
            rows = jax.random.randint(k_idx, (local_batch,), 0, local_n)
            vblock = values_local[rows]
            u_shape = ((local_batch, 1) if coupling == "shared"
                       else (local_batch, n_campaigns))
            u = jax.random.uniform(k_u, u_shape)
            active = u < pi[None, :]
            winners, prices = auction.resolve(vblock, active, rule)
            local_sum = auction.spend_sums(winners, prices, n_campaigns)
            mean_spend = jax.lax.psum(local_sum, axes) / global_batch
            eta_t = eta / (1.0 + eta_decay * step.astype(jnp.float32))
            pi = jnp.clip(pi + eta_t * global_batch * (btilde - mean_spend),
                          0.0, 1.0)
            return (pi, step + 1), None

        keys = jax.random.split(dev_key, num_iters)
        (pi, _), _ = jax.lax.scan(body, (pi0_in, jnp.int32(0)), keys)
        # identical on every device (same psum'd updates) — but the Bernoulli
        # draws differ per device only inside the residual, so assert via mean
        return jax.lax.pmean(pi, axes)

    return jax.jit(_vi)(values, pi_init, key)


# --------------------------------------------------------------------------
# Mesh-batched scenario sweep: the batched Algorithm-2 while_loop, sharded
# --------------------------------------------------------------------------

def sweep_sharded(
    values: jax.Array,            # (N, C) — events sharded over the mesh
    budgets: jax.Array,           # (S, C)
    rules: AuctionRule,           # batched: multipliers (S, C), reserve (S,)
    spec: SweepMeshSpec,
    resolve: str = "auto",
    block_t: int = 256,
    interpret: Optional[bool] = None,
    skip_retired: bool = True,
    chunks=None,                  # int | ChunkSpec — chunking × sharding
    scenario_chunks=None,         # int | ScenarioChunkSpec — S-axis chunks
):
    """The batched Algorithm-2 loop as ONE mesh program: events sharded over
    ``spec.event_axes``, campaign/scenario state replicated, the scenario
    axis vmapped per device or sharded over ``spec.scenario_axis``.

    This is the ``placement="sharded"`` cell of the executor layer
    (:mod:`repro.core.executor`, docs/ARCHITECTURE.md): the SAME round body
    as the single-device :func:`repro.core.sweep.sweep_state_machine`, run
    under ``shard_map``, with each reduction's canonical block partials
    produced from the local shard (placed on the global grid via the shard
    offset) and psum'd over the event axes — the round's only cross-device
    traffic, two (S_local, REDUCE_BLOCKS, C) float32 tensors. Unique block
    ownership makes the psum exact, so results are **bit-for-bit identical
    to the single-device sweep** on any mesh satisfying the alignment
    contract (shards hold whole canonical reduction blocks; checked, with a
    pad-or-error message, at trace time). See docs/SCALING.md.

    ``resolve="fused"`` swaps the resolve-once structure for two fused
    resolve+reduce kernel passes per round whose outputs ARE the psum
    operands (communication and bits unchanged); ``skip_retired`` passes
    the loop's per-lane alive flags into the kernel so frozen scenarios'
    grid steps are skipped (pure wall-clock). ``chunks`` composes chunking
    with sharding: each device scans its own shard's chunks before the
    psum, so the per-device working set is O(events_per_chunk · C) — still
    bit-for-bit, for chunk sizes aligned to the canonical grid within the
    shard. ``scenario_chunks`` scans each device's scenario lanes in fixed
    slices (chunk sizes must divide the per-device scenario count) — lanes
    are independent, so this too is bit-for-bit, and it composes with event
    chunking.

    Returns the same batched tuple as ``sweep_state_machine``:
    ``(s_hat (S, C), cap_times (S, C), retired (S, C+1), boundaries
    (S, C+2), num_rounds (S,), n_hat (S,))``, gathered across the scenario
    axis when one is meshed.
    """
    plan = SweepPlan(placement="sharded", mesh=spec, resolve=resolve,
                     block_t=block_t, interpret=interpret,
                     skip_retired=skip_retired,
                     chunks=as_chunk_spec(chunks),
                     scenario_chunks=as_scenario_chunk_spec(scenario_chunks))
    return execute_sweep(values, budgets, rules, plan)


# --------------------------------------------------------------------------
# Mesh-batched SORT2AGGREGATE sweep (Algorithm-3 with warm starts, sharded)
# --------------------------------------------------------------------------

def _batched_first_crossing(winners, prices, local_sums, budgets, offset,
                            axes, n_events, n_campaigns):
    """Distributed first-crossing for a scenario batch (inside shard_map).

    Same two-pass prefix as :func:`_local_first_crossing`, with the
    collectives hoisted out of the scenario vmap: ONE all-gather of the
    (S_local, C) partials builds every device's exclusive prefix, the local
    cumulative scan runs vmapped, and ONE pmin merges the candidates.
    """
    s_local, local_n = winners.shape
    all_sums = jax.lax.all_gather(local_sums, axes, tiled=False)
    # (ndev, S_local, C)
    ndev = all_sums.shape[0]
    my_rank = offset // local_n
    before = (jnp.arange(ndev, dtype=jnp.int32) < my_rank
              ).astype(local_sums.dtype)
    s0 = (all_sums * before[:, None, None]).sum(axis=0)      # (S_local, C)
    sentinel = jnp.int32(never_capped(n_events))

    def one(w, p, s0_s, b_s):
        sm = auction.spend_matrix(w, p, n_campaigns)
        cum = s0_s[None, :] + jnp.cumsum(sm, axis=0)
        crossed = cum >= b_s[None, :]
        any_cross = crossed.any(axis=0)
        t_first = jnp.argmax(crossed, axis=0)
        return jnp.where(any_cross,
                         (offset + t_first + 1).astype(jnp.int32), sentinel)

    cand = jax.vmap(one)(winners, prices, s0, budgets)       # (S_local, C)
    return jax.lax.pmin(cand, axes)


def sweep_first_crossing_sharded(
    values: jax.Array,            # (N, C) — events sharded
    cap_times: jax.Array,         # (S, C) assumed cap times (1-based)
    budgets: jax.Array,           # (S, C)
    rules: AuctionRule,           # batched
    spec: SweepMeshSpec,
) -> jax.Array:
    """Diagnose each scenario's budget-crossing times under its assumed cap
    times, on the mesh — the scenario-batched extension of
    :func:`sharded_first_crossing` and the engine of the sharded
    SORT2AGGREGATE refine step. Returns (S, C) 1-based crossing times
    (``N+1`` = never crosses)."""
    _check_sweep_shapes(values, budgets, rules, spec,
                        require_block_alignment=False)
    _, caps, _, _ = _sweep_s2a_program(values, cap_times, budgets, rules,
                                       spec, refine_iters=0)
    return caps


@functools.partial(jax.jit, static_argnames=("spec", "refine_iters"))
def _sweep_s2a_program(values, cap_times0, budgets, rules, spec,
                       refine_iters):
    """(S, C) spends + diagnosed crossing times after ``refine_iters``
    fixed-point iterations of the segment history, all on the mesh."""
    n_events, n_campaigns = values.shape
    sentinel = jnp.int32(never_capped(n_events))
    axes = tuple(spec.event_axes)
    sc = spec.scenario_axis
    local_n = n_events // spec.event_device_count

    spec_vals = P(axes, None)
    spec_sc2 = P(sc, None)

    @functools.partial(
        shard_map, mesh=spec.mesh,
        in_specs=(spec_vals, spec_sc2, spec_sc2, spec_sc2, P(sc)),
        out_specs=(spec_sc2, spec_sc2, spec_sc2, P(sc)))
    def _s2a(values_local, caps0_l, b_l, mult_l, res_l):
        offset = _global_offset(axes, local_n)
        gidx = offset + jnp.arange(local_n, dtype=jnp.int32)
        rules_l = AuctionRule(multipliers=mult_l, reserve=res_l,
                              kind=rules.kind)
        b = b_l.astype(jnp.float32)

        def replay(caps):
            """One sharded aggregate pass under per-scenario cap times.

            The (local_n, C) activation mask is rebuilt locally from the
            replicated cap times (event n is active for campaign c iff
            ``n < cap_times[c]`` — the per-event form of the
            ``Segments.from_cap_times`` masks, since every finite cap time
            is itself a segment boundary), so only the (S_local, C) spend
            partials and crossing candidates cross the interconnect.
            """
            def one(caps_s, r_s):
                act = gidx[:, None] < caps_s[None, :]
                winners, prices = auction.resolve(values_local, act, r_s)
                return winners, prices, auction.spend_sums(
                    winners, prices, n_campaigns)

            winners, prices, local_sums = jax.vmap(one)(caps, rules_l)
            totals = jax.lax.psum(local_sums, axes)
            caps_diag = _batched_first_crossing(
                winners, prices, local_sums, b, offset, axes, n_events,
                n_campaigns)
            return totals, caps_diag

        caps = jnp.minimum(caps0_l.astype(jnp.int32), sentinel)
        iters = jnp.zeros((caps.shape[0],), jnp.int32)
        if refine_iters > 0:
            def step(carry, _):
                c, moved = carry
                _, diag = replay(c)
                new = jnp.minimum(diag, sentinel)
                moved = moved + jnp.any(new != c, axis=-1).astype(jnp.int32)
                return (new, moved), None
            (caps, iters), _ = jax.lax.scan(step, (caps, iters), None,
                                            length=refine_iters)
        totals, caps_diag = replay(caps)
        return totals, caps_diag, caps, iters

    return _s2a(values, cap_times0, budgets, rules.multipliers,
                jnp.asarray(rules.reserve, jnp.float32))


def sweep_sort2aggregate_sharded(
    values: jax.Array,            # (N, C) — events sharded
    budgets: jax.Array,           # (S, C)
    rules: AuctionRule,           # batched
    spec: SweepMeshSpec,
    cap_times_init: Optional[jax.Array] = None,   # (S, C) or (C,) warm start
    refine_iters: int = 8,
) -> Tuple[SimResult, jax.Array, jax.Array]:
    """SORT2AGGREGATE over a scenario batch, on the mesh: per-scenario
    fixed-point refinement of the cap times + one aggregate pass, events
    sharded throughout (the mesh analogue of
    :func:`repro.core.sweep.sweep_sort2aggregate`).

    Each refine iteration does one local resolve of the shard under every
    scenario's activation mask, one (S, C) psum of spend partials, and one
    all-gather + pmin for the distributed crossing diagnosis. Warm-start
    with the base design's cap times (on the mesh: ``estimate_pi_sharded``
    + ``pi_to_cap_times``, which is what
    ``CounterfactualEngine.sweep(method="sort2aggregate", driver="sharded")``
    does) or default to the optimistic all-active start.

    Unlike :func:`sweep_sharded`, spends here are plain psum'd partials (the
    aggregate pass is tolerance-checked against the oracle anyway, not
    bit-compared), so they can differ from the single-device sweep in the
    last ulp; crossing times are integer decisions and agree in practice.
    Returns ``(results, consistency_gaps, refine_iters_used)`` with
    ``gaps[s]`` the max |assumed − replayed| cap time of scenario ``s`` in
    events, and ``refine_iters_used[s]`` the count of refine iterations that
    moved scenario ``s``'s cap times (the warm-start quality signal).
    """
    _check_sweep_shapes(values, budgets, rules, spec,
                        require_block_alignment=False)
    n_events, n_campaigns = values.shape
    n_scenarios = budgets.shape[0]
    if cap_times_init is None:
        cap_times_init = jnp.full((n_campaigns,), n_events + 1, jnp.int32)
    caps0 = jnp.broadcast_to(jnp.asarray(cap_times_init, jnp.int32),
                             (n_scenarios, n_campaigns))
    totals, caps_diag, caps_assumed, iters = _sweep_s2a_program(
        values, caps0, budgets, rules, spec, refine_iters=refine_iters)
    sentinel = jnp.int32(never_capped(n_events))
    gaps = jnp.max(jnp.abs(jnp.minimum(caps_diag, sentinel) - caps_assumed)
                   .astype(jnp.float32), axis=-1)
    result = SimResult(final_spend=totals, cap_times=caps_diag,
                       winners=None, prices=None, segments=None)
    return result, gaps, iters
