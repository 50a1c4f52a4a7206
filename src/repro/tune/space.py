"""Candidate lattice + cost-model ranking for :class:`SweepPlan` knobs.

The tuner treats a sweep as a *problem shape* — (N, C, S, placement,
resolve back-end, event source) on a (platform, device_count) — and
enumerates the plan knobs that are free to move without changing a single
output bit (the chunk-equivalence contracts of ``core/executor.py``):

* ``block_t`` — Pallas event-tile size, when a kernel actually dispatches;
* ``events_per_chunk`` — event-chunked streaming sizes that satisfy
  :func:`~repro.core.executor.check_chunks` (whole canonical reduction
  blocks, dividing the per-device event count) — legal by construction;
* ``scenarios_per_chunk`` — sizes satisfying
  :func:`~repro.core.executor.check_scenario_chunks`;
* ``prefetch`` — host-stream double-buffering on/off;
* ``skip_retired`` — retired-lane grid predication on/off.

Candidates are pruned by a roofline cost model
(:func:`predicted_cost` — T_comp/T_mem/T_coll via
:class:`repro.launch.roofline.HardwareSpec` rates, plus dispatch/padding
overhead terms that actually distinguish the knobs) with the executor's
``round_fused_bytes`` VMEM table as a *hard* feasibility filter: a
candidate whose explicit configuration would exceed
:data:`~repro.core.executor.ONE_LAUNCH_VMEM_BYTES` never surfaces.
:func:`dryrun_terms` refines the bytes/FLOPs of top candidates from the
actual compiled program via the trip-count-aware HLO walker
(:mod:`repro.launch.hlo_cost`).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from repro.core import executor as ex
from repro.core import segments as seg_lib
from repro.launch.roofline import HardwareSpec, RooflineTerms, terms_from_cost

DEFAULT_BLOCK_T = 256
# divisor-aligned Pallas event tiles: multiples of the 128-lane register
# tile; events are padded to block_t, so every size is legal — the lattice
# stays aligned so padding waste is the only block_t-dependent cost
TILE_SIZES = (128, 256, 512, 1024)


@dataclasses.dataclass(frozen=True)
class ProblemShape:
    """The cache-key axes: what the tuned decision is conditioned on."""

    n_events: int
    n_campaigns: int
    n_scenarios: int
    platform: str = "cpu"          # jax.default_backend()
    device_count: int = 1
    device_kind: str = "cpu"       # jax.devices()[0].device_kind
    placement: str = "batched"
    resolve: str = "jnp"           # concrete back-end (pick_resolve applied)
    source: str = "device"         # event log residency


def shape_for(plan: ex.SweepPlan, *, n_events: int, n_campaigns: int,
              n_scenarios: int) -> ProblemShape:
    """The :class:`ProblemShape` a plan + dimensions resolve to."""
    import jax
    return ProblemShape(
        n_events=int(n_events), n_campaigns=int(n_campaigns),
        n_scenarios=int(n_scenarios), platform=jax.default_backend(),
        device_count=jax.device_count(),
        device_kind=jax.devices()[0].device_kind, placement=plan.placement,
        resolve=ex.pick_resolve(plan.resolve),
        source=plan.chunks.source if plan.chunks is not None else "device")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the knob lattice. ``None`` chunk fields = unchunked."""

    block_t: int = DEFAULT_BLOCK_T
    events_per_chunk: Optional[int] = None
    scenarios_per_chunk: Optional[int] = None
    prefetch: bool = True
    skip_retired: bool = True

    def config(self) -> dict:
        """The JSON-cacheable form (what ``tune/cache.py`` persists)."""
        return dataclasses.asdict(self)

    def sort_key(self) -> tuple:
        return (self.block_t, self.events_per_chunk or 0,
                self.scenarios_per_chunk or 0, not self.prefetch,
                not self.skip_retired)

    def apply(self, plan: ex.SweepPlan) -> ex.SweepPlan:
        """The concrete plan this candidate resolves ``plan`` to — only
        free knobs move; pinned fields pass through untouched. The result
        has ``tuned=False`` and an int ``block_t`` (jit-static ready)."""
        free = free_knobs(plan)
        chunks = plan.chunks
        if free["chunks"] and self.events_per_chunk is not None:
            chunks = ex.ChunkSpec(self.events_per_chunk,
                                  prefetch=self.prefetch)
        elif free["prefetch"] and chunks is not None:
            chunks = dataclasses.replace(chunks, prefetch=self.prefetch)
        scen = plan.scenario_chunks
        if free["scenario_chunks"] and self.scenarios_per_chunk is not None:
            scen = ex.ScenarioChunkSpec(self.scenarios_per_chunk)
        return dataclasses.replace(
            plan,
            block_t=self.block_t if free["block_t"] else plan.block_t,
            skip_retired=(self.skip_retired if free["skip_retired"]
                          else plan.skip_retired),
            chunks=chunks, scenario_chunks=scen, tuned=False)


def candidate_from_config(config: dict) -> Candidate:
    """Rebuild a :class:`Candidate` from its cached config dict (unknown
    keys — a newer writer — are ignored; missing keys take defaults)."""
    fields = {f.name for f in dataclasses.fields(Candidate)}
    return Candidate(**{k: v for k, v in config.items() if k in fields})


def free_knobs(plan: ex.SweepPlan) -> dict:
    """Which knobs the tuner may move for this plan.

    ``block_t="auto"`` frees the tile size; ``tuned=True`` additionally
    frees unpinned chunk specs, host-chunk prefetch and ``skip_retired``.
    Explicitly pinned fields (an int ``block_t``, a given ``ChunkSpec``
    size / ``ScenarioChunkSpec``) always win — the tuner never overrides
    a stated size (a service's append-alignment contract may depend on
    it); for an explicit host ``ChunkSpec`` only ``prefetch`` moves.
    """
    return {
        "block_t": plan.block_t == "auto",
        "chunks": bool(plan.tuned) and plan.chunks is None,
        "scenario_chunks": bool(plan.tuned) and plan.scenario_chunks is None,
        "prefetch": bool(plan.tuned) and plan.chunks is not None
                    and plan.chunks.source == "host",
        "skip_retired": bool(plan.tuned),
    }


def default_candidate(plan: ex.SweepPlan) -> Candidate:
    """The incumbent: every free knob at its executor default, every pinned
    knob at its pinned value. ``apply`` of this candidate is exactly the
    untuned program."""
    return Candidate(
        block_t=DEFAULT_BLOCK_T if plan.block_t == "auto" else plan.block_t,
        events_per_chunk=None,
        scenarios_per_chunk=None,
        prefetch=(plan.chunks.prefetch if plan.chunks is not None else True),
        skip_retired=plan.skip_retired)


def _kernel_dispatches(plan: ex.SweepPlan, resolve: str) -> bool:
    """Whether block_t reaches an actual (or interpreted) Pallas grid."""
    if resolve == "pallas":
        return True          # interpret-mode off-TPU, still tiled by block_t
    if resolve == "fused":
        return ex.fused_runs_kernel(plan.interpret)
    return False


def _local_counts(plan: ex.SweepPlan, shape: ProblemShape
                  ) -> Tuple[int, int]:
    """(events, scenarios) per device under the plan's mesh (if any)."""
    local_n, local_s = shape.n_events, shape.n_scenarios
    if plan.mesh is not None:
        d_ev = plan.mesh.event_device_count
        d_sc = plan.mesh.scenario_device_count
        if d_ev and local_n % d_ev == 0:
            local_n //= d_ev
        if d_sc and local_s % d_sc == 0:
            local_s //= d_sc
    return local_n, local_s


def _chunk_sizes(n_events: int, local_n: int) -> List[int]:
    """Legal events_per_chunk values: divisors of the per-device count
    holding whole canonical reduction blocks (the check_chunks contract),
    thinned to the per-device halving ladder."""
    block = seg_lib.reduce_block_size(n_events)
    sizes = []
    parts = 2
    while parts <= seg_lib.REDUCE_BLOCKS:
        epc, rem = divmod(local_n, parts)
        if rem == 0 and epc >= 1 and epc % block == 0:
            sizes.append(epc)
        parts *= 2
    return sizes


def _scenario_chunk_sizes(local_s: int) -> List[int]:
    """Legal scenarios_per_chunk values: proper divisors of the per-device
    lane count (the check_scenario_chunks contract)."""
    return [local_s // p for p in (2, 4, 8)
            if local_s % p == 0 and local_s // p >= 1]


def vmem_feasible(cand: Candidate, plan: ex.SweepPlan,
                  shape: ProblemShape) -> bool:
    """The hard VMEM filter: a candidate that explicitly configures more
    one-launch resident state than :data:`~repro.core.executor.
    ONE_LAUNCH_VMEM_BYTES` never surfaces. (Unchunked fused candidates
    pass — the executor's own gate auto-picks a fitting scenario chunk or
    the two-pass shape for those, see ``planned_scenario_chunk``.)"""
    if not _kernel_dispatches(plan, shape.resolve):
        return True
    _, local_s = _local_counts(plan, shape)
    if shape.resolve == "fused" and cand.scenarios_per_chunk is not None:
        return ex.round_fused_fits(cand.scenarios_per_chunk,
                                   shape.n_campaigns, cand.block_t)
    # two-pass / pallas resolve: one (block_t, C_pad) values tile + the
    # (lanes, C_pad) winner/price rows resident per launch
    c_pad = -(-shape.n_campaigns // 128) * 128
    lanes = cand.scenarios_per_chunk or local_s
    tile_bytes = (cand.block_t * c_pad + 4 * lanes * c_pad) * 4
    return tile_bytes <= ex.ONE_LAUNCH_VMEM_BYTES


def is_legal(cand: Candidate, plan: ex.SweepPlan,
             shape: ProblemShape) -> bool:
    """Legality = the executor's own alignment contracts + the VMEM gate.
    Used both to build the lattice and to validate cached configs against
    the *exact* shape at resolve time (buckets are coarser than shapes)."""
    free = free_knobs(plan)
    if not free["block_t"] and cand.block_t != plan.block_t:
        return False
    if not free["chunks"] and cand.events_per_chunk is not None:
        return False
    if not free["scenario_chunks"] and cand.scenarios_per_chunk is not None:
        return False
    local_n, local_s = _local_counts(plan, shape)
    try:
        if cand.events_per_chunk is not None:
            ex.check_chunks(ex.ChunkSpec(cand.events_per_chunk),
                            n_events=shape.n_events, local_n=local_n)
        if cand.scenarios_per_chunk is not None:
            ex.check_scenario_chunks(
                ex.ScenarioChunkSpec(cand.scenarios_per_chunk),
                n_scenarios=shape.n_scenarios, local_s=local_s)
    except ValueError:
        return False
    return vmem_feasible(cand, plan, shape)


def enumerate_candidates(plan: ex.SweepPlan,
                         shape: ProblemShape) -> List[Candidate]:
    """The legal lattice, deterministic order, incumbent first."""
    free = free_knobs(plan)
    local_n, local_s = _local_counts(plan, shape)
    base = default_candidate(plan)
    tiles: Sequence[int] = [base.block_t]
    if free["block_t"] and _kernel_dispatches(plan, shape.resolve):
        # tiles beyond 2N are pure padding; keep at least the smallest
        tiles = [t for t in TILE_SIZES if t <= 2 * shape.n_events] \
            or [TILE_SIZES[0]]
    epcs: List[Optional[int]] = [None]
    if free["chunks"]:
        epcs += _chunk_sizes(shape.n_events, local_n)
    spcs: List[Optional[int]] = [None]
    if free["scenario_chunks"]:
        spcs += _scenario_chunk_sizes(local_s)
    prefetches = [base.prefetch]
    if free["prefetch"]:
        prefetches = [True, False]
    skips = [base.skip_retired]
    if free["skip_retired"] and _kernel_dispatches(plan, shape.resolve):
        skips = [True, False]
    out = []
    for bt in tiles:
        for epc in epcs:
            for spc in spcs:
                for pf in prefetches:
                    for sk in skips:
                        cand = Candidate(bt, epc, spc, pf, sk)
                        if is_legal(cand, plan, shape):
                            out.append(cand)
    out = sorted(set(out), key=Candidate.sort_key)
    if base in out:                      # incumbent first, rest stable
        out.remove(base)
    return [base] + out


# -- the cost model ---------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PredictedCost:
    """Per-sweep predicted seconds, split into roofline + overhead terms."""

    terms: RooflineTerms       # T_comp / T_mem / T_coll over the sweep
    t_h2d: float               # host->device streaming, after overlap
    t_dispatch: float          # launch-count overhead
    total: float


def predicted_cost(cand: Candidate, plan: ex.SweepPlan,
                   shape: ProblemShape,
                   hw: Optional[HardwareSpec] = None) -> PredictedCost:
    """Analytic roofline cost of one full sweep under this candidate.

    All candidates share the identical round structure (the knobs are
    bitwise-equivalence axes), so constant factors divide out of the
    *ranking*; the terms that differ are padding waste (block_t), resolve
    passes and launch counts (chunking), serial launch depth (scenario
    chunking), H2D overlap (prefetch) and retired-lane grid steps
    (skip_retired).
    """
    if hw is None:
        hw = HardwareSpec.for_device_kind(shape.device_kind)
    local_n, local_s = _local_counts(plan, shape)
    n, c, s = shape.n_events, shape.n_campaigns, local_s
    rounds = min(shape.n_campaigns, 64) + 1       # cap-out rounds, worst-ish
    kernel = _kernel_dispatches(plan, shape.resolve)
    # one-launch fused round: single pass, otherwise rate+block two-pass;
    # event chunks re-resolve per pass per chunk (same totals, more launches)
    eff_s = cand.scenarios_per_chunk or s
    one_launch = (shape.resolve == "fused" and kernel
                  and shape.placement != "sharded"
                  and cand.events_per_chunk is None
                  and plan.chunks is None
                  and ex.round_fused_fits(eff_s, c, cand.block_t))
    passes = 1 if one_launch else 2
    pad = -(-local_n // cand.block_t) * cand.block_t / max(local_n, 1) \
        if kernel else 1.0
    # per-round flops: compare+select over (S, N, C) per pass; kernels skip
    # retired lanes' grid steps (~the capped-out fraction, modelled at 10%)
    flops = passes * s * local_n * c * 2.0 * pad
    if kernel and cand.skip_retired:
        flops *= 0.9
    # per-round bytes: kernels re-read the (local_n, C) tile once per pass
    # (tile reuse across lanes); jnp materialises per-lane winner rows
    values_bytes = local_n * c * 4.0
    partials_bytes = s * seg_lib.REDUCE_BLOCKS * c * 4.0 * 2
    lane_bytes = (s * local_n * 4.0 * 2 if not kernel else 0.0)
    nbytes = passes * (values_bytes * pad + lane_bytes) + partials_bytes
    # sharded placements all-reduce the (S, G, C) partials every round
    wire = 0.0
    if shape.placement in ("sharded", "multihost") and plan.mesh is not None:
        d = max(plan.mesh.event_device_count, 1)
        if d > 1:
            # ring all-reduce of the (S, G, C) partials tensor
            wire = 2.0 * partials_bytes * (d - 1) / d
    terms = terms_from_cost(flops * rounds, nbytes * rounds, wire * rounds,
                            hw)
    # H2D streaming (host-source chunks): the whole log crosses per pass;
    # prefetch overlaps the copy with compute, sync adds it
    t_h2d = 0.0
    if shape.source == "host":
        t_copy = rounds * passes * values_bytes / hw.h2d_bw
        t_h2d = t_copy * (0.15 if cand.prefetch else 1.0)
    # launch overhead: one dispatch per (event chunk x scenario chunk) per
    # pass per round, plus a light per-grid-step cost for tiled kernels
    n_chunks = (local_n // cand.events_per_chunk
                if cand.events_per_chunk else 1)
    n_schunks = (s // cand.scenarios_per_chunk
                 if cand.scenarios_per_chunk else 1)
    launches = rounds * passes * n_chunks * n_schunks
    grid_steps = 0.0
    if kernel:
        grid_steps = launches * (-(-local_n // n_chunks // cand.block_t))
    t_dispatch = (launches * hw.dispatch_us
                  + grid_steps * 0.05 * hw.dispatch_us) * 1e-6
    total = max(terms.t_compute, terms.t_memory) + terms.t_collective \
        + t_h2d + t_dispatch
    return PredictedCost(terms=terms, t_h2d=t_h2d, t_dispatch=t_dispatch,
                         total=total)


def rank_candidates(plan: ex.SweepPlan, shape: ProblemShape,
                    hw: Optional[HardwareSpec] = None,
                    candidates: Optional[Sequence[Candidate]] = None,
                    ) -> List[Tuple[Candidate, PredictedCost]]:
    """The lattice sorted by predicted cost (deterministic: exact ties
    break on the candidate's knob tuple, so equal-cost runs reproduce)."""
    if candidates is None:
        candidates = enumerate_candidates(plan, shape)
    scored = [(c, predicted_cost(c, plan, shape, hw)) for c in candidates]
    return sorted(scored, key=lambda t: (t[1].total, t[0].sort_key()))


def dryrun_terms(cand: Candidate, plan: ex.SweepPlan, shape: ProblemShape,
                 hw: Optional[HardwareSpec] = None
                 ) -> Optional[RooflineTerms]:
    """Trip-count-aware bytes/FLOPs from the candidate's actual compiled
    program (dry-run: ShapeDtypeStructs in, no data, no execution), rated
    through the same :class:`HardwareSpec`. Returns ``None`` where the
    program can't lower in-process (host streams, multihost)."""
    import jax
    import jax.numpy as jnp
    from repro.core.types import AuctionRule
    from repro.launch import hlo_cost
    if shape.source == "host" or shape.placement == "multihost":
        return None
    if hw is None:
        hw = HardwareSpec.for_device_kind(shape.device_kind)
    concrete = cand.apply(plan)
    if concrete.placement == "device":
        b = jax.ShapeDtypeStruct((shape.n_campaigns,), jnp.float32)
        rules = AuctionRule(
            multipliers=jax.ShapeDtypeStruct((shape.n_campaigns,),
                                             jnp.float32),
            reserve=jax.ShapeDtypeStruct((), jnp.float32))
    else:
        b = jax.ShapeDtypeStruct((shape.n_scenarios, shape.n_campaigns),
                                 jnp.float32)
        rules = AuctionRule(
            multipliers=jax.ShapeDtypeStruct(
                (shape.n_scenarios, shape.n_campaigns), jnp.float32),
            reserve=jax.ShapeDtypeStruct((shape.n_scenarios,), jnp.float32))
    v = jax.ShapeDtypeStruct((shape.n_events, shape.n_campaigns),
                             jnp.float32)
    fn = jax.jit(lambda v_, b_, r_: ex.execute_sweep(v_, b_, r_, concrete))
    try:
        compiled = fn.lower(v, b, rules).compile()
    except ValueError:
        return None         # an illegal plan: the executor's own checks
    cost = hlo_cost.analyze(compiled.as_text())
    return terms_from_cost(cost.flops, cost.bytes, cost.coll_wire_bytes, hw)
