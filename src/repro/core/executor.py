"""The unified sweep-executor layer: ONE Algorithm-2 program, many backends.

PRs 1–4 grew four Algorithm-2 entry points — ``parallel_state_machine``
(S=1), ``sweep_state_machine`` (scenario-batched), ``sweep_sharded``
(mesh-batched) and the SORT2AGGREGATE sweeps — each carrying its own copy of
the driver/resolve dispatch, its own validation, and its own while_loop
scaffolding. This module collapses them: a :class:`SweepPlan` names every
axis of the execution —

* **placement** — where the loop runs: ``"device"`` (one unbatched lane),
  ``"batched"`` (the S-lane loop on one device), ``"sharded"`` (the same
  loop under ``shard_map`` on ``plan.mesh``), ``"multihost"`` (the sharded
  program on a ``jax.distributed`` process mesh: each process feeds its own
  event shard, the two per-round psums cross processes unchanged);
* **resolve** — the per-round back-end: ``"jnp"``, ``"pallas"``,
  ``"fused"``, or ``"auto"`` (fused on TPU, jnp elsewhere — never an
  interpret-mode Pallas kernel, see :func:`pick_resolve`);
* **reduction grid** — every reduction goes through the canonical
  ``(REDUCE_BLOCKS, C)`` block partials of :mod:`repro.core.segments`,
  which is what makes every placement bit-for-bit equal;
* **chunks** — optional event-chunked streaming (:class:`ChunkSpec`): each
  round scans the event log in fixed chunks, accumulating the canonical
  ``(S, 32, C)`` spend partials chunk-by-chunk via the same ``index_offset``
  mechanism the mesh shards use, so only one chunk's per-event intermediates
  are live at a time. ``source="device"`` scans a device-resident log
  (``lax.scan``); ``source="host"`` streams each chunk from host RAM
  through a double-buffered ``device_put`` pipeline (:class:`HostStream`,
  :func:`_sweep_hoststream`), so the log itself never has to fit device
  memory;
* **scenario_chunks** — optional scenario-chunked execution
  (:class:`ScenarioChunkSpec`): the whole round program is scanned over
  fixed slices of the scenario axis. Lanes are independent (carried burnout
  state is per-scenario; finished lanes are frozen by select), so scenario
  chunks are bit-for-bit the unchunked program and compose with every other
  axis. When the fused one-launch round would exceed its VMEM gate, the
  executor auto-picks a fitting scenario chunk (:func:`planned_scenario_chunk`)
  instead of degrading to the two-pass shape;
* **skip_retired / block_t / interpret** — kernel knobs, unchanged.

and :func:`execute_sweep` generates the program. The legacy entry points are
thin wrappers that build a plan; a new axis (a placement, a back-end, a chunk
schedule) is now a change HERE, not in five modules.

Program shapes the plan can generate, all sharing :func:`_run_loop` (the
while_loop scaffolding: alive-lane condition, frozen-lane select, round log)
and the per-lane scalar logic (:func:`lane_predict` / :func:`lane_commit`):

* **resolve-once** (jnp / pallas / fused-oracle-on-CPU, unchunked) — one
  resolve of the local events per round; rate and block reductions are two
  weighted partials of the same winners/prices (exactly the ``lane_round``
  decomposition);
* **one-launch fused round** (``resolve="fused"`` where Pallas compiles,
  batched placement, unchunked) — the whole round is one ``round_fused``
  kernel launch, winners/prices never reach HBM;
* **two-pass** (sharded fused, and EVERY chunked plan) — one weighted
  partials pass per reduction window (``[n_hat, N)`` then ``[n_hat,
  n_next)``), each pass built from per-shard / per-chunk canonical partials
  placed on the global grid via ``index_offset`` and combined by psum
  (sharded) or chunk-scan accumulation (chunked). Because every canonical
  block is owned by exactly one shard×chunk, combining adds exact zeros —
  the partials tensor, and therefore ``final_spend``/``cap_times``, is
  bit-for-bit identical to the in-memory drivers (docs/SCALING.md,
  docs/ARCHITECTURE.md).

Misaligned chunk sizes (chunks not holding whole canonical blocks, or not
dividing the per-device event count) raise the same pad-or-error contract as
misaligned meshes: :func:`check_chunks`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compat import (axis_size as compat_axis_size,
                          host_local_to_global, shard_map)
from repro.core import auction
from repro.core import crn
from repro.core import segments as seg_lib
from repro.core.types import AuctionRule, ScenarioOverlay, never_capped
from repro import kernels, obs
from repro.kernels.auction_resolve import ops as resolve_ops
from repro.launch.mesh import SweepMeshSpec

RESOLVE_BACKENDS = ("jnp", "pallas", "fused")
SWEEP_DRIVERS = ("batched", "sharded", "multihost")
SIM_DRIVERS = ("auto", "device", "host")
PLACEMENTS = ("device", "batched", "sharded", "multihost")
CHUNK_SOURCES = ("device", "host")


def _unknown(kind: str, got, known) -> ValueError:
    """THE unknown-option error: every entry point raises through here, so
    the message for a bad ``driver=``/``resolve=`` string is identical
    whether it comes from ``sweep.py``, ``counterfactual.py``,
    ``sharded.py``, or a plan built directly."""
    names = ", ".join(repr(k) for k in known)
    return ValueError(f"unknown {kind}: {got!r} (choose from {names})")


def pick_resolve(resolve: str, on_tpu: Optional[bool] = None) -> str:
    """Resolve the ``"auto"`` preference to a concrete back-end.

    ``"auto"`` picks the fused round kernel where Pallas compiles (TPU) and
    the vmapped jnp path everywhere else. It must NEVER land on an
    interpret-mode Pallas kernel: BENCH_sweep.json's sweep layer shows
    interpret-mode pallas ~3–5× slower than the vmapped jnp path on CPU
    (e.g. S=8: ~1.2 s vs ~0.24 s per sweep) — interpret mode is a
    correctness harness, not a production path (regression-tested in
    tests/test_scenario_sweep.py).
    """
    on_tpu = kernels.on_tpu() if on_tpu is None else on_tpu
    if resolve == "auto":
        return "fused" if on_tpu else "jnp"
    if resolve not in RESOLVE_BACKENDS:
        raise _unknown("resolve back-end", resolve,
                       RESOLVE_BACKENDS + ("auto",))
    return resolve


def fused_runs_kernel(interpret: Optional[bool]) -> bool:
    """Whether ``resolve="fused"`` dispatches the Pallas round kernel.

    True on TPU (compiled) or when interpret mode is explicitly forced
    (kernel tests); otherwise the fused round runs its jnp oracle
    composition (the exact ``lane_round`` stages) — never an *implicit*
    interpret-mode kernel."""
    return kernels.on_tpu() or interpret is True


def check_sim_driver(driver: str) -> str:
    """Validate a single-scenario ``parallel_simulate`` driver string."""
    if driver not in SIM_DRIVERS:
        raise _unknown("driver", driver, SIM_DRIVERS)
    return driver


# ---------------------------------------------------------------------------
# The plan: every axis of a sweep execution, hashable (jit-static)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChunkSpec:
    """Event-chunked streaming: scan the log ``events_per_chunk`` at a time.

    Each Algorithm-2 round becomes a ``lax.scan`` over fixed event chunks
    that accumulates the canonical ``(S, REDUCE_BLOCKS, C)`` spend partials —
    each chunk's rows placed on the *global* reduction grid via the kernels'
    ``index_offset``, exactly as mesh shards place theirs — while the
    carried burnout state ``(s_hat, active, cap_times, n_hat)`` stays O(S·C).
    Per-event intermediates (winners, prices, spend one-hots) exist for one
    chunk at a time, so the working set is O(events_per_chunk · C) instead
    of O(N · C) and N can grow past what a resident (S, N) round would
    allow. Results are bit-for-bit those of the in-memory drivers for any
    aligned chunk size (chunks holding whole canonical blocks and dividing
    the per-device event count — :func:`check_chunks`); misaligned sizes
    raise the same pad-or-error contract as misaligned meshes.

    Composes with every placement and resolve back-end: under
    ``placement="sharded"`` each device scans its own shard's chunks before
    the per-round psum (chunking × sharding), and ``resolve="fused"`` uses
    the ``sweep_partials`` kernel per chunk where Pallas compiles.

    ``source`` picks where the chunk data lives between rounds:

    * ``"device"`` (default) — the whole log is device-resident and each
      round is a ``lax.scan`` over its chunks (bounds per-event
      *intermediates*, not the log itself);
    * ``"host"`` — the log lives in host RAM (:class:`HostStream`, or any
      array the executor pulls back once) and every round streams it chunk
      by chunk through per-chunk ``jax.device_put``, so device memory holds
      one or two chunks plus the O(S·C) carried state and N is bounded by
      host RAM, not HBM. ``prefetch=True`` double-buffers the pipeline:
      chunk k+1's H2D copy is issued right after chunk k's jitted partials
      step is dispatched, so (by JAX's async dispatch) transfer overlaps
      compute; ``prefetch=False`` is the synchronous-put baseline the
      ``hoststream`` benchmark layer times it against. Both orders run the
      identical per-chunk program, so results are bit-for-bit the
      device-resident driver either way (same alignment contract, checked
      by the same :func:`check_chunks`).
    """

    events_per_chunk: int
    source: str = "device"
    prefetch: bool = True

    def __post_init__(self):
        if self.events_per_chunk < 1:
            raise ValueError(
                f"ChunkSpec.events_per_chunk must be >= 1, got "
                f"{self.events_per_chunk}")
        if self.source not in CHUNK_SOURCES:
            raise _unknown("chunk source", self.source, CHUNK_SOURCES)


def as_chunk_spec(chunks) -> Optional[ChunkSpec]:
    """Normalise ``None`` | int | :class:`ChunkSpec` to an optional spec."""
    if chunks is None or isinstance(chunks, ChunkSpec):
        return chunks
    return ChunkSpec(events_per_chunk=int(chunks))


class HostStream:
    """A host-resident event log: numpy slabs, streamed to device chunkwise.

    The "events pytree" of a log that outgrows device memory. Rows live in
    host RAM as a list of float32 slabs (the service's append slabs,
    verbatim — no concatenated copy is ever materialised, on host or
    device); :meth:`chunk` hands the executor's double-buffered pipeline
    ``[start, stop)`` row windows, a zero-copy view whenever the window
    sits inside one slab. Passing a ``HostStream`` to
    :func:`execute_sweep` / :func:`execute_sweep_resumable` (with
    ``chunks=ChunkSpec(..., source="host")`` or any aligned chunk size)
    selects the host-streamed driver; results are bit-for-bit the
    device-resident program on aligned sizes.
    """

    def __init__(self, slabs):
        slabs = [np.asarray(s, dtype=np.float32) for s in slabs]
        if not slabs:
            raise ValueError("HostStream needs at least one event slab")
        n_campaigns = slabs[0].shape[1] if slabs[0].ndim == 2 else -1
        for s in slabs:
            if s.ndim != 2 or s.shape[1] != n_campaigns or s.shape[0] < 1:
                raise ValueError(
                    "HostStream slabs must be non-empty (n, C) valuation "
                    f"blocks with one shared C; got shapes "
                    f"{[tuple(x.shape) for x in slabs]}")
        self._slabs = slabs
        self._starts = np.concatenate(
            ([0], np.cumsum([s.shape[0] for s in slabs])))

    @classmethod
    def from_array(cls, values) -> "HostStream":
        """Wrap an in-memory (N, C) log (pulled back to host once)."""
        return cls([np.asarray(jax.device_get(values), np.float32)])

    @property
    def shape(self):
        return (int(self._starts[-1]), int(self._slabs[0].shape[1]))

    @property
    def ndim(self) -> int:
        return 2

    @property
    def n_events(self) -> int:
        return int(self._starts[-1])

    @property
    def n_campaigns(self) -> int:
        return int(self._slabs[0].shape[1])

    def chunk(self, start: int, stop: int) -> np.ndarray:
        """Rows ``[start, stop)`` — a view when one slab covers the window
        (guaranteed under the service's whole-chunk append contract when
        slab sizes are chunk multiples), else a host-side concatenation."""
        if not 0 <= start < stop <= self.n_events:
            raise ValueError(
                f"chunk window [{start}, {stop}) outside the stream's "
                f"{self.n_events} events")
        i = int(np.searchsorted(self._starts, start, side="right")) - 1
        pieces = []
        while start < stop:
            s0 = int(self._starts[i])
            slab = self._slabs[i]
            take = min(stop, s0 + slab.shape[0])
            pieces.append(slab[start - s0:take - s0])
            start = take
            i += 1
        return pieces[0] if len(pieces) == 1 else np.concatenate(pieces)


@dataclasses.dataclass(frozen=True)
class ScenarioChunkSpec:
    """Scenario-chunked execution: run the S-lane loop ``scenarios_per_chunk``
    lanes at a time.

    The executor's whole round program — round body, while_loop, frozen-lane
    select — is generated once and scanned (``lax.map``) over fixed slices of
    the scenario axis, exactly as :class:`ChunkSpec` scans the event axis.
    The carried burnout state ``(s_hat, active, cap_times, n_hat)`` is
    per-scenario and lanes never read other lanes' state (finished lanes are
    frozen by select, so a chunk's extra or missing rounds are no-ops), which
    makes scenario chunks *independent*: results are bit-for-bit those of
    the unchunked program for any chunk size dividing the per-device
    scenario count (:func:`check_scenario_chunks`; misaligned sizes raise
    the same pad-or-error contract as event chunks and meshes).

    Composes with every placement, resolve back-end and event ``chunks=``:
    under ``placement="sharded"`` each scenario-axis device slice scans its
    own lanes chunk-by-chunk, and ``resolve="fused"`` runs the one-launch
    ``round_fused`` kernel per chunk — which is how a sweep whose full S
    does not fit :data:`ONE_LAUNCH_VMEM_BYTES` keeps the one-launch shape
    instead of degrading to two-pass (the executor auto-picks a fitting
    chunk; :func:`planned_scenario_chunk`). Peak memory for per-round
    intermediates drops from O(S · …) to O(scenarios_per_chunk · …) at the
    cost of serial depth across chunks.
    """

    scenarios_per_chunk: int

    def __post_init__(self):
        if self.scenarios_per_chunk < 1:
            raise ValueError(
                f"ScenarioChunkSpec.scenarios_per_chunk must be >= 1, got "
                f"{self.scenarios_per_chunk}")


def as_scenario_chunk_spec(scenario_chunks) -> Optional[ScenarioChunkSpec]:
    """Normalise ``None`` | int | :class:`ScenarioChunkSpec`."""
    if scenario_chunks is None or isinstance(scenario_chunks,
                                             ScenarioChunkSpec):
        return scenario_chunks
    return ScenarioChunkSpec(scenarios_per_chunk=int(scenario_chunks))


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """Everything that decides which Algorithm-2 program gets generated.

    Frozen + hashable so a plan rides through ``jax.jit`` as one static
    argument. Fields:

    * ``placement`` — ``"device"`` (one unbatched lane; the executor runs
      the batched program at S=1 and unstacks), ``"batched"`` (default),
      ``"sharded"`` (requires ``mesh``), or ``"multihost"`` (the sharded
      program on a ``jax.distributed`` process mesh — requires ``mesh``,
      normally :meth:`repro.launch.mesh.SweepMeshSpec.for_processes`; each
      process passes its own event shard to :func:`execute_sweep`);
    * ``resolve`` — ``"jnp" | "pallas" | "fused" | "auto"``;
    * ``block_t`` — Pallas event-tile size, or ``"auto"`` to let the plan
      tuner (:mod:`repro.tune`) pick it at :func:`execute_sweep` time from
      the persistent tuning cache / cost-model ranking;
    * ``tuned`` — hand every *unpinned* knob (tile when ``"auto"``, chunk
      specs when ``None``, host prefetch, ``skip_retired``) to the tuner.
      Resolution never changes numerics: every candidate is bit-for-bit
      the default plan by the chunk-equivalence contracts below;
    * ``interpret`` — force (True) / suppress (False) Pallas interpret mode;
      ``None`` = interpret off-TPU, except ``"fused"`` which falls back to
      its jnp oracle instead of interpreting;
    * ``skip_retired`` — predicate retired lanes' kernel grid steps off
      (pure wall-clock; results are bit-identical either way);
    * ``mesh`` — :class:`repro.launch.mesh.SweepMeshSpec`, sharded only;
    * ``chunks`` — optional :class:`ChunkSpec` for event-chunked streaming;
    * ``scenario_chunks`` — optional :class:`ScenarioChunkSpec`: scan the
      round program over fixed scenario slices (``None`` also lets the
      executor auto-pick a VMEM-fitting chunk for the fused one-launch
      round — see :func:`planned_scenario_chunk`).
    """

    placement: str = "batched"
    resolve: str = "auto"
    block_t: int = 256           # int, or "auto" for tuner resolution
    interpret: Optional[bool] = None
    skip_retired: bool = True
    mesh: Optional[SweepMeshSpec] = None
    chunks: Optional[ChunkSpec] = None
    scenario_chunks: Optional[ScenarioChunkSpec] = None
    tuned: bool = False

    def __post_init__(self):
        if self.placement not in PLACEMENTS:
            raise _unknown("placement", self.placement, PLACEMENTS)
        if self.block_t != "auto" and (
                not isinstance(self.block_t, int)
                or isinstance(self.block_t, bool) or self.block_t < 1):
            raise ValueError(
                f"SweepPlan.block_t must be a positive int or 'auto', got "
                f"{self.block_t!r}")
        if self.resolve not in RESOLVE_BACKENDS + ("auto",):
            raise _unknown("resolve back-end", self.resolve,
                           RESOLVE_BACKENDS + ("auto",))
        if self.placement in ("sharded", "multihost") and self.mesh is None:
            raise ValueError(
                f"placement={self.placement!r} needs mesh=SweepMeshSpec(...);"
                " see repro.launch.mesh.SweepMeshSpec.for_devices (sharded) "
                "/ .for_processes (multihost)")
        object.__setattr__(self, "chunks", as_chunk_spec(self.chunks))
        object.__setattr__(self, "scenario_chunks",
                           as_scenario_chunk_spec(self.scenario_chunks))


def plan_for_driver(driver: str, *, resolve: str = "auto",
                    block_t=256, interpret: Optional[bool] = None,
                    skip_retired: bool = True, mesh=None,
                    chunks=None, scenario_chunks=None,
                    tuned: bool = False) -> SweepPlan:
    """Build the plan for a legacy ``driver=`` string (``sweep_parallel`` /
    ``engine.sweep``), with the one consistent unknown-driver error."""
    if driver not in SWEEP_DRIVERS:
        raise _unknown("sweep driver", driver, SWEEP_DRIVERS)
    meshed = driver in ("sharded", "multihost")
    if meshed and mesh is None:
        raise ValueError(
            f"driver={driver!r} needs mesh=SweepMeshSpec(...); see "
            "repro.launch.mesh.SweepMeshSpec.for_devices (sharded) / "
            ".for_processes (multihost)")
    return SweepPlan(placement=driver, resolve=resolve, block_t=block_t,
                     interpret=interpret, skip_retired=skip_retired,
                     mesh=mesh if meshed else None,
                     chunks=as_chunk_spec(chunks),
                     scenario_chunks=as_scenario_chunk_spec(scenario_chunks),
                     tuned=tuned)


def needs_tuning(plan: SweepPlan) -> bool:
    """Whether the plan carries knobs the tuner must resolve before any
    jitted program can treat it as static."""
    return plan.tuned or plan.block_t == "auto"


def resolve_auto_plan(plan: SweepPlan, *, n_events: int, n_campaigns: int,
                      n_scenarios: int) -> SweepPlan:
    """Resolve ``block_t="auto"`` / ``tuned=True`` to a concrete plan via
    the tuning cache + cost-model ranking (:func:`repro.tune.resolve_plan`
    — lazy import; tune depends on this module). No-op for concrete plans.
    Resolution only moves bitwise-equivalence knobs, never answers."""
    if not needs_tuning(plan):
        return plan
    from repro import tune
    return tune.resolve_plan(plan, n_events=n_events,
                             n_campaigns=n_campaigns,
                             n_scenarios=n_scenarios)


def _untuned(plan: SweepPlan) -> SweepPlan:
    """Pin tuner knobs at executor defaults WITHOUT consulting the tuner —
    for entry points whose knob lattice the tuner does not model (the
    sort2aggregate spine, resumable folds)."""
    if not needs_tuning(plan):
        return plan
    return dataclasses.replace(
        plan, block_t=256 if plan.block_t == "auto" else plan.block_t,
        tuned=False)


# ---------------------------------------------------------------------------
# Shape / alignment validation (one home for every entry point's checks)
# ---------------------------------------------------------------------------

def check_batch_shapes(values, budgets, rules) -> None:
    """The (S, C)-batch contract shared by every sweep entry point."""
    if rules.multipliers.ndim != 2 or budgets.ndim != 2:
        raise ValueError(
            "sweep inputs must be batched: multipliers/budgets (S, C), "
            f"got {rules.multipliers.shape} / {budgets.shape}")
    n_campaigns = values.shape[1]
    if budgets.shape[1] != n_campaigns or \
            rules.multipliers.shape != budgets.shape:
        raise ValueError(
            f"scenario batch mismatch: values C={n_campaigns}, "
            f"multipliers {rules.multipliers.shape}, budgets {budgets.shape}")


def check_sharded_shapes(values, budgets, rules, spec,
                         require_block_alignment=True) -> None:
    """Static-shape validation + the shard contract.

    ``require_block_alignment`` adds the canonical-reduction-grid alignment
    needed for the sharded Algorithm-2 sweep's bit-for-bit guarantee; the
    SORT2AGGREGATE sweep paths (plain psum'd spends, tolerance-checked) only
    need evenly divisible shards.
    """
    check_batch_shapes(values, budgets, rules)
    n_events = values.shape[0]
    n_scenarios = budgets.shape[0]
    d_ev = spec.event_device_count
    if n_events % d_ev != 0:
        raise ValueError(
            f"ragged shard: N={n_events} events over {d_ev} event-axis "
            f"devices leaves a remainder of {n_events % d_ev}. Pad the event "
            "log to a multiple of the event-device count (zero-valuation "
            "events never win, but they DO count toward rate denominators — "
            "pad the log upstream where that is accounted for) or use "
            "driver='batched'.")
    block = seg_lib.reduce_block_size(n_events)
    local_n = n_events // d_ev
    if require_block_alignment and d_ev > 1 and local_n % block != 0:
        if seg_lib.REDUCE_BLOCKS % d_ev != 0:
            # no N can align: shards can never hold whole canonical blocks
            raise ValueError(
                f"shard/grid misalignment: {d_ev} event-axis devices cannot "
                f"divide the canonical reduction grid (REDUCE_BLOCKS="
                f"{seg_lib.REDUCE_BLOCKS}); the event-device count must "
                "divide REDUCE_BLOCKS for the bit-for-bit contract. Use a "
                "device count that divides it, raise "
                "repro.core.segments.REDUCE_BLOCKS (a repo-wide constant — "
                "it regroups every driver's reductions consistently, so the "
                "cross-driver bit-for-bit contract is preserved but absolute "
                "low bits shift), or use driver='batched'.")
        g = seg_lib.REDUCE_BLOCKS
        aligned_n = max(1, -(-n_events // g)) * g   # d_ev | g => d_ev | k*g
        raise ValueError(
            f"shard/grid misalignment: each shard holds {local_n} events but "
            f"the canonical reduction grid uses blocks of {block} "
            f"(REDUCE_BLOCKS={g}); shards must hold whole blocks for the "
            f"bit-for-bit reduction contract. Pad N to a multiple of {g} "
            f"(e.g. {aligned_n}), or use driver='batched'.")
    d_sc = spec.scenario_device_count
    if n_scenarios % d_sc != 0:
        raise ValueError(
            f"ragged scenario shard: S={n_scenarios} scenarios over {d_sc} "
            f"devices on mesh axis {spec.scenario_axis!r}. Pad the grid with "
            "repeats of the base design, or drop scenario_axis.")


def check_chunks(chunks: Optional[ChunkSpec], *, n_events: int,
                 local_n: int) -> None:
    """The chunk-alignment contract (mirrors the mesh's pad-or-error).

    A chunk must (a) hold whole canonical reduction blocks, so every block
    of the ``(REDUCE_BLOCKS, C)`` partials grid is owned by exactly one
    chunk and the chunk-scan accumulation adds exact zeros (the bit-for-bit
    argument of docs/SCALING.md, verbatim), and (b) evenly divide the
    per-device event count, so every scan step processes a full chunk.
    """
    if chunks is None:
        return
    epc = chunks.events_per_chunk
    block = seg_lib.reduce_block_size(n_events)
    g = seg_lib.REDUCE_BLOCKS
    if epc % block != 0:
        raise ValueError(
            f"chunk/grid misalignment: ChunkSpec(events_per_chunk={epc}) "
            f"does not hold whole canonical reduction blocks of {block} "
            f"events (N={n_events}, REDUCE_BLOCKS={g}); chunks must cover "
            "whole blocks for the bit-for-bit reduction contract. Use a "
            f"chunk size that is a multiple of {block}, pad N so the block "
            "size divides your chunk, or drop chunks=.")
    if local_n % epc != 0:
        raise ValueError(
            f"ragged chunk: {local_n} events per device do not divide into "
            f"chunks of {epc} (remainder {local_n % epc}). Pad the event "
            "log so every chunk is full (zero-valuation events never win, "
            "but they DO count toward rate denominators — pad the log "
            "upstream where that is accounted for), pick a chunk size that "
            "divides the per-device event count, or drop chunks=.")


def check_append_alignment(chunks: Optional[ChunkSpec], n_new: int) -> None:
    """The append-side chunk contract: a slab appended to a growing log must
    hold whole chunks, so every later chunk-scan step is a full chunk.

    Raises the IDENTICAL "ragged chunk" pad-or-error message as a chunked
    sweep (:func:`check_chunks`) — one contract text everywhere, asserted by
    tests/test_scenario_sweep.py. The reduction-grid alignment branch is a
    property of the *total* log at sweep time, not of one append (the
    canonical block size grows with N), so this check constructs an
    ``n_events`` whose block equals the chunk and only the ragged branch
    can fire.
    """
    if chunks is None:
        return
    check_chunks(chunks,
                 n_events=chunks.events_per_chunk * seg_lib.REDUCE_BLOCKS,
                 local_n=n_new)


def check_host_stream(plan: SweepPlan, *,
                      overlay: Optional[ScenarioOverlay] = None) -> None:
    """The host-streamed execution contract (callable up front).

    Host-streamed chunks feed ONE device's pipeline, so the plan must be a
    single-device placement with an explicit chunk size; alignment itself
    is :func:`check_chunks`, verbatim.
    """
    if plan.chunks is None:
        raise ValueError(
            "host-streamed execution needs chunks=: the log is fed to the "
            "device one chunk at a time, so ChunkSpec(events_per_chunk=..., "
            "source='host') (or an aligned int chunk size alongside a "
            "HostStream log) must state the working-set size.")
    if plan.placement not in ("device", "batched"):
        raise ValueError(
            "host-streamed chunks run placement='device'/'batched' only "
            f"(the host feeds one device's pipeline), got "
            f"{plan.placement!r}; device-resident logs scale out via "
            "placement='sharded'/'multihost' instead.")
    if plan.scenario_chunks is not None:
        raise ValueError(
            "scenario_chunks= does not compose with host-streamed chunks; "
            "drop scenario_chunks= (the host pipeline already bounds "
            "per-round intermediates by the event chunk).")
    if overlay is not None:
        raise ValueError(
            "overlays are not supported with host-streamed chunks; replay "
            "overlay families from a device-resident log "
            "(ChunkSpec(source='device') bounds their per-event "
            "intermediates the same way).")


def check_scenario_chunks(scenario_chunks: Optional[ScenarioChunkSpec], *,
                          n_scenarios: int, local_s: int) -> None:
    """The scenario-chunk alignment contract (the S-axis pad-or-error).

    Unlike event chunks there is no reduction-grid constraint on the
    scenario axis — lanes are independent — so the only requirement is that
    chunks evenly divide the per-device scenario count, making every scan
    step a full chunk.
    """
    if scenario_chunks is None:
        return
    spc = scenario_chunks.scenarios_per_chunk
    if local_s % spc != 0:
        raise ValueError(
            f"ragged scenario chunk: {local_s} scenarios per device do not "
            f"divide into chunks of {spc} (remainder {local_s % spc}). Pad "
            "the grid with repeats of the base design (duplicate lanes run "
            "the identical per-lane program, so they cannot change any "
            "other lane's bits), pick a scenario-chunk size that divides "
            "the per-device scenario count, or drop scenario_chunks=.")


def check_overlay(overlay: Optional[ScenarioOverlay], *, n_scenarios: int,
                  n_campaigns: int, resolve: str,
                  interpret: Optional[bool]) -> None:
    """The :class:`~repro.core.types.ScenarioOverlay` contract.

    Shapes are (S, C); live windows come in pairs; stochastic fields need
    the family key for their CRN streams; and per-event overlays (bid
    noise, participation jitter, time-varying windows) are a jnp-resolve
    feature — a plan that would dispatch an actual Pallas kernel per round
    fails fast here rather than silently ignoring the overlay. Static
    pause/window overlays (``time_varying=False``) fold into the
    activation mask and compose with every kernel back-end.
    """
    if overlay is None:
        return
    shape = (n_scenarios, n_campaigns)
    for name in ("live_start", "live_stop", "bid_sigma", "part_prob"):
        arr = getattr(overlay, name)
        if arr is not None and tuple(arr.shape) != shape:
            raise ValueError(
                f"ScenarioOverlay.{name} must be (S, C)={shape}, got "
                f"{tuple(arr.shape)}")
    if (overlay.live_start is None) != (overlay.live_stop is None):
        raise ValueError(
            "ScenarioOverlay live windows need BOTH live_start and "
            "live_stop (half-open [start, stop) per scenario×campaign)")
    if overlay.time_varying and overlay.live_start is None:
        raise ValueError(
            "ScenarioOverlay.time_varying=True without live windows; "
            "time_varying only qualifies live_start/live_stop")
    if (overlay.bid_sigma is not None or overlay.part_prob is not None) \
            and overlay.key is None:
        raise ValueError(
            "stochastic overlay fields (bid_sigma / part_prob) need "
            "ScenarioOverlay.key — the family PRNG key their CRN streams "
            "derive from (repro.core.crn)")
    if overlay.per_event and (
            resolve == "pallas"
            or (resolve == "fused" and fused_runs_kernel(interpret))):
        raise ValueError(
            "per-event scenario overlays (bid noise, participation jitter, "
            "time-varying live windows) run on the jnp resolve path only; "
            "use resolve='jnp' (or 'auto'/'fused' off-TPU, which lower to "
            "the identical jnp program). Static pause/boost overlays "
            "compose with every kernel back-end.")


def _overlay_noise(overlay: Optional[ScenarioOverlay], n_events: int,
                   n_campaigns: int):
    """The overlay's (N, C) CRN noise fields, drawn ONCE over global event
    indices (scenario-independent — every lane shares them; sharded and
    chunked executions slice the identical arrays)."""
    if overlay is None:
        return None, None
    gidx = jnp.arange(n_events, dtype=jnp.int32)
    z = u = None
    if overlay.bid_sigma is not None:
        z = crn.event_campaign_normals(
            crn.stream_key(overlay.key, "bid_noise"), gidx, n_campaigns)
    if overlay.part_prob is not None:
        u = crn.event_campaign_uniforms(
            crn.stream_key(overlay.key, "participation"), gidx, n_campaigns)
    return z, u


def _local_overlay(overlay: Optional[ScenarioOverlay]):
    """The overlay without its key — the per-lane form threaded through the
    round program (noise is already drawn; only (S, C) fields remain, so
    scenario-axis sharding/chunking can slice every leaf uniformly)."""
    if overlay is None:
        return None
    return dataclasses.replace(overlay, key=None)


# One-launch fused-round VMEM budget: the TPU v5e compiler's default scoped
# VMEM limit, which is what refuses an over-full kernel.
ONE_LAUNCH_VMEM_BYTES = 16 << 20


def round_fused_bytes(n_scenarios: int, n_campaigns: int,
                      block_t: int = 256) -> int:
    """Float32 bytes of VMEM the one-launch ``round_fused`` kernel needs:
    two (S, G, C_pad) partials blocks, four double-buffered (S, C_pad)
    scenario-state blocks, four (block_t, C_pad) tiles (the double-buffered
    values tile and the resolve's temporaries) and four (block_t, 128)
    lane-padded per-row columns. Fitted to the v5e compiler (described
    topology) so that every size it admits compiles: at C=1024,
    block_t=256 it admits S <= 40 where the compiler takes S <= 52; where
    tile temporaries dominate (block_t=1024, or C >= 2048) it stays under
    the compiler's limit too (docs/ALGORITHMS.md; tests/test_tpu_compile.py
    compiles the largest S it admits)."""
    c_pad = -(-n_campaigns // 128) * 128
    return (2 * n_scenarios * seg_lib.REDUCE_BLOCKS * c_pad
            + 8 * n_scenarios * c_pad + 4 * block_t * c_pad
            + 4 * block_t * 128) * 4


def round_fused_fits(n_scenarios: int, n_campaigns: int,
                     block_t: int = 256) -> bool:
    """Whether the one-launch ``round_fused`` kernel's resident state fits
    the VMEM budget. Past it the executor *scenario-chunks* the loop down to
    a fitting lane count (:func:`planned_scenario_chunk`) so the round keeps
    its one-launch shape; only when no chunk fits (or the caller pinned an
    unfitting explicit ``scenario_chunks=``) does it fall back to the
    two-pass shape (one ``sweep_partials`` launch per reduction window —
    half the resident partials). Both alternatives produce the identical
    canonical partials tensor, so neither gate can change results."""
    return round_fused_bytes(n_scenarios, n_campaigns,
                             block_t) <= ONE_LAUNCH_VMEM_BYTES


def fitting_scenario_chunk(n_scenarios: int, n_campaigns: int,
                           block_t: int = 256) -> Optional[int]:
    """The largest divisor of ``n_scenarios`` whose one-launch fused round
    fits :data:`ONE_LAUNCH_VMEM_BYTES` (``None`` when even one lane does
    not fit). Divisors only: every scan step must be a full chunk
    (:func:`check_scenario_chunks`)."""
    for spc in range(n_scenarios, 0, -1):
        if n_scenarios % spc == 0 and \
                round_fused_fits(spc, n_campaigns, block_t):
            return spc
    return None


def planned_scenario_chunk(plan: SweepPlan, n_scenarios: int,
                           n_campaigns: int,
                           resolve: Optional[str] = None) -> Optional[int]:
    """The scenario-chunk size ``plan`` will actually execute at, per
    device (``None`` = the whole local batch in one pass).

    An explicit ``plan.scenario_chunks`` always wins. Otherwise the
    executor auto-picks a chunk in exactly one situation: the plan wants
    the fused one-launch round (``resolve="fused"`` where the kernel
    dispatches, unsharded, no event chunks) but the full batch exceeds the
    VMEM gate — then the largest fitting divisor keeps every round on the
    one-launch kernel instead of degrading to two-pass. Exposed as a
    function so tests (and planners) can ask what the executor will do
    without tracing it."""
    if plan.scenario_chunks is not None:
        return plan.scenario_chunks.scenarios_per_chunk
    resolve = pick_resolve(plan.resolve) if resolve is None else resolve
    if (resolve == "fused" and fused_runs_kernel(plan.interpret)
            and plan.placement != "sharded" and plan.chunks is None
            and not round_fused_fits(n_scenarios, n_campaigns,
                                     plan.block_t)):
        return fitting_scenario_chunk(n_scenarios, n_campaigns, plan.block_t)
    return None


def global_event_offset(event_axes, local_n: int) -> jax.Array:
    """Global index of this shard's first event (row-major over event axes;
    call inside ``shard_map``)."""
    idx = jnp.int32(0)
    for ax in event_axes:
        idx = idx * compat_axis_size(ax) + jax.lax.axis_index(ax)
    return idx * local_n


# ---------------------------------------------------------------------------
# Per-lane scalar logic (the bit-for-bit contract between ALL placements)
# ---------------------------------------------------------------------------

def lane_predict(sums, b, s_hat, active, n_hat, *, n_events):
    """Scalar half 1 of an Algorithm-2 round: from the spend of the
    remaining events ``[n_hat, N)`` (``sums``, the canonical block sum of
    the rate partials), predict which campaign caps out next and where its
    block ends.

    Returns ``(c_next, no_cap, n_next)``; pure per-lane O(C) arithmetic, no
    event-log access — every placement runs it verbatim between its two
    reductions, and the fused round kernel repeats it op for op.
    """
    denom = jnp.maximum(n_events - n_hat, 1).astype(sums.dtype)
    rates = sums / denom                      # the remaining-rate estimate
    # time to live (b - s_hat) / rates, spelled as XLA simplifies
    # A / (B / C) = (A * C) / B: written so, every program — XLA's and the
    # fused kernel's, which compiles without that rewrite — rounds alike
    ttl = jnp.where(active & (rates > 0), (b - s_hat) * denom / sums,
                    jnp.float32(jnp.inf))
    ttl = jnp.where(ttl < 0, jnp.float32(0.0), ttl)  # past budget -> retire
    c_next = jnp.argmin(ttl).astype(jnp.int32)
    no_cap = jnp.isinf(ttl[c_next])
    # floor(ttl) clamped to N before the int cast (inf/huge-safe); with
    # step <= N this equals the host's min(n_hat + floor(ttl), N).
    step = jnp.minimum(jnp.floor(ttl[c_next]),
                       jnp.float32(n_events)).astype(jnp.int32)
    n_next = jnp.where(no_cap, jnp.int32(n_events),
                       jnp.minimum(n_hat + step, n_events))
    return c_next, no_cap, n_next


def lane_commit(blk, c_next, no_cap, n_next, s_hat, active, cap, rnd,
                retired, bnds, *, sentinel):
    """Scalar half 2 of an Algorithm-2 round: apply the exact block spends,
    retire the predicted campaign, log the round. Pure per-lane arithmetic."""
    s_hat = s_hat + blk
    cap = jnp.where(no_cap, cap,
                    cap.at[c_next].set(jnp.minimum(n_next + 1, sentinel)))
    active = jnp.where(no_cap, active, active.at[c_next].set(False))
    retired = retired.at[rnd].set(jnp.where(no_cap, -1, c_next))
    bnds = bnds.at[rnd + 1].set(n_next)
    return (s_hat, active, cap, n_next, rnd + 1, retired, bnds)


def lane_round(winners, prices, b, s_hat, active, cap, n_hat, rnd, retired,
               bnds, *, n_events, n_campaigns, sentinel):
    """One Algorithm-2 round for a single lane, given the round's resolved
    (winners, prices): predict the next cap-out from the remaining-rate,
    replay the block up to it, retire the campaign, log the round.

    This is the reference decomposition every executor program realises:
    resolve → canonical rate partials → :func:`lane_predict` → canonical
    block partials → :func:`lane_commit`. The executor's resolve-once round
    body is exactly these stages (same primitives, same order), its fused
    and chunked bodies replace only *where* the two partials tensors are
    produced (one kernel launch / per-chunk scans / per-shard psums) — the
    tensors themselves, and hence every downstream bit, are identical.
    """
    sums = seg_lib.block_from_events(winners, prices, n_campaigns, n_hat,
                                     n_events)
    c_next, no_cap, n_next = lane_predict(sums, b, s_hat, active, n_hat,
                                          n_events=n_events)
    blk = seg_lib.block_from_events(winners, prices, n_campaigns, n_hat,
                                    n_next)
    return lane_commit(blk, c_next, no_cap, n_next, s_hat, active, cap,
                       rnd, retired, bnds, sentinel=sentinel)


# ---------------------------------------------------------------------------
# The one round body + the one while_loop
# ---------------------------------------------------------------------------

def log_layout(plan: SweepPlan, resolve: str, values_local, *,
               n_events: int, resume_offset: int = 0):
    """This device's event rows laid out once a program as the fused
    kernels' block-relative tiles (:func:`resolve_ops.block_tiles`):
    ``(tiles, t, tiles_per_block)`` where the round body hands the whole
    local log to a kernel (the fused kernel, no event ``chunks=``), else
    ``None``. The log never changes within a sweep, so every round and
    every scenario chunk reads the same tiles. An event-chunk scan lays
    each chunk out inside its step instead: laying out the whole log would
    undo its O(events_per_chunk · C) working set."""
    if plan.chunks is not None or not (
            resolve == "fused" and fused_runs_kernel(plan.interpret)):
        return None
    block = seg_lib.reduce_block_size(n_events)
    with jax.named_scope("relayout"):
        return resolve_ops.block_tiles(values_local, block_size=block,
                                       block_t=plan.block_t,
                                       offset_in_block=resume_offset % block)


def _make_round_body(plan: SweepPlan, resolve: str, *, values_local,
                     layout, rules_local, budgets_f32, n_events: int,
                     n_campaigns: int, offset_fn, psum, use_interpret: bool,
                     overlay: Optional[ScenarioOverlay] = None,
                     noise=(None, None), resume_offset: int = 0):
    """Build the per-round body for any (placement, resolve, chunks) cell.

    ``values_local`` is this device's event rows and ``layout`` their
    :func:`log_layout`, made by the caller outside the round loop and any
    scenario-chunk scan; ``offset_fn()`` the global
    index of its first row (0 off-mesh), ``psum`` the cross-device combiner
    (identity off-mesh). ``overlay`` carries this lane slice's (S_local, C)
    intervention fields (key already stripped), ``noise`` the (local_n, C)
    CRN draws aligned with ``values_local``. ``resume_offset`` is the
    static global index of the first local row in a *resumable* fold
    (:func:`execute_sweep_resumable`); non-zero offsets disqualify the
    one-launch fused round, whose kernel assumes its rows start the log —
    the two-pass shape places rows globally via ``index_offset`` instead.
    The returned ``round_body(core, keep)`` maps the carried Algorithm-2
    state to the next round's state via :func:`lane_commit`; the loop
    scaffolding freezes finished lanes.
    """
    sentinel = jnp.int32(never_capped(n_events))
    lane_pred = functools.partial(lane_predict, n_events=n_events)
    lane_comm = functools.partial(lane_commit, sentinel=sentinel)
    second = rules_local.kind == "second_price"
    block = seg_lib.reduce_block_size(n_events)
    local_n = values_local.shape[0]
    b = budgets_f32
    chunks = plan.chunks
    fused_kernel = resolve == "fused" and fused_runs_kernel(plan.interpret)
    one_launch = fused_kernel and plan.placement != "sharded" \
        and chunks is None and resume_offset == 0 \
        and round_fused_fits(budgets_f32.shape[0], n_campaigns,
                             plan.block_t)
    two_pass = chunks is not None or (fused_kernel and not one_launch)

    ol = overlay
    z_local, u_local = noise if noise is not None else (None, None)
    per_event = ol is not None and ol.per_event
    live_static = None
    if ol is not None and ol.live_start is not None and not per_event:
        # time_varying=False promises every window is empty-or-full, so the
        # windows fold into the activation mask once per round and every
        # kernel back-end keeps working
        live_static = ol.live_stop > ol.live_start
    if per_event:
        # placeholder rows for absent fields — the static presence gates in
        # resolve_all keep them out of the generated program
        shape = budgets_f32.shape
        start_rows = (ol.live_start if ol.live_start is not None
                      else jnp.zeros(shape, jnp.int32))
        stop_rows = (ol.live_stop if ol.live_stop is not None
                     else jnp.full(shape, n_events, jnp.int32))
        sig_rows = (ol.bid_sigma if ol.bid_sigma is not None
                    else jnp.zeros(shape, jnp.float32))
        prob_rows = (ol.part_prob if ol.part_prob is not None
                     else jnp.ones(shape, jnp.float32))

    def resolve_all(v, act, offset, z, u):
        """(S_local, T) winners/prices of the rows in ``v`` — purely local,
        no collectives (the auction is per-event). ``offset``/``z``/``u``
        feed the per-event overlay path; the overlay-free program ignores
        them."""
        if not per_event:
            if resolve == "pallas":
                winners, prices, _ = resolve_ops.sweep_resolve(
                    v, rules_local.multipliers, act, rules_local.reserve,
                    second_price=second, block_t=plan.block_t,
                    interpret=use_interpret)
                return winners, prices
            return jax.vmap(lambda a, r: auction.resolve(v, a, r),
                            in_axes=(0, 0))(act, rules_local)
        gidx = offset + jnp.arange(v.shape[0], dtype=jnp.int32)

        def one(a, r, start, stop, sig, prob):
            vv = v
            if ol.bid_sigma is not None:
                vv = vv * jnp.exp(sig[None, :] * z)
            m = jnp.broadcast_to(a[None, :], vv.shape)
            if ol.live_start is not None:
                m = m & (gidx[:, None] >= start[None, :]) \
                      & (gidx[:, None] < stop[None, :])
            if ol.part_prob is not None:
                m = m & (u < prob[None, :])
            return auction.resolve(vv, m, r)

        return jax.vmap(one)(act, rules_local, start_rows, stop_rows,
                             sig_rows, prob_rows)

    def weighted_partials(winners, prices, lo, hi, offset):
        """(S_l, G, C) canonical partials of events in global ``[lo, hi)``,
        rows placed on the global grid via ``offset`` (NOT yet psum'd)."""
        gidx = offset + jnp.arange(winners.shape[-1], dtype=jnp.int32)

        def one(w, p, lo_s, hi_s):
            weight = ((gidx >= lo_s) & (gidx < hi_s)).astype(p.dtype)
            return seg_lib.partial_spend_sums(
                w, p, n_campaigns, weight, block_size=block,
                index_offset=offset)

        return jax.vmap(one)(winners, prices, lo, hi)

    def exchange(parts):
        """``psum`` of a round's partials, scoped ``exchange`` so that a
        device trace's op metadata names the collectives (nothing under
        the scope off-mesh, where ``psum`` is the identity)."""
        with jax.named_scope("exchange"):
            return psum(parts)

    kernel_kw = dict(n_events_global=n_events,
                     reduce_blocks=seg_lib.REDUCE_BLOCKS, second_price=second,
                     skip_retired=plan.skip_retired, interpret=use_interpret)

    def kernel_partials(v, active, keep, lo, hi, offset):
        """One fused resolve+reduce kernel pass (NOT psum'd) over the event
        chunk ``v``, laid out here, or with ``v=None`` over the whole local
        log's ``layout``. Shards and chunks start on canonical block
        boundaries; only a resumable fold's rows start
        ``resume_offset % block`` into one."""
        args = (rules_local.multipliers, active, rules_local.reserve, lo, hi,
                keep, offset)
        if v is None:
            tiles, t, tpb = layout
            return resolve_ops.sweep_partials_tiles(
                tiles, *args, n_rows=local_n, t=t, tiles_per_block=tpb,
                **kernel_kw)
        return resolve_ops.sweep_partials(
            v, *args, offset_in_block=resume_offset % block,
            block_t=plan.block_t, **kernel_kw)

    def window_partials(act, keep, lo, hi):
        """The two-pass reduction: psum'd (S_l, G, C) partials of the global
        window [lo, hi) — whole-shard kernel pass, or a chunk scan."""
        offset = offset_fn()
        if chunks is None:
            return exchange(kernel_partials(None, act, keep, lo, hi, offset))
        epc = chunks.events_per_chunk
        n_chunks = local_n // epc
        v_chunks = values_local.reshape(n_chunks, epc,
                                        values_local.shape[1])
        chunked = lambda x: None if x is None else x.reshape(
            n_chunks, epc, n_campaigns)

        def step(acc, xs):
            v_k, z_k, u_k, k = xs
            off_k = offset + k * epc
            if fused_kernel:
                parts_k = kernel_partials(v_k, act, keep, lo, hi, off_k)
            else:
                winners, prices = resolve_all(v_k, act, off_k, z_k, u_k)
                parts_k = weighted_partials(winners, prices, lo, hi, off_k)
            # every canonical block is owned by exactly one chunk, so this
            # accumulation only ever adds exact zeros to a block's partial —
            # the chunk-scan analogue of the mesh psum's exactness
            return acc + parts_k, None

        acc0 = jnp.zeros((act.shape[0], seg_lib.REDUCE_BLOCKS,
                          n_campaigns), jnp.float32)
        parts, _ = jax.lax.scan(
            step, acc0, (v_chunks, chunked(z_local), chunked(u_local),
                         jnp.arange(n_chunks, dtype=jnp.int32)))
        return exchange(parts)

    def round_body(core, keep):
        s_hat, active, cap, n_hat, rnd, retired, bnds = core
        # static live windows AND into the mask every resolve sees;
        # lane_predict keeps the carried `active` (a masked-off campaign
        # never wins, so its rate is 0 and its ttl is inf either way —
        # bitwise identical across the two conventions)
        act = active if live_static is None else active & live_static
        if one_launch:
            # resolve + rate partials + in-kernel prediction + block
            # partials in ONE launch; winners/prices never reach HBM
            tiles, t, tpb = layout
            _, block_parts, c_next, no_cap, n_next = \
                resolve_ops.round_fused_tiles(
                    tiles, rules_local.multipliers, act, rules_local.reserve,
                    b, s_hat, n_hat, keep, n_events=n_events, t=t,
                    tiles_per_block=tpb, reduce_blocks=seg_lib.REDUCE_BLOCKS,
                    second_price=second, skip_retired=plan.skip_retired,
                    interpret=use_interpret)
            blk = seg_lib.sum_blocks(block_parts)
        else:
            hi_all = jnp.full_like(n_hat, n_events)
            if two_pass:
                rate_parts = window_partials(act, keep, n_hat, hi_all)
            else:
                winners, prices = resolve_all(values_local, act, offset_fn(),
                                              z_local, u_local)
                rate_parts = exchange(weighted_partials(
                    winners, prices, n_hat, hi_all, offset_fn()))
            with jax.named_scope("predict"):
                c_next, no_cap, n_next = jax.vmap(lane_pred)(
                    seg_lib.sum_blocks(rate_parts), b, s_hat, active, n_hat)
            if two_pass:
                block_parts = window_partials(act, keep, n_hat, n_next)
            else:
                block_parts = exchange(weighted_partials(
                    winners, prices, n_hat, n_next, offset_fn()))
            blk = seg_lib.sum_blocks(block_parts)
        with jax.named_scope("commit"):
            return jax.vmap(lane_comm)(blk, c_next, no_cap, n_next, s_hat,
                                       active, cap, rnd, retired, bnds)

    return round_body


def _run_loop(round_body, *, s_local: int, n_events: int, n_campaigns: int,
              scenario_axis=None, init_core=None):
    """The one while_loop every placement shares: run rounds until every
    lane (everywhere) has retired its last cap-out, freezing finished lanes
    by select. Returns the carried core state. ``init_core`` overrides the
    fresh initial state — the resumable fold seeds it from a
    :class:`SweepCarry` (carried burnout state, fresh per-fold round log)."""
    sentinel = jnp.int32(never_capped(n_events))

    def alive(core):
        _, active, _, n_hat, rnd, _, _ = core
        return (rnd < n_campaigns + 1) & (n_hat < n_events) & active.any(-1)

    def global_any(flags):
        # with a meshed scenario axis the loop must run until the LAST
        # slice retires its last cap-out (same trip count everywhere so
        # the event-axis psums stay aligned); event-axis devices already
        # agree (replicated state), so only the scenario axis reduces.
        local = jnp.any(flags)
        if scenario_axis is None:
            return local
        return jax.lax.psum(local.astype(jnp.int32), scenario_axis) > 0

    def body(st):
        core, _ = st
        keep = alive(core)
        new = round_body(core, keep)
        merged = jax.tree.map(
            lambda n, o: jnp.where(
                keep.reshape(keep.shape + (1,) * (n.ndim - 1)), n, o),
            new, core)
        return merged, global_any(alive(merged))

    if init_core is None:
        init_core = (
            jnp.zeros((s_local, n_campaigns), jnp.float32),
            jnp.ones((s_local, n_campaigns), bool),
            jnp.full((s_local, n_campaigns), sentinel, jnp.int32),
            jnp.zeros((s_local,), jnp.int32),
            jnp.zeros((s_local,), jnp.int32),
            jnp.full((s_local, n_campaigns + 1), -1, jnp.int32),
            jnp.zeros((s_local, n_campaigns + 2), jnp.int32),
        )
    core, _ = jax.lax.while_loop(
        lambda st: st[1], body, (init_core, global_any(alive(init_core))))
    return core


# ---------------------------------------------------------------------------
# The placements: batched (one device) and sharded (shard_map)
# ---------------------------------------------------------------------------

def _unpack(core):
    s_hat, active, cap, n_hat, rnd, retired, bnds = core
    return s_hat, cap, retired, bnds, rnd, n_hat


def _run_lanes(plan: SweepPlan, resolve: str, *, values_local, mult_local,
               res_local, kind, budgets_f32, n_events: int,
               n_campaigns: int, offset_fn, psum, use_interpret: bool,
               scenario_axis=None, overlay: Optional[ScenarioOverlay] = None,
               noise=(None, None)):
    """Run the local scenario lanes through the round program, scanning
    fixed scenario chunks when the plan asks for (or auto-picks) them.

    Each chunk builds and runs the IDENTICAL round body + while_loop over
    its slice of the lane state. Per-lane arithmetic never reads other
    lanes (resolve/partials/predict/commit are all vmapped per lane, and
    the loop freezes finished lanes by select, so a chunk looping fewer or
    more rounds than the full batch changes no lane's bits) — scenario
    chunks are therefore bit-for-bit the unchunked program, the S-axis
    analogue of the event-chunk exactness argument.
    """
    s_local = budgets_f32.shape[0]
    # laid out once, before the scenario-chunk scan and the round loop
    layout = log_layout(plan, resolve, values_local, n_events=n_events)

    def run(b_c, mult_c, res_c, ol_c):
        rules_c = AuctionRule(multipliers=mult_c, reserve=res_c, kind=kind)
        round_body = _make_round_body(
            plan, resolve, values_local=values_local, layout=layout,
            rules_local=rules_c,
            budgets_f32=b_c, n_events=n_events, n_campaigns=n_campaigns,
            offset_fn=offset_fn, psum=psum, use_interpret=use_interpret,
            overlay=ol_c, noise=noise)
        return _run_loop(round_body, s_local=b_c.shape[0],
                         n_events=n_events, n_campaigns=n_campaigns,
                         scenario_axis=scenario_axis)

    spc = planned_scenario_chunk(plan, s_local, n_campaigns, resolve)
    if spc is None or spc == s_local:
        return run(budgets_f32, mult_local, res_local, overlay)
    n_chunks = s_local // spc
    # the overlay's (S_local, C) fields slice along scenarios exactly like
    # budgets/rules; the (local_n, C) noise fields are event-axis and stay
    # closure-captured (shared by every scenario chunk — the CRN contract)
    ol_chunks = None if overlay is None else jax.tree.map(
        lambda x: x.reshape((n_chunks, spc) + x.shape[1:]), overlay)
    out = jax.lax.map(
        lambda xs: run(*xs),
        (budgets_f32.reshape(n_chunks, spc, n_campaigns),
         mult_local.reshape(n_chunks, spc, n_campaigns),
         res_local.reshape(n_chunks, spc),
         ol_chunks))
    return jax.tree.map(lambda x: x.reshape((s_local,) + x.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=("plan",))
def _sweep_batched(values, budgets, rules, overlay, plan: SweepPlan):
    """The scenario-batched Algorithm-2 loop on one device."""
    check_batch_shapes(values, budgets, rules)
    resolve = pick_resolve(plan.resolve)
    n_events, n_campaigns = values.shape
    n_scenarios = budgets.shape[0]
    check_overlay(overlay, n_scenarios=n_scenarios, n_campaigns=n_campaigns,
                  resolve=resolve, interpret=plan.interpret)
    check_chunks(plan.chunks, n_events=n_events, local_n=n_events)
    check_scenario_chunks(plan.scenario_chunks, n_scenarios=n_scenarios,
                          local_s=n_scenarios)
    use_interpret = kernels.use_interpret(plan.interpret)
    noise = _overlay_noise(overlay, n_events, n_campaigns)
    core = _run_lanes(
        plan, resolve, values_local=values, mult_local=rules.multipliers,
        res_local=jnp.asarray(rules.reserve, jnp.float32), kind=rules.kind,
        budgets_f32=budgets.astype(jnp.float32), n_events=n_events,
        n_campaigns=n_campaigns, offset_fn=lambda: 0, psum=lambda x: x,
        use_interpret=use_interpret, overlay=_local_overlay(overlay),
        noise=noise)
    return _unpack(core)


@functools.partial(jax.jit, static_argnames=("plan",))
def _sweep_sharded(values, budgets, rules, overlay, plan: SweepPlan):
    """The same loop under ``shard_map`` on ``plan.mesh``: events sharded
    over ``spec.event_axes``, scenarios vmapped per device or sharded over
    ``spec.scenario_axis``; two psums per round (one per reduction)."""
    spec = plan.mesh
    check_sharded_shapes(values, budgets, rules, spec)
    resolve = pick_resolve(plan.resolve)
    n_events, n_campaigns = values.shape
    local_n = n_events // spec.event_device_count
    check_overlay(overlay, n_scenarios=budgets.shape[0],
                  n_campaigns=n_campaigns, resolve=resolve,
                  interpret=plan.interpret)
    check_chunks(plan.chunks, n_events=n_events, local_n=local_n)
    check_scenario_chunks(
        plan.scenario_chunks, n_scenarios=budgets.shape[0],
        local_s=budgets.shape[0] // spec.scenario_device_count)
    use_interpret = kernels.use_interpret(plan.interpret)
    axes = tuple(spec.event_axes)
    sc = spec.scenario_axis

    spec_vals = P(axes, None)
    spec_sc2 = P(sc, None)        # (S, ...) arrays; sc=None -> replicated
    spec_sc1 = P(sc)

    # the overlay's CRN noise is drawn ONCE on global indices and sharded
    # like the event log, so every device sees the identical draws its rows
    # would see on one device; the (S, C) overlay fields shard with the
    # scenario arrays
    z, u = _overlay_noise(overlay, n_events, n_campaigns)
    ol_local = _local_overlay(overlay)
    ol_spec = jax.tree.map(lambda _: spec_sc2, ol_local)
    noise_spec = jax.tree.map(lambda _: spec_vals, (z, u))

    @functools.partial(
        shard_map, mesh=spec.mesh,
        in_specs=(spec_vals, spec_sc2, spec_sc2, spec_sc1, ol_spec,
                  noise_spec),
        out_specs=(spec_sc2, spec_sc2, spec_sc2, spec_sc2, spec_sc1,
                   spec_sc1))
    def _driver(values_local, b_local, mult_local, res_local, ol_shard,
                noise_shard):
        core = _run_lanes(
            plan, resolve, values_local=values_local,
            mult_local=mult_local, res_local=res_local, kind=rules.kind,
            budgets_f32=b_local.astype(jnp.float32), n_events=n_events,
            n_campaigns=n_campaigns,
            offset_fn=lambda: global_event_offset(axes, local_n),
            psum=lambda x: jax.lax.psum(x, axes),
            use_interpret=use_interpret, scenario_axis=sc,
            overlay=ol_shard, noise=noise_shard)
        return _unpack(core)

    return _driver(values, budgets, rules.multipliers,
                   jnp.asarray(rules.reserve, jnp.float32), ol_local,
                   (z, u))


# ---------------------------------------------------------------------------
# Host-streamed placement: the log lives in host RAM, chunks flow H2D
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("plan", "resolve", "kind",
                                             "n_events", "n_campaigns",
                                             "offset_in_block"))
def _hs_chunk_partials(acc, v_k, mult, res, act, keep, lo, hi, off_k, *,
                       plan: SweepPlan, resolve: str, kind: str,
                       n_events: int, n_campaigns: int,
                       offset_in_block: int):
    """One pipeline step: fold chunk ``v_k`` (global rows from ``off_k``,
    ``off_k % block == offset_in_block``) into the (S, G, C)
    canonical-partials accumulator.

    This is the IDENTICAL per-chunk program as the device-resident chunk
    scan's step (``window_partials`` in :func:`_make_round_body`) — same
    resolve, same weighted canonical partials on the global grid, same
    in-order accumulate — jitted standalone so the host round loop can
    interleave its dispatch with the next chunk's H2D copy. ``off_k`` is a
    traced scalar, so every chunk reuses one compiled program.
    """
    second = kind == "second_price"
    use_interpret = kernels.use_interpret(plan.interpret)
    if resolve == "fused" and fused_runs_kernel(plan.interpret):
        parts_k = resolve_ops.sweep_partials(
            v_k, mult, act, res, lo, hi, keep, off_k,
            n_events_global=n_events, reduce_blocks=seg_lib.REDUCE_BLOCKS,
            offset_in_block=offset_in_block, second_price=second,
            skip_retired=plan.skip_retired, block_t=plan.block_t,
            interpret=use_interpret)
    else:
        if resolve == "pallas":
            winners, prices, _ = resolve_ops.sweep_resolve(
                v_k, mult, act, res, second_price=second,
                block_t=plan.block_t, interpret=use_interpret)
        else:
            rules_local = AuctionRule(multipliers=mult, reserve=res,
                                      kind=kind)
            winners, prices = jax.vmap(
                lambda a, r: auction.resolve(v_k, a, r),
                in_axes=(0, 0))(act, rules_local)
        gidx = off_k + jnp.arange(v_k.shape[0], dtype=jnp.int32)
        block = seg_lib.reduce_block_size(n_events)

        def one(w, p, lo_s, hi_s):
            weight = ((gidx >= lo_s) & (gidx < hi_s)).astype(p.dtype)
            return seg_lib.partial_spend_sums(
                w, p, n_campaigns, weight, block_size=block,
                index_offset=off_k)

        parts_k = jax.vmap(one)(winners, prices, lo, hi)
    # same exactness argument as the device-resident chunk scan: every
    # canonical block is owned by exactly one chunk, so this add only ever
    # contributes exact zeros to blocks other chunks own
    return acc + parts_k


@functools.partial(jax.jit, static_argnames=("n_events",))
def _hs_predict(rate_parts, b, s_hat, active, n_hat, *, n_events: int):
    """Scalar half 1 between the two streamed passes (per-lane, O(S·C))."""
    return jax.vmap(functools.partial(lane_predict, n_events=n_events))(
        seg_lib.sum_blocks(rate_parts), b, s_hat, active, n_hat)


@functools.partial(jax.jit, static_argnames=("n_events",))
def _hs_commit(core, keep, block_parts, c_next, no_cap, n_next, *,
               n_events: int):
    """Scalar half 2 plus the loop scaffolding's frozen-lane select: commit
    the block partials into the carried core exactly as ``_run_loop``'s
    body merges a round, and report which lanes stay alive."""
    s_hat, active, cap, n_hat, rnd, retired, bnds = core
    blk = seg_lib.sum_blocks(block_parts)
    lane_comm = functools.partial(
        lane_commit, sentinel=jnp.int32(never_capped(n_events)))
    new = jax.vmap(lane_comm)(blk, c_next, no_cap, n_next, s_hat, active,
                              cap, rnd, retired, bnds)
    merged = jax.tree.map(
        lambda n, o: jnp.where(
            keep.reshape(keep.shape + (1,) * (n.ndim - 1)), n, o),
        new, core)
    n_campaigns = s_hat.shape[1]
    _, active_m, _, n_hat_m, rnd_m, _, _ = merged
    alive = (rnd_m < n_campaigns + 1) & (n_hat_m < n_events) \
        & active_m.any(-1)
    return merged, alive


@functools.partial(jax.jit, static_argnames=("n_events",))
def _hs_alive(core, *, n_events: int):
    _, active, _, n_hat, rnd, _, _ = core
    n_campaigns = active.shape[1]
    return (rnd < n_campaigns + 1) & (n_hat < n_events) & active.any(-1)


def _sweep_hoststream(stream: HostStream, budgets, rules, plan: SweepPlan,
                      *, carry=None):
    """The host-streamed Algorithm-2 loop: one device, log in host RAM.

    Runs the device-resident chunked two-pass round program — same
    per-chunk canonical partials, same predict/commit scalars, same
    frozen-lane merge, so results are bit-for-bit identical on aligned
    sizes — but the round loop lives on the host, and each reduction
    window streams the log chunk-by-chunk through ``jax.device_put``.
    With ``plan.chunks.prefetch`` the pipeline is double-buffered: chunk
    k's jitted partials step is dispatched (async), then chunk k+1's H2D
    copy is issued immediately, so transfer overlaps compute;
    ``prefetch=False`` serialises copy → compute per chunk (the benchmark
    baseline). ``carry`` seeds a resumable fold at global offset
    ``carry.n_events_seen`` exactly as :func:`_resume_batched` does.
    Returns the raw core state tuple (callers ``_unpack``).
    """
    resolve = pick_resolve(plan.resolve)
    check_batch_shapes(stream, budgets, rules)
    n_new, n_campaigns = stream.shape
    n_seen = 0 if carry is None else carry.n_events_seen
    n_events = n_seen + n_new
    check_chunks(plan.chunks, n_events=n_events, local_n=n_new)
    epc = plan.chunks.events_per_chunk
    prefetch = plan.chunks.prefetch
    n_chunks = n_new // epc
    s_local = budgets.shape[0]
    sentinel = jnp.int32(never_capped(n_events))

    b = jnp.asarray(budgets).astype(jnp.float32)
    mult = jnp.asarray(rules.multipliers)
    res = jnp.asarray(rules.reserve, jnp.float32)
    # every chunk starts n_seen (mod the canonical block) into a block:
    # chunks hold whole blocks (check_chunks above)
    statics = dict(plan=plan, resolve=resolve, kind=rules.kind,
                   n_events=n_events, n_campaigns=n_campaigns,
                   offset_in_block=n_seen % seg_lib.reduce_block_size(
                       n_events))

    if carry is None:
        core = (
            jnp.zeros((s_local, n_campaigns), jnp.float32),
            jnp.ones((s_local, n_campaigns), bool),
            jnp.full((s_local, n_campaigns), sentinel, jnp.int32),
            jnp.zeros((s_local,), jnp.int32),
            jnp.zeros((s_local,), jnp.int32),
            jnp.full((s_local, n_campaigns + 1), -1, jnp.int32),
            jnp.zeros((s_local, n_campaigns + 2), jnp.int32),
        )
    else:
        # carried burnout state + a fresh per-fold round log, with
        # not-yet-capped sentinels moved to the grown log's — the exact
        # seeding _resume_batched performs
        active0 = jnp.asarray(carry.active)
        n_hat0 = jnp.asarray(carry.n_hat).astype(jnp.int32)
        core = (
            jnp.asarray(carry.s_hat).astype(jnp.float32),
            active0,
            jnp.where(active0, sentinel,
                      jnp.asarray(carry.cap_times, jnp.int32)),
            n_hat0,
            jnp.zeros((s_local,), jnp.int32),
            jnp.full((s_local, n_campaigns + 1), -1, jnp.int32),
            jnp.zeros((s_local, n_campaigns + 2),
                      jnp.int32).at[:, 0].set(n_hat0),
        )

    def stream_pass(act, keep, lo, hi):
        acc = jnp.zeros((s_local, seg_lib.REDUCE_BLOCKS, n_campaigns),
                        jnp.float32)
        if not prefetch:
            # synchronous baseline: wait out each copy, then each step
            for k in range(n_chunks):
                cur = jax.block_until_ready(
                    jax.device_put(stream.chunk(k * epc, (k + 1) * epc)))
                acc = jax.block_until_ready(_hs_chunk_partials(
                    acc, cur, mult, res, act, keep, lo, hi,
                    jnp.int32(n_seen + k * epc), **statics))
            return acc
        # double-buffered: dispatch chunk k's step (async), then
        # immediately issue chunk k+1's H2D copy so it overlaps
        buf = jax.device_put(stream.chunk(0, epc))
        for k in range(n_chunks):
            cur = buf
            acc = _hs_chunk_partials(acc, cur, mult, res, act, keep, lo,
                                     hi, jnp.int32(n_seen + k * epc),
                                     **statics)
            if k + 1 < n_chunks:
                buf = jax.device_put(
                    stream.chunk((k + 1) * epc, (k + 2) * epc))
        return acc

    def any_alive(keep) -> bool:
        with obs.span("executor.sync"):
            return bool(jax.device_get(jnp.any(keep)))

    keep = _hs_alive(core, n_events=n_events)
    alive = any_alive(keep)
    while alive:
        with obs.span("executor.round"):
            s_hat, active, cap, n_hat, rnd, retired, bnds = core
            hi_all = jnp.full_like(n_hat, n_events)
            rate_parts = stream_pass(active, keep, n_hat, hi_all)
            c_next, no_cap, n_next = _hs_predict(rate_parts, b, s_hat,
                                                 active, n_hat,
                                                 n_events=n_events)
            block_parts = stream_pass(active, keep, n_hat, n_next)
            core, keep = _hs_commit(core, keep, block_parts, c_next, no_cap,
                                    n_next, n_events=n_events)
            alive = any_alive(keep)
    return core


# ---------------------------------------------------------------------------
# Multi-host placement: the sharded program on a jax.distributed mesh
# ---------------------------------------------------------------------------

def _sweep_multihost(values_local, budgets, rules, overlay,
                     plan: SweepPlan):
    """The sharded program on a ``jax.distributed`` process mesh.

    Each process passes its own contiguous event shard (``values_local``)
    plus full replicated copies of budgets/rules; the shards are assembled
    into one global array (:func:`repro.compat.host_local_to_global`) whose
    row-major device placement matches
    :meth:`~repro.launch.mesh.SweepMeshSpec.for_processes`'s
    ``index_offset`` contract, and the IDENTICAL :func:`_sweep_sharded`
    program runs on it — the same two per-round psums now cross processes,
    still moving only the O(S·G·C) canonical partials per round. Outputs
    come back replicated on every process. Under one process this
    degenerates exactly to ``_sweep_sharded``, which is also the
    bit-for-bit bridge: multihost == single-process sharded == batched on
    aligned shapes (tests/test_multihost.py pins the 2-process case).
    """
    spec = plan.mesh
    if spec.scenario_axis is not None:
        raise ValueError(
            "placement='multihost' shards events over processes only; "
            "scenario-axis process meshes are not supported (shard "
            "scenarios within one process via placement='sharded').")
    if overlay is not None:
        raise ValueError(
            "overlays are not supported with placement='multihost' yet; "
            "run overlay families on placement='sharded' or 'batched'.")
    mesh = spec.mesh
    axes = tuple(spec.event_axes)
    rep2, rep1 = P(None, None), P(None)
    g_values = host_local_to_global(jnp.asarray(values_local), mesh,
                                    P(axes, None))
    g_budgets = host_local_to_global(jnp.asarray(budgets), mesh, rep2)
    g_rules = AuctionRule(
        multipliers=host_local_to_global(jnp.asarray(rules.multipliers),
                                         mesh, rep2),
        reserve=host_local_to_global(
            jnp.asarray(rules.reserve, jnp.float32), mesh, rep1),
        kind=rules.kind)
    return _sweep_sharded(g_values, g_budgets, g_rules, None,
                          dataclasses.replace(plan, placement="sharded"))


def execute_sweep(values, budgets, rules, plan: SweepPlan, *,
                  overlay: Optional[ScenarioOverlay] = None):
    """Run the Algorithm-2 sweep program described by ``plan``.

    ``placement="batched"``/``"sharded"`` take batched inputs (budgets
    (S, C), stacked rules) and return the batched tuple ``(s_hat (S, C),
    cap_times (S, C), retired (S, C+1), boundaries (S, C+2), num_rounds
    (S,), n_hat (S,))``; ``placement="device"`` takes ONE scenario
    (budgets (C,), unstacked rule) and returns the unbatched tuple.

    ``overlay`` threads a :class:`~repro.core.types.ScenarioOverlay`
    (per-scenario live windows, CRN bid noise / participation jitter —
    the lowering target of :mod:`repro.scenarios`) through the round body;
    ``None`` generates the exact overlay-free program. For
    ``placement="device"`` the overlay's array fields are unbatched
    ``(C,)`` rows, matching the unbatched budgets/rule.

    A :class:`HostStream` ``values`` (or ``chunks.source="host"``, which
    pulls an in-memory ``values`` back to host once) selects the
    host-streamed driver: the log stays in host RAM and every round
    streams it through the double-buffered ``device_put`` pipeline —
    bit-for-bit the device-resident program on aligned chunk sizes.
    ``placement="multihost"`` takes THIS PROCESS's event shard as
    ``values`` (the full log under a single process) and returns
    replicated outputs on every process.

    ``plan.block_t="auto"`` / ``plan.tuned=True`` resolve here — before
    any jitted program sees the plan — through the tuning cache + cost
    model (:func:`resolve_auto_plan`); the resolved plan's outputs are
    bit-for-bit the default plan's.
    """
    lanes = np.shape(budgets)[0] if np.ndim(budgets) == 2 else 1
    with obs.span("executor.sweep", placement=plan.placement, lanes=lanes,
                  events=values.shape[0]) as sweep:
        if needs_tuning(plan):
            n_ev, n_c = (values.shape if isinstance(values, HostStream)
                         else tuple(values.shape))
            b = jnp.asarray(budgets)
            plan = resolve_auto_plan(
                plan, n_events=int(n_ev), n_campaigns=int(n_c),
                n_scenarios=int(b.shape[0]) if b.ndim == 2 else 1)
        # where the fused kernels' tiles are laid out (log_layout): once
        # before the round loop, or per event chunk inside the chunk scan
        sweep.set(layout="once" if plan.chunks is None else "per_chunk")
        # the fused kernels skip every grid step whose tile holds no row of
        # its lane's window (kernels/auction_resolve/round_fused.py)
        if pick_resolve(plan.resolve) == "fused" \
                and fused_runs_kernel(plan.interpret):
            sweep.set(tile_skip="window")
        if plan.placement == "sharded":
            shards = plan.mesh.event_device_count
            sweep.set(shards=shards, local_events=values.shape[0] // shards)
        if isinstance(values, HostStream) or (
                plan.chunks is not None and plan.chunks.source == "host"):
            check_host_stream(plan, overlay=overlay)
            stream = values if isinstance(values, HostStream) \
                else HostStream.from_array(values)
            if plan.placement == "device":
                rules_b = AuctionRule(
                    multipliers=rules.multipliers[None, :],
                    reserve=jnp.asarray(rules.reserve, jnp.float32)[None],
                    kind=rules.kind)
                core = _sweep_hoststream(
                    stream, jnp.asarray(budgets)[None, :], rules_b,
                    dataclasses.replace(plan, placement="batched"))
                return tuple(x[0] for x in _unpack(core))
            return _unpack(_sweep_hoststream(stream, budgets, rules, plan))
        if plan.placement == "multihost":
            return _sweep_multihost(values, budgets, rules, overlay, plan)
        if plan.placement == "sharded":
            return _sweep_sharded(values, budgets, rules, overlay, plan)
        if plan.placement == "device":
            rules_b = AuctionRule(
                multipliers=rules.multipliers[None, :],
                reserve=jnp.asarray(rules.reserve, jnp.float32)[None],
                kind=rules.kind)
            if overlay is not None:
                expand = lambda x: None if x is None else x[None]
                overlay = dataclasses.replace(
                    overlay, live_start=expand(overlay.live_start),
                    live_stop=expand(overlay.live_stop),
                    bid_sigma=expand(overlay.bid_sigma),
                    part_prob=expand(overlay.part_prob))
            out = _sweep_batched(
                values, budgets[None, :], rules_b, overlay,
                dataclasses.replace(plan, placement="batched"))
            return tuple(x[0] for x in out)
        return _sweep_batched(values, budgets, rules, overlay, plan)


# ---------------------------------------------------------------------------
# Resumable execution: fold new event slabs into carried burnout state
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SweepCarry:
    """The per-scenario burnout state carried between resumable folds.

    This is exactly the state the chunk scan already carries across event
    chunks *within* a sweep — ``(s_hat, active, cap_times, n_hat)`` —
    promoted to a first-class, persistable value so a long-lived service
    can fold newly appended event slabs into it
    (:func:`execute_sweep_resumable`) instead of replaying the whole log.

    ``cap_times`` are GLOBAL event indices; campaigns that have not capped
    hold the sentinel ``never_capped(n_events_seen)``, which each fold
    re-maps to the grown log's sentinel (capped campaigns keep their
    recorded index). ``n_events_seen`` (static metadata, not a leaf) is the
    total number of events already folded in — the global offset of the
    next fold's first row.

    A registered pytree dataclass: it rides through ``jax.jit`` /
    ``jax.device_get`` / ``jax.device_put`` and survives a pickle
    round-trip with bitwise-identical continuation (tests/test_service.py —
    the persistence seam multi-host serving needs).

    Semantics note: a fold's round predictions use only the events seen so
    far (no lookahead — Algorithm 2's remaining-rate estimates are
    window-sums over the *available* log), so the carried state is the
    **causal / streaming** estimator of the growing log. It is bitwise the
    offline full-log sweep when the whole log arrives in one fold; once the
    log is split across folds the offline estimator may predict different
    cap-out rounds because it sees future events. The service's exact
    ``ask`` path answers offline questions by replaying the full stored log
    (docs/ARCHITECTURE.md "Service layer").
    """

    s_hat: jax.Array       # (S, C) float32 spend so far
    active: jax.Array      # (S, C) bool   not-yet-capped mask
    cap_times: jax.Array   # (S, C) int32  global cap indices / sentinel
    n_hat: jax.Array       # (S,)   int32  global frontier per lane
    n_events_seen: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_scenarios(self) -> int:
        return self.s_hat.shape[0]

    @property
    def num_campaigns(self) -> int:
        return self.s_hat.shape[1]


def initial_carry(n_scenarios: int, n_campaigns: int) -> SweepCarry:
    """The empty-log carry: nothing spent, everyone active, frontier at 0."""
    return SweepCarry(
        s_hat=jnp.zeros((n_scenarios, n_campaigns), jnp.float32),
        active=jnp.ones((n_scenarios, n_campaigns), bool),
        cap_times=jnp.full((n_scenarios, n_campaigns), never_capped(0),
                           jnp.int32),
        n_hat=jnp.zeros((n_scenarios,), jnp.int32),
        n_events_seen=0)


@functools.partial(jax.jit, static_argnames=("plan", "n_seen"))
def _resume_batched(values_new, budgets, rules, s_hat0, active0, cap0,
                    n_hat0, plan: SweepPlan, n_seen: int):
    """One resumable fold: the batched round program over the NEW rows only,
    seeded from carried state, with global indexing at offset ``n_seen``."""
    resolve = pick_resolve(plan.resolve)
    n_new, n_campaigns = values_new.shape
    n_total = n_seen + n_new
    check_chunks(plan.chunks, n_events=n_total, local_n=n_new)
    use_interpret = kernels.use_interpret(plan.interpret)
    sentinel = jnp.int32(never_capped(n_total))
    # not-yet-capped campaigns carried the previous fold's sentinel; move
    # them to the grown log's (capped campaigns keep their global index)
    cap0 = jnp.where(active0, sentinel, cap0)
    s_local = budgets.shape[0]
    rules_c = AuctionRule(multipliers=rules.multipliers,
                          reserve=jnp.asarray(rules.reserve, jnp.float32),
                          kind=rules.kind)
    round_body = _make_round_body(
        plan, resolve, values_local=values_new,
        layout=log_layout(plan, resolve, values_new, n_events=n_total,
                          resume_offset=n_seen),
        rules_local=rules_c,
        budgets_f32=budgets.astype(jnp.float32), n_events=n_total,
        n_campaigns=n_campaigns, offset_fn=lambda: n_seen,
        psum=lambda x: x, use_interpret=use_interpret,
        resume_offset=n_seen)
    # carried burnout state + a FRESH per-fold round log (rnd/retired/bnds):
    # every fold has the full C+1 round budget, and a fold can never exhaust
    # it with lanes still active (each cap round retires a campaign; a
    # no-cap round ends the lane), so active lanes always leave a fold with
    # n_hat == the events seen — the next fold reads only its new rows
    init_core = (
        s_hat0.astype(jnp.float32), active0, cap0,
        n_hat0.astype(jnp.int32),
        jnp.zeros((s_local,), jnp.int32),
        jnp.full((s_local, n_campaigns + 1), -1, jnp.int32),
        jnp.zeros((s_local, n_campaigns + 2),
                  jnp.int32).at[:, 0].set(n_hat0),
    )
    return _run_loop(round_body, s_local=s_local, n_events=n_total,
                     n_campaigns=n_campaigns, init_core=init_core)


def execute_sweep_resumable(values_new, budgets, rules, plan: SweepPlan, *,
                            carry: Optional[SweepCarry] = None):
    """Fold a slab of NEW event rows into carried per-scenario burnout state.

    Returns ``(outputs, new_carry)``: ``outputs`` is the batched 6-tuple of
    :func:`execute_sweep` for the updated state (``s_hat`` / ``cap_times``
    are cumulative over every fold so far; ``retired`` / ``boundaries`` /
    ``num_rounds`` log THIS fold's rounds only), ``new_carry`` the
    :class:`SweepCarry` to pass back with the next slab. ``carry=None``
    starts from the empty log, so a single fold over the whole log is
    *bitwise* ``execute_sweep`` on it (tests/test_service.py); each
    subsequent fold does O(new events) work per round — the frontier
    ``n_hat`` sits at the previously seen event count, so rate and block
    windows touch only the new rows.

    Supported cells: ``placement="batched"`` (the service's streaming path;
    shard the exact replay path instead to scale out), any resolve
    back-end, optional event ``chunks=`` *within* a slab — including
    host-streamed chunks: a :class:`HostStream` slab (or
    ``chunks.source="host"``) folds without the new rows ever being
    resident on device at once, bit-for-bit the device fold on aligned
    sizes. Overlays and ``scenario_chunks=`` are not supported here —
    register design-only scenarios for streaming and route overlay
    families through the exact replay path.
    """
    plan = _untuned(plan)   # the tuner models full sweeps, not fold windows
    if plan.placement != "batched":
        raise ValueError(
            "execute_sweep_resumable runs placement='batched' only (the "
            f"streaming fold is a single-device program), got "
            f"{plan.placement!r}; use the exact replay path "
            "(execute_sweep) for sharded placements.")
    if plan.scenario_chunks is not None:
        raise ValueError(
            "scenario_chunks= is not supported by execute_sweep_resumable; "
            "fold scenario groups separately instead.")
    host = isinstance(values_new, HostStream) or (
        plan.chunks is not None and plan.chunks.source == "host")
    if host:
        check_host_stream(plan)
        values_new = values_new if isinstance(values_new, HostStream) \
            else HostStream.from_array(values_new)
    check_batch_shapes(values_new, budgets, rules)
    n_new, n_campaigns = values_new.shape
    if n_new < 1:
        raise ValueError("resumable fold needs at least one new event row")
    n_scenarios = budgets.shape[0]
    if carry is None:
        carry = initial_carry(n_scenarios, n_campaigns)
    if tuple(carry.s_hat.shape) != (n_scenarios, n_campaigns):
        raise ValueError(
            f"carry/batch mismatch: carry holds "
            f"{tuple(carry.s_hat.shape)} lanes but the fold got "
            f"(S, C)=({n_scenarios}, {n_campaigns})")
    if host:
        core = _sweep_hoststream(values_new, budgets, rules, plan,
                                 carry=carry)
    else:
        core = _resume_batched(values_new, budgets, rules, carry.s_hat,
                               carry.active, carry.cap_times, carry.n_hat,
                               plan, carry.n_events_seen)
    s_hat, active, cap, n_hat, _, _, _ = core
    new_carry = SweepCarry(s_hat=s_hat, active=active, cap_times=cap,
                           n_hat=n_hat,
                           n_events_seen=carry.n_events_seen + n_new)
    return _unpack(core), new_carry


def check_s2a_options(plan: SweepPlan, record_events: bool = False) -> None:
    """Validate the SORT2AGGREGATE sweep's plan (callable up front, so an
    engine can fail fast before paying for a warm start)."""
    if plan.placement == "multihost":
        raise ValueError(
            "placement='multihost' runs method='parallel' sweeps only; the "
            "sort2aggregate estimator scales out via placement='sharded' "
            "within one process.")
    if plan.chunks is not None:
        if plan.placement == "sharded":
            raise ValueError(
                "chunks= does not compose with the sharded sort2aggregate "
                "sweep (its first-crossing prefix is an all_gather'd "
                "cross-shard scan); use driver='batched' for chunked "
                "replays, or drop chunks=.")
        if plan.chunks.source == "host":
            raise ValueError(
                "host-streamed chunks apply to method='parallel' sweeps "
                "only; the chunked sort2aggregate replay scans a "
                "device-resident log (ChunkSpec(source='device')).")
        if record_events:
            raise ValueError(
                "record_events is not supported with chunks= on the "
                "sort2aggregate sweep: per-event winners/prices of the "
                "whole log are the O(N·C) residency chunking avoids. Drop "
                "record_events (spends/cap times stream fine) or drop "
                "chunks=.")
    if plan.scenario_chunks is not None:
        raise ValueError(
            "scenario_chunks= (scenario-chunked execution) currently "
            "applies to method='parallel' sweeps only; drop "
            "scenario_chunks= for the sort2aggregate sweep.")
    if plan.placement == "sharded" and record_events:
        raise ValueError(
            "record_events is not supported with driver='sharded': "
            "per-event winners/prices are an (S, N) gather off the "
            "mesh. Use driver='batched', or replay the scenarios of "
            "interest via sharded_aggregate.")


def execute_s2a_sweep(values, budgets, rules, plan: SweepPlan, *,
                      cap_times_init=None, refine_iters: int = 8,
                      record_events: bool = False,
                      crossing_block: int = 4096):
    """Dispatch the SORT2AGGREGATE scenario sweep to ``plan.placement``.

    Returns ``(SimResult, consistency_gaps, refine_iters_used)`` from
    :func:`repro.core.sweep.sweep_sort2aggregate` (batched, optionally with
    ``plan.chunks`` streaming each refine/aggregate pass through the
    chunk-carried first-crossing prefix —
    :func:`repro.core.sort2aggregate.refine_fixed_chunked`) or
    :func:`repro.core.sharded.sweep_sort2aggregate_sharded` (sharded) — the
    executor owns the placement dispatch and its validation
    (:func:`check_s2a_options`), the estimator modules own the algorithm.
    """
    plan = _untuned(plan)   # the tuner models the parallel lattice only
    check_s2a_options(plan, record_events)
    if plan.placement == "sharded":
        from repro.core.sharded import sweep_sort2aggregate_sharded
        return sweep_sort2aggregate_sharded(
            values, budgets, rules, plan.mesh,
            cap_times_init=cap_times_init, refine_iters=refine_iters)
    from repro.core.sweep import sweep_sort2aggregate
    return sweep_sort2aggregate(
        values, budgets, rules, cap_times_init=cap_times_init,
        refine_iters=refine_iters, record_events=record_events,
        chunks=plan.chunks, crossing_block=crossing_block)
