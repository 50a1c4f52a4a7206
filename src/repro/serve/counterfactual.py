"""Always-on counterfactual service: the sweep executor behind a growing log.

Everything below this layer is one-shot — hand :func:`execute_sweep` a log
and a grid, get answers. The paper's motivating setting (an ad platform with
campaign budgets) asks what-if questions *continuously while the log grows*,
so :class:`CounterfactualService` keeps the state a one-shot call throws
away:

* **incremental append** — :meth:`append` admits aligned event slabs
  (whole multiples of ``events_per_chunk``; ragged slabs raise the
  executor's verbatim "ragged chunk" pad-or-error message,
  :func:`~repro.core.executor.check_append_alignment`), bumps the monotone
  ``log_version``, and folds each slab into every *registered* scenario's
  carried burnout state via :func:`~repro.core.executor.
  execute_sweep_resumable` — O(new events) work per append instead of a
  full replay;
* **admission batching** — :meth:`ask` enqueues a request and returns a
  :class:`Ticket`; :meth:`flush` drains the queue in one
  :func:`execute_sweep` call per pricing kind (the ``serve/engine.py``
  drain-loop shape: admit → plan fixed batches → run), packing distinct
  designs into S-lanes, padding oversized batches to a whole number of
  :class:`~repro.core.executor.ScenarioChunkSpec` chunks (duplicate lanes
  cannot change any other lane's bits), and routing results back in
  deterministic FIFO order;
* **delta-aware caching** — answers are keyed on ``(log_version, canonical
  scenario fingerprint)`` (:func:`~repro.scenarios.family.
  design_fingerprint` — exact design bytes, no rounding), so overlapping
  grids from :meth:`CounterfactualEngine.search` or repeated callers dedupe
  exactly; appends invalidate the cache (version bump + drop), and
  hit/miss counters are surfaced via :attr:`stats`;
* **host-resident store + persistence** — ``store="host"`` keeps the log
  out of device memory entirely (exact replays stream the slabs through
  the double-buffered :class:`~repro.core.executor.HostStream` pipeline;
  appends fold host slabs via :func:`~repro.core.executor.
  execute_sweep_resumable` without ever concatenating the log on device),
  and :meth:`save` / :meth:`load` checkpoint the whole service — slabs,
  base design, streaming carries, ``log_version`` — via
  :mod:`repro.checkpoint.ckpt`, so a restored service answers bitwise an
  uninterrupted one.

Two answer semantics, honestly separated (see docs/ARCHITECTURE.md
"Service layer"):

* the **exact path** (:meth:`ask` / :meth:`sweep`) answers against the full
  stored log: a cache miss replays the concatenated log in one executor
  program, so every answer is *bitwise* a one-shot ``engine.sweep`` of the
  current log — for every placement / resolve / scenario_chunks cell and
  every aligned append partition (the tests/test_service.py harness);
* the **streaming path** (:meth:`register` / :meth:`streaming`) maintains
  the causal frontier estimate: Algorithm-2 rounds whose rate windows only
  ever saw the events available at fold time (no lookahead). It is bitwise
  the exact path when the whole log arrived in one append, and is the
  O(new events) signal to watch between exact asks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint.ckpt import (latest_step, restore_checkpoint,
                                   save_checkpoint)
from repro.core import segments as seg_lib
from repro.core import sweep as sweep_lib
from repro.core.counterfactual import (CounterfactualEngine, ScenarioGrid,
                                       SweepResult)
from repro.core.executor import (ChunkSpec, HostStream, SweepCarry,
                                 SweepPlan, as_chunk_spec,
                                 as_scenario_chunk_spec,
                                 check_append_alignment, execute_sweep,
                                 execute_sweep_resumable, initial_carry)
from repro.core.types import AuctionRule, ScenarioOverlay, SimResult
from repro.scenarios.family import (CompiledFamily, design_fingerprint,
                                    family_fingerprints, grid_fingerprints)


@dataclasses.dataclass(frozen=True)
class ServiceAnswer:
    """One scenario's exact answer, pinned to the log version it replayed."""

    final_spend: np.ndarray      # (C,)
    cap_times: np.ndarray        # (C,)
    log_version: int


@dataclasses.dataclass
class Ticket:
    """FIFO handle for one admitted :meth:`CounterfactualService.ask`.

    ``result()`` drains the service queue if this ticket is still pending;
    tickets admitted together are answered by one batched sweep and routed
    back in admission order.
    """

    seq: int
    fingerprint: str
    label: str
    _service: "CounterfactualService"
    _answer: Optional[ServiceAnswer] = None

    @property
    def done(self) -> bool:
        return self._answer is not None

    def result(self) -> ServiceAnswer:
        if self._answer is None:
            self._service.flush()
        return self._answer


@dataclasses.dataclass
class _StreamGroup:
    """Registered streaming scenarios of one pricing kind, folded together
    (stacked lanes share every fold's program; lanes never read each
    other's state, so group membership cannot change any lane's bits)."""

    labels: List[str]
    rules: AuctionRule           # stacked (S, C)
    budgets: jax.Array           # (S, C)
    carry: SweepCarry


class CounterfactualService:
    """A long-lived counterfactual answerer over a growing event log.

    ``budgets`` / ``base_rule`` name the base design defaults for
    :meth:`ask` and :meth:`register`; ``events_per_chunk`` is the append
    granularity (every slab must hold whole chunks); ``max_batch`` bounds
    the scenario lanes one drain executes at once (bigger drains run
    scenario-chunked); the remaining knobs build the executor
    :class:`~repro.core.executor.SweepPlan` every exact replay runs on —
    any cell produces bit-identical answers, so the plan is a pure
    capacity/placement choice.

    ``store="host"`` keeps the log out of device memory entirely: slabs
    stay host-resident numpy, the exact path replays them through the
    double-buffered :class:`~repro.core.executor.HostStream` pipeline
    (device residency O(events_per_chunk · C), answers still bitwise the
    device-resident replay), and appends fold the new slab into streaming
    carries without ever materialising the concatenated log on device.
    Host mode serves design-only scenarios on ``placement="batched"``
    with no mesh / scenario chunking (overlay families raise the
    executor's host-stream error); ``events_per_chunk`` must hold whole
    canonical reduction blocks (a multiple of
    :data:`~repro.core.segments.REDUCE_BLOCKS`), and replay chunk sizes
    are re-aligned to the canonical grid per log size (the grid coarsens
    as N grows — see :func:`~repro.core.segments.reduce_block_size`).

    :meth:`save` / :meth:`load` persist the whole service (slabs, base
    design, streaming carries, log version) through
    :mod:`repro.checkpoint.ckpt`, so a restored service answers — and
    keeps folding appends — bitwise an uninterrupted one.
    """

    def __init__(self, budgets, base_rule: Optional[AuctionRule] = None, *,
                 events=None, events_per_chunk: int = 256,
                 max_batch: int = 32, placement: str = "batched",
                 resolve: str = "auto", mesh=None, chunks=None,
                 scenario_chunks=None, interpret: Optional[bool] = None,
                 store: str = "device", tuned: bool = False):
        self.base_budgets = jnp.asarray(budgets, jnp.float32)
        if self.base_budgets.ndim != 1:
            raise ValueError(
                f"service budgets are the (C,) base design, got shape "
                f"{tuple(self.base_budgets.shape)}")
        self.n_campaigns = self.base_budgets.shape[0]
        self.base_rule = base_rule or AuctionRule.first_price(
            self.n_campaigns)
        self._chunk_spec = as_chunk_spec(int(events_per_chunk))
        self.max_batch = int(max_batch)
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if store not in ("device", "host"):
            raise ValueError(
                f"unknown store: {store!r} (use 'device' or 'host')")
        self.store = store
        if store == "host":
            if placement != "batched" or mesh is not None:
                raise ValueError(
                    "store='host' replays through the host-stream pipeline "
                    "(placement='batched', no mesh); shard within a replay "
                    "via store='device' + placement='sharded' instead")
            if scenario_chunks is not None:
                raise ValueError(
                    "store='host' does not compose with scenario_chunks= "
                    "(the host-stream driver runs all lanes per pass)")
            if events_per_chunk % seg_lib.REDUCE_BLOCKS != 0:
                raise ValueError(
                    f"store='host' needs events_per_chunk to hold whole "
                    f"canonical reduction blocks: {events_per_chunk} is not "
                    f"a multiple of REDUCE_BLOCKS={seg_lib.REDUCE_BLOCKS}")
            # replay chunk-size ambition; actual chunk sizes are re-aligned
            # to the canonical grid per log size (_host_chunks)
            self._host_epc_target = (
                as_chunk_spec(chunks).events_per_chunk
                if chunks is not None else int(events_per_chunk))
            chunks = None
        # the exact-replay plan (validated here: unknown placement/resolve
        # and missing meshes fail at construction, not first ask).
        # tuned=True hands the plan's unpinned performance knobs to
        # repro.tune at replay time (cache -> cost model); explicit
        # chunks/scenario_chunks stay pinned, so append alignment and lane
        # padding are unaffected — and every plan cell answers bitwise.
        self.plan = SweepPlan(placement=placement, resolve=resolve,
                              mesh=mesh, chunks=as_chunk_spec(chunks),
                              scenario_chunks=as_scenario_chunk_spec(
                                  scenario_chunks),
                              interpret=interpret,
                              block_t="auto" if tuned else 256,
                              tuned=tuned)
        # the streaming-fold plan: batched single-device program, same
        # resolve preference (any back-end folds to identical bits)
        self._stream_plan = SweepPlan(placement="batched", resolve=resolve,
                                      interpret=interpret)
        self.log_version = 0
        self._slabs: List[jax.Array] = []
        self._n_events = 0
        self._values = None
        self._values_version = -1
        self._cache: Dict[Tuple[int, str],
                          Tuple[np.ndarray, np.ndarray]] = {}
        self.hits = 0
        self.misses = 0
        self.batches = 0
        self.appends = 0
        self._queue: List[Tuple[Ticket, AuctionRule, jax.Array]] = []
        self._seq = 0
        self._streams: Dict[str, _StreamGroup] = {}
        if events is not None:
            self.append(events)

    # -- the stored log ----------------------------------------------------

    @property
    def n_events(self) -> int:
        return self._n_events

    @property
    def values(self):
        """The full stored log, the exact path's replay input: the
        appended slabs concatenated (cached per ``log_version``) — or,
        under ``store="host"``, a zero-copy
        :class:`~repro.core.executor.HostStream` view of the host-resident
        slabs (never concatenated, never device-resident)."""
        if not self._slabs:
            raise ValueError(
                "empty log: append events before asking the service")
        if self.store == "host":
            return HostStream(list(self._slabs))
        if self._values_version != self.log_version:
            if len(self._slabs) == 1:
                self._values = self._slabs[0]
            else:
                with obs.span("serve.concat", rows=self._n_events):
                    self._values = jnp.concatenate(self._slabs, axis=0)
            self._values_version = self.log_version
        return self._values

    def _host_chunks(self, window: int, total: int) -> Optional[ChunkSpec]:
        """An aligned host :class:`ChunkSpec` for streaming ``window``
        events of a ``total``-event log (full replay: ``window == total``;
        resumable fold: the new rows of a log that will have ``total``).

        The canonical reduction grid coarsens with the log
        (``reduce_block_size(total)``), so a fixed chunk size cannot stay
        aligned forever; this picks the largest whole-block chunk at most
        ``_host_epc_target`` that divides the window. Full replays always
        have one (``events_per_chunk`` is a multiple of
        ``REDUCE_BLOCKS``); a fold window may not — ``None`` means "no
        aligned host chunking exists", and the caller folds the slab
        through the device program instead (bitwise the same answer)."""
        block = seg_lib.reduce_block_size(total)
        if window % block:
            return None
        m = window // block
        limit = max(self._host_epc_target // block, 1)
        k = max(d for d in range(1, min(m, limit) + 1) if m % d == 0)
        return ChunkSpec(block * k, source="host")

    def append(self, events) -> int:
        """Admit a new aligned event slab; returns the new ``log_version``.

        Pending asks are flushed FIRST — tickets are answered against the
        log they were admitted under, which keeps admission batching
        deterministic across interleavings. The slab must be whole chunks
        of ``events_per_chunk`` (the executor's verbatim "ragged chunk"
        pad-or-error contract otherwise) with the service's campaign
        count. Every registered streaming scenario's carry is folded
        forward over the new rows only; the exact-answer cache is
        invalidated by the version bump (stale entries dropped — the
        versioned key alone already makes them unservable).
        """
        events = jnp.asarray(events, jnp.float32)
        if events.ndim != 2 or events.shape[1] != self.n_campaigns:
            raise ValueError(
                f"append expects (n, C={self.n_campaigns}) event rows, got "
                f"shape {tuple(events.shape)}")
        if events.shape[0] == 0:
            raise ValueError("append needs at least one event row")
        check_append_alignment(self._chunk_spec, events.shape[0])
        with obs.span("serve.append", rows=events.shape[0]) as span:
            self.flush()
            if self.store == "host":
                events = np.asarray(jax.device_get(events), np.float32)
            self._slabs.append(events)
            self._n_events += events.shape[0]
            self.log_version += 1
            self.appends += 1
            self._cache.clear()
            span.set(version=self.log_version)
            for group in self._streams.values():
                group.carry = self._fold(events, group.budgets, group.rules,
                                         group.carry)
        return self.log_version

    def _fold(self, slab, budgets, rules, carry) -> SweepCarry:
        """Fold one new slab into a streaming carry — O(slab) work.

        Under ``store="host"`` the slab is host-resident and streams
        through the host-chunk pipeline when an aligned chunking exists
        for this fold window (falling back to the device program on the
        slab — same bits, slab-bounded device residency — when the
        canonical grid misaligns)."""
        n_new = slab.shape[0]
        spec = (self._host_chunks(n_new, int(carry.n_events_seen) + n_new)
                if self.store == "host" else None)
        with obs.span("serve.fold", lanes=budgets.shape[0]):
            if spec is None:
                values, plan = jnp.asarray(slab), self._stream_plan
            else:
                values = HostStream([np.asarray(slab, np.float32)])
                plan = dataclasses.replace(self._stream_plan, chunks=spec)
            _, carry = execute_sweep_resumable(values, budgets, rules, plan,
                                               carry=carry)
        return carry

    # -- admission batching (the exact path) -------------------------------

    def _normalise(self, rule: Optional[AuctionRule], budgets
                   ) -> Tuple[AuctionRule, jax.Array]:
        rule = rule or self.base_rule
        budgets = (self.base_budgets if budgets is None
                   else jnp.asarray(budgets, jnp.float32))
        if tuple(budgets.shape) != (self.n_campaigns,) or \
                tuple(rule.multipliers.shape) != (self.n_campaigns,):
            raise ValueError(
                f"scenario shape mismatch: service serves C="
                f"{self.n_campaigns} campaigns, got multipliers "
                f"{tuple(rule.multipliers.shape)} / budgets "
                f"{tuple(budgets.shape)}")
        return rule, budgets

    def ask(self, rule: Optional[AuctionRule] = None, budgets=None, *,
            label: Optional[str] = None) -> Ticket:
        """Admit one what-if scenario (defaults: the base design). Returns
        a :class:`Ticket`; concurrent asks queue until :meth:`flush` (or
        the first ``ticket.result()``) packs them into batched sweeps."""
        with obs.span("serve.admit", seq=self._seq):
            rule, budgets = self._normalise(rule, budgets)
            fp = design_fingerprint(kind=rule.kind,
                                    multipliers=rule.multipliers,
                                    reserve=rule.reserve, budgets=budgets)
            ticket = Ticket(seq=self._seq, fingerprint=fp,
                            label=label or f"ask{self._seq}", _service=self)
            self._seq += 1
            self._queue.append((ticket, rule, budgets))
        return ticket

    def flush(self) -> int:
        """Drain the admission queue: per pricing kind, pack the distinct
        uncached designs into one S-batch and run ONE :func:`execute_sweep`
        call, then route every ticket its row in FIFO order. Returns the
        number of tickets answered."""
        if not self._queue:
            return 0
        pending, self._queue = self._queue, []
        with obs.span("serve.flush", first_seq=pending[0][0].seq,
                      tickets=len(pending)) as span:
            version = self.log_version
            by_kind: Dict[str, List[Tuple[str, AuctionRule,
                                          jax.Array]]] = {}
            seen = set()
            for ticket, rule, budgets in pending:
                if (version, ticket.fingerprint) in self._cache or \
                        ticket.fingerprint in seen:
                    continue
                seen.add(ticket.fingerprint)
                by_kind.setdefault(rule.kind, []).append(
                    (ticket.fingerprint, rule, budgets))
            misses = len(seen)
            self.hits += len(pending) - misses
            self.misses += misses
            span.set(hits=len(pending) - misses, misses=misses)
            answered = []
            for lanes in by_kind.values():
                with obs.span("serve.stack", lanes=len(lanes)):
                    rules_s = sweep_lib.stack_rules([r for _, r, _ in lanes])
                    budgets_s = jnp.stack([b for _, _, b in lanes])
                answered.append((lanes, self._execute_batch(rules_s,
                                                            budgets_s)))
            with obs.span("serve.route"):
                for lanes, (spend, caps) in answered:
                    for i, (fp, _, _) in enumerate(lanes):
                        self._cache[(version, fp)] = (spend[i], caps[i])
                for ticket, _, _ in pending:
                    spend_row, caps_row = self._cache[(version,
                                                       ticket.fingerprint)]
                    ticket._answer = ServiceAnswer(final_spend=spend_row,
                                                   cap_times=caps_row,
                                                   log_version=version)
        return len(pending)

    def _batch_plan(self, n_lanes: int) -> Tuple[SweepPlan, int]:
        """The plan + padded lane count one drain executes at: an explicit
        ``scenario_chunks`` wins; otherwise batches past ``max_batch`` run
        scenario-chunked at ``max_batch`` lanes a pass. Lanes are padded to
        a whole number of chunks (× scenario-axis devices) with repeats of
        lane 0 — the documented pad remedy; duplicate lanes run the
        identical per-lane program and cannot change any other lane's
        bits."""
        plan = self.plan
        if self.store == "host":
            # host-stream replays run all lanes per pass (no scenario
            # chunking) with chunk sizes re-aligned to the canonical grid
            # at the current log size
            return dataclasses.replace(
                plan, chunks=self._host_chunks(self._n_events,
                                               self._n_events)), n_lanes
        spc = (plan.scenario_chunks.scenarios_per_chunk
               if plan.scenario_chunks is not None else None)
        if spc is None and n_lanes > self.max_batch:
            spc = self.max_batch
            plan = dataclasses.replace(
                plan, scenario_chunks=as_scenario_chunk_spec(spc))
        unit = spc or 1
        if plan.mesh is not None:
            d_sc = plan.mesh.scenario_device_count
            unit = unit * d_sc // math.gcd(unit, d_sc)
        return plan, -(-n_lanes // unit) * unit

    def _execute_batch(self, rules_s: AuctionRule, budgets_s: jax.Array,
                       overlay: Optional[ScenarioOverlay] = None
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """One exact replay of the full stored log for a lane batch;
        returns host (S, C) final_spend / cap_times (padding stripped)."""
        n_lanes = budgets_s.shape[0]
        plan, n_pad = self._batch_plan(n_lanes)
        with obs.span("serve.replay", lanes=n_lanes, padded_to=n_pad,
                      events=self._n_events):
            if n_pad > n_lanes:
                with obs.span("serve.pad"):
                    pad = lambda x: jnp.concatenate(
                        [x, jnp.repeat(x[:1], n_pad - n_lanes, axis=0)],
                        axis=0)
                    rules_s = AuctionRule(
                        multipliers=pad(rules_s.multipliers),
                        reserve=pad(rules_s.reserve), kind=rules_s.kind)
                    budgets_s = pad(budgets_s)
                    if overlay is not None:
                        grow = lambda x: None if x is None else pad(x)
                        overlay = dataclasses.replace(
                            overlay, live_start=grow(overlay.live_start),
                            live_stop=grow(overlay.live_stop),
                            bid_sigma=grow(overlay.bid_sigma),
                            part_prob=grow(overlay.part_prob))
            s_hat, cap_times, *_ = execute_sweep(
                self.values, budgets_s, rules_s, plan, overlay=overlay)
            self.batches += 1
            with obs.span("serve.fetch"):
                spend = np.asarray(jax.device_get(s_hat))[:n_lanes]
                caps = np.asarray(jax.device_get(cap_times))[:n_lanes]
        return spend, caps

    # -- grid/family sweeps (what a service-bound engine delegates to) -----

    def sweep(self, grid, *, base_index: int = 0) -> SweepResult:
        """Evaluate a :class:`~repro.core.counterfactual.ScenarioGrid` (or
        a :class:`~repro.scenarios.CompiledFamily` compiled on this
        service's log) against the current log, through the delta-aware
        cache: scenarios whose ``(log_version, fingerprint)`` is cached are
        served from it, the rest run as ONE batched replay, bitwise the
        one-shot ``engine.sweep`` of the full log."""
        overlay = None
        if isinstance(grid, CompiledFamily):
            family = grid
            if family.num_entrants:
                raise ValueError(
                    "entrant families extend the valuation matrix, but the "
                    "service's stored log is authoritative; recompile the "
                    "family without AddEntrant, or sweep it one-shot via "
                    "CounterfactualEngine.")
            if tuple(family.values.shape) != (self.n_events,
                                              self.n_campaigns):
                raise ValueError(
                    f"stale family: compiled over values of shape "
                    f"{tuple(family.values.shape)} but the service log is "
                    f"now ({self.n_events}, {self.n_campaigns}); recompile "
                    "from service.values after append().")
            fps = family_fingerprints(family)
            grid, overlay = family.grid, family.overlay
            base_index = family.base_index
        else:
            fps = grid_fingerprints(grid)
        self.values                      # raises on an empty log
        version = self.log_version
        missing: List[int] = []
        missing_fps: List[str] = []
        seen = set()
        for s, fp in enumerate(fps):
            if (version, fp) in self._cache or fp in seen:
                self.hits += 1
                continue
            self.misses += 1
            seen.add(fp)
            missing.append(s)
            missing_fps.append(fp)
        if missing:
            idx = jnp.asarray(missing, jnp.int32)
            sub_rules = AuctionRule(
                multipliers=grid.rules.multipliers[idx],
                reserve=jnp.asarray(grid.rules.reserve,
                                    jnp.float32)[idx],
                kind=grid.rules.kind)
            sub_overlay = None
            if overlay is not None:
                take = lambda x: None if x is None else x[idx]
                sub_overlay = dataclasses.replace(
                    overlay, live_start=take(overlay.live_start),
                    live_stop=take(overlay.live_stop),
                    bid_sigma=take(overlay.bid_sigma),
                    part_prob=take(overlay.part_prob))
            spend, caps = self._execute_batch(sub_rules, grid.budgets[idx],
                                              overlay=sub_overlay)
            for i, fp in enumerate(missing_fps):
                self._cache[(version, fp)] = (spend[i], caps[i])
        rows = [self._cache[(version, fp)] for fp in fps]
        results = SimResult(
            final_spend=jnp.asarray(np.stack([r[0] for r in rows])),
            cap_times=jnp.asarray(np.stack([r[1] for r in rows])),
            winners=None, prices=None, segments=None)
        return SweepResult(grid=grid, results=results,
                           n_events=self.n_events, base_index=base_index)

    def engine(self) -> CounterfactualEngine:
        """A :class:`CounterfactualEngine` snapshot of the current log,
        bound to this service: its ``sweep``/``search`` route through the
        admission batch + cache (bitwise the unbound engine's answers).
        Re-create after :meth:`append` — a stale snapshot raises."""
        return CounterfactualEngine(self.values, self.base_budgets,
                                    self.base_rule, service=self)

    def tune(self, *, scenarios: Optional[int] = None, cache=None,
             cache_path=None, max_events: int = 4096, trials: int = 7,
             quick_trials: int = 3, top_k: int = 4, measure: bool = True):
        """One measured tuning pass on the stored log, then pin the winner
        as this service's replay plan: candidates are timed paired against
        the default plan (``benchmarks.common.time_pair``) at a
        representative lane count (``scenarios``, default ``max_batch``),
        the winner is persisted in the tuning cache, and ``self.plan``
        becomes the concrete tuned plan — explicit ctor
        ``chunks``/``scenario_chunks`` stay pinned, so append alignment is
        untouched, and every candidate answers bit-for-bit (the executor's
        chunk-equivalence contracts), so the cache keeps its entries.
        Returns the :class:`repro.tune.TuneReport`."""
        from repro import tune as tune_lib
        if self.store == "host":
            raise ValueError(
                "store='host' replans its chunking per log size "
                "(_host_chunks), so there is no stable plan to tune; "
                "construct the service with tuned=True instead — host "
                "replays then resolve their free knobs through the tuning "
                "cache at each ask.")
        self.flush()
        n_lanes = int(scenarios) if scenarios is not None else self.max_batch
        grid = ScenarioGrid.product(
            self.base_rule, self.base_budgets,
            bid_scales=tuple(1.0 + 0.25 * i for i in range(n_lanes)))
        plan = dataclasses.replace(self.plan, block_t="auto", tuned=True)
        report = tune_lib.autotune(
            self.values, grid.budgets, grid.rules, plan,
            cache=cache, cache_path=cache_path, max_events=max_events,
            trials=trials, quick_trials=quick_trials, top_k=top_k,
            measure=measure)
        self.plan = report.plan(plan)
        return report

    # -- streaming carries (the causal path) -------------------------------

    def register(self, label: str, rule: Optional[AuctionRule] = None,
                 budgets=None) -> None:
        """Register a design-only scenario for streaming: its carried
        burnout state is caught up over the stored log once, then every
        :meth:`append` folds only the new rows into it."""
        if any(label in g.labels for g in self._streams.values()):
            raise ValueError(f"streaming scenario {label!r} already "
                             "registered")
        rule, budgets = self._normalise(rule, budgets)
        lane_rules = sweep_lib.stack_rules([rule])
        lane_budgets = budgets[None, :]
        carry = initial_carry(1, self.n_campaigns)
        for slab in self._slabs:
            carry = self._fold(slab, lane_budgets, lane_rules, carry)
        group = self._streams.get(rule.kind)
        if group is None:
            self._streams[rule.kind] = _StreamGroup(
                labels=[label], rules=lane_rules, budgets=lane_budgets,
                carry=carry)
            return
        cat = lambda a, b: jnp.concatenate([a, b], axis=0)
        group.labels.append(label)
        group.rules = AuctionRule(
            multipliers=cat(group.rules.multipliers, lane_rules.multipliers),
            reserve=cat(jnp.atleast_1d(group.rules.reserve),
                        jnp.atleast_1d(lane_rules.reserve)),
            kind=rule.kind)
        group.budgets = cat(group.budgets, lane_budgets)
        group.carry = SweepCarry(
            s_hat=cat(group.carry.s_hat, carry.s_hat),
            active=cat(group.carry.active, carry.active),
            cap_times=cat(group.carry.cap_times, carry.cap_times),
            n_hat=cat(group.carry.n_hat, carry.n_hat),
            n_events_seen=self._n_events)

    def streaming(self, label: str) -> ServiceAnswer:
        """The registered scenario's current causal frontier estimate —
        O(1), no replay. Bitwise :meth:`ask` when the whole log arrived in
        one append (the carried state then IS one full Algorithm-2 run)."""
        for group in self._streams.values():
            if label in group.labels:
                i = group.labels.index(label)
                return ServiceAnswer(
                    final_spend=np.asarray(
                        jax.device_get(group.carry.s_hat[i])),
                    cap_times=np.asarray(
                        jax.device_get(group.carry.cap_times[i])),
                    log_version=self.log_version)
        raise ValueError(
            f"unknown streaming scenario: {label!r} (registered: "
            f"{[l for g in self._streams.values() for l in g.labels]})")

    # -- persistence -------------------------------------------------------

    def save(self, path) -> "object":
        """Persist the whole service under ``path`` (a checkpoint directory
        per ``log_version``, :func:`repro.checkpoint.ckpt.save_checkpoint`):
        the stored slabs, the base design, and every streaming group's
        stacked design + carried burnout frontier. Pending asks are
        flushed first (tickets cannot survive a restart). Returns the
        checkpoint directory; restore with :meth:`load`, after which
        answers and appended folds are bitwise an uninterrupted
        service's."""
        self.flush()
        tree = {
            "slabs": [np.asarray(jax.device_get(s), np.float32)
                      for s in self._slabs],
            "base_budgets": np.asarray(self.base_budgets),
            "base_multipliers": np.asarray(self.base_rule.multipliers),
            "base_reserve": np.asarray(self.base_rule.reserve),
            "streams": {
                kind: {
                    "multipliers": np.asarray(g.rules.multipliers),
                    "reserve": np.asarray(jnp.atleast_1d(g.rules.reserve)),
                    "budgets": np.asarray(g.budgets),
                    "s_hat": np.asarray(g.carry.s_hat),
                    "active": np.asarray(g.carry.active),
                    "cap_times": np.asarray(g.carry.cap_times),
                    "n_hat": np.asarray(g.carry.n_hat),
                } for kind, g in self._streams.items()},
        }
        extra = {
            "log_version": self.log_version,
            "n_events": self._n_events,
            "n_slabs": len(self._slabs),
            "n_campaigns": self.n_campaigns,
            "events_per_chunk": self._chunk_spec.events_per_chunk,
            "max_batch": self.max_batch,
            "store": self.store,
            "base_kind": self.base_rule.kind,
            "seq": self._seq,
            "stream_labels": {k: list(g.labels)
                              for k, g in self._streams.items()},
            "stream_n_seen": {k: int(g.carry.n_events_seen)
                              for k, g in self._streams.items()},
            "counters": {"hits": self.hits, "misses": self.misses,
                         "batches": self.batches, "appends": self.appends},
        }
        return save_checkpoint(path, self.log_version, tree, extra)

    @classmethod
    def load(cls, path, *, step: Optional[int] = None,
             placement: str = "batched", resolve: str = "auto", mesh=None,
             chunks=None, scenario_chunks=None,
             interpret: Optional[bool] = None,
             tuned: bool = False) -> "CounterfactualService":
        """Restore a service saved by :meth:`save` (the latest checkpoint
        under ``path``, or an explicit ``step`` = log version). Log slabs,
        base design, log version and every streaming carry come back
        exactly; the execution-plan knobs are per-process capacity choices
        (meshes are not serialisable), so pass them here — any cell
        answers bitwise, so the restored service's answers and subsequent
        appended folds match an uninterrupted one bit-for-bit. The
        delta-aware cache starts empty (first asks re-replay)."""
        if step is None:
            step = latest_step(path)
            if step is None:
                raise FileNotFoundError(
                    f"no service checkpoints under {path}")
        # two-phase restore: the manifest names the tree structure (slab
        # count, stream kinds), then the real tree restores into it
        _, manifest = restore_checkpoint(path, {}, step=step)
        extra = manifest["extra"]
        kinds = list(extra["stream_labels"])
        like = {
            "slabs": [0] * int(extra["n_slabs"]),
            "base_budgets": 0, "base_multipliers": 0, "base_reserve": 0,
            "streams": {kind: {"multipliers": 0, "reserve": 0,
                               "budgets": 0, "s_hat": 0, "active": 0,
                               "cap_times": 0, "n_hat": 0}
                        for kind in kinds},
        }
        tree, _ = restore_checkpoint(path, like, step=step)
        base_rule = AuctionRule(multipliers=tree["base_multipliers"],
                                reserve=tree["base_reserve"],
                                kind=extra["base_kind"])
        svc = cls(tree["base_budgets"], base_rule,
                  events_per_chunk=int(extra["events_per_chunk"]),
                  max_batch=int(extra["max_batch"]), placement=placement,
                  resolve=resolve, mesh=mesh, chunks=chunks,
                  scenario_chunks=scenario_chunks, interpret=interpret,
                  store=extra["store"], tuned=tuned)
        slabs = tree["slabs"]
        if svc.store == "host":
            slabs = [np.asarray(jax.device_get(s), np.float32)
                     for s in slabs]
        svc._slabs = list(slabs)
        svc._n_events = int(extra["n_events"])
        svc.log_version = int(extra["log_version"])
        svc._seq = int(extra["seq"])
        counters = extra["counters"]
        svc.hits, svc.misses = int(counters["hits"]), int(counters["misses"])
        svc.batches = int(counters["batches"])
        svc.appends = int(counters["appends"])
        for kind in kinds:
            g = tree["streams"][kind]
            svc._streams[kind] = _StreamGroup(
                labels=list(extra["stream_labels"][kind]),
                rules=AuctionRule(multipliers=g["multipliers"],
                                  reserve=g["reserve"], kind=kind),
                budgets=g["budgets"],
                carry=SweepCarry(
                    s_hat=g["s_hat"], active=g["active"],
                    cap_times=g["cap_times"], n_hat=g["n_hat"],
                    n_events_seen=int(extra["stream_n_seen"][kind])))
        return svc

    # -- observability -----------------------------------------------------

    @property
    def stats(self) -> dict:
        """Hit/miss counters and log bookkeeping, for dashboards/tests."""
        return {"log_version": self.log_version, "n_events": self.n_events,
                "hits": self.hits, "misses": self.misses,
                "batches": self.batches, "appends": self.appends,
                "pending": len(self._queue),
                "cached": len(self._cache),
                "registered": sum(len(g.labels)
                                  for g in self._streams.values())}
