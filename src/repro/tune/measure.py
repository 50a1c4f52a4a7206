"""Measured autotuning: time the cost model's top candidates for real.

The methodology is the repo's CI-gated one (ROADMAP): interleaved-median
A/B timing via ``benchmarks.common.time_pair`` — every candidate is timed
*paired against the incumbent default plan*, (cand, default, cand,
default, …), so background load drift hits both alike. Sequential timing
windows swing 2x on shared CI boxes; interleaved ratios don't.

Budget controls:

* measurement runs on a *truncated* log (``max_events``) — cap-out round
  structure is shape-driven, so the knob ordering transfers while each
  trial stays cheap;
* a quick pass (``quick_trials``) prunes candidates slower than
  ``prune_ratio`` (default 1.5x) times the incumbent before the full
  ``trials`` budget is spent;
* the winner must *strictly beat* the default in its paired measurement,
  else the default config is recorded — a tuned plan can therefore never
  regress past measurement noise (CI additionally gates at 1.10x).

Every candidate is bitwise-identical in outputs (the chunk-equivalence
contracts), so measurement order, pruning and even a wrong winner can
only cost wall-clock, never correctness.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.core import executor as ex
from repro.core import segments as seg_lib
from repro.launch.roofline import HardwareSpec
from repro.tune import cache as cache_lib
from repro.tune import space as space_lib
from repro.tune.space import ProblemShape


@dataclasses.dataclass
class Measurement:
    """One candidate's paired timing (microsecond medians)."""

    config: dict
    us: float
    us_default: float
    predicted_total: float
    pruned: bool = False        # dropped at the quick stage

    @property
    def ratio(self) -> float:
        return self.us / max(self.us_default, 1e-9)


@dataclasses.dataclass
class TuneReport:
    """What one tuning pass decided, measured and persisted."""

    shape: ProblemShape
    key: str
    winner_config: dict
    origin: str                       # "measured" | "cost_model"
    us_tuned: Optional[float]
    us_default: Optional[float]
    measurements: List[Measurement]
    cache_path: Optional[str]
    n_candidates: int
    measured_events: int

    @property
    def speedup(self) -> Optional[float]:
        if self.us_tuned is None or self.us_default is None:
            return None
        return self.us_default / max(self.us_tuned, 1e-9)

    def plan(self, plan: ex.SweepPlan) -> ex.SweepPlan:
        """The concrete tuned plan for ``plan``'s pinned fields."""
        return space_lib.candidate_from_config(self.winner_config).apply(plan)


def _time_pair(fn_a, fn_b, repeats: int = 15, warmup: int = 2):
    """Interleaved paired medians (us) — same methodology as
    ``benchmarks.common.time_pair``, vendored so the library never imports
    the top-level ``benchmarks`` package (absent when a script runs with
    only ``src`` on ``sys.path``)."""
    try:
        from benchmarks.common import time_pair
        return time_pair(fn_a, fn_b, repeats=repeats, warmup=warmup)
    except ImportError:
        pass
    for _ in range(warmup):
        jax.block_until_ready(fn_a())
        jax.block_until_ready(fn_b())
    ta, tb = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        tb.append(time.perf_counter() - t0)
    med = lambda ts: sorted(ts)[len(ts) // 2] * 1e6
    return med(ta), med(tb)


def truncated_events(n_events: int, max_events: int) -> int:
    """The measurement log length: ``min(N, max_events)`` rounded down to
    whole canonical reduction blocks so chunk candidates stay aligned."""
    t = min(int(n_events), int(max_events))
    return max(t - t % seg_lib.REDUCE_BLOCKS, 1)


def autotune(values, budgets, rules, plan: ex.SweepPlan, *,
             overlay=None,
             cache=None,
             cache_path=None,
             hw: Optional[HardwareSpec] = None,
             top_k: int = 4,
             trials: int = 7,
             quick_trials: int = 3,
             prune_ratio: float = 1.5,
             max_events: int = 4096,
             measure: bool = True,
             refine_with_hlo: bool = True) -> TuneReport:
    """One tuning pass for ``plan`` on this problem: enumerate the legal
    knob lattice, rank by the roofline cost model, refine the top slice
    with trip-count-aware dry-run HLO costs, time the survivors paired
    against the default plan, and persist the winner.

    ``measure=False`` stops after the cost model (ranking only — what a
    dry-run-only platform records); ``cache=None`` + ``cache_path=None``
    writes the default cache file (:func:`repro.tune.cache
    .default_cache_path`). Returns a :class:`TuneReport`.
    """
    if isinstance(values, ex.HostStream):
        n_events, n_campaigns = values.shape
    else:
        n_events, n_campaigns = values.shape
    budgets = jnp.asarray(budgets, jnp.float32)
    n_scenarios = budgets.shape[0] if budgets.ndim == 2 else 1
    shape = space_lib.shape_for(plan, n_events=n_events,
                                n_campaigns=n_campaigns,
                                n_scenarios=n_scenarios)
    if hw is None:
        hw = HardwareSpec.for_device_kind(shape.device_kind)
    ranked = space_lib.rank_candidates(plan, shape, hw)
    candidates = [c for c, _ in ranked]
    predicted = {c: p.total for c, p in ranked}
    default = space_lib.default_candidate(plan)
    top = candidates[:max(int(top_k), 1)]
    if refine_with_hlo and len(top) > 1 and not isinstance(
            values, ex.HostStream):
        # trip-count-aware refinement: re-rank the short list by the
        # compiled program's actual bytes/FLOPs (launch/hlo_cost walker)
        refined = {}
        for c in top:
            terms = space_lib.dryrun_terms(c, plan, shape, hw)
            if terms is None:
                refined = None
                break
            refined[c] = max(terms.t_compute, terms.t_memory) \
                + terms.t_collective
        if refined:
            top = sorted(top, key=lambda c: (refined[c], c.sort_key()))

    measurements: List[Measurement] = []
    winner, origin = top[0], "cost_model"
    us_tuned = us_default = None
    t = truncated_events(n_events, max_events)
    if measure and len(top) > 0:
        time_pair = _time_pair
        if isinstance(values, ex.HostStream):
            v_meas = values if t == n_events else ex.HostStream(
                [values.chunk(0, t)])
        else:
            v_meas = values[:t]
        mshape = dataclasses.replace(shape, n_events=t)
        default_plan = default.apply(plan)

        def run(p):
            return lambda: ex.execute_sweep(v_meas, budgets, rules, p,
                                            overlay=overlay)

        base_fn = run(default_plan)
        best_us = None
        for cand in top:
            if cand == default:
                continue          # the default is the B side of every pair
            if not space_lib.is_legal(cand, plan, mshape):
                continue          # aligned on N but not on the truncation
            cand_fn = run(cand.apply(plan))
            us_c, us_d = time_pair(cand_fn, base_fn,
                                   repeats=max(int(quick_trials), 1))
            pruned = (best_us is not None
                      and us_c > prune_ratio * best_us)
            if not pruned and trials > quick_trials:
                us_c, us_d = time_pair(cand_fn, base_fn,
                                       repeats=max(int(trials), 1))
            measurements.append(Measurement(
                config=cand.config(), us=us_c, us_default=us_d,
                predicted_total=predicted.get(cand, float("nan")),
                pruned=pruned))
            if not pruned and (best_us is None or us_c < best_us):
                best_us = us_c
        # the winner must strictly beat the default's paired time; ties
        # and regressions keep the default (tuning can't make it worse)
        best = None
        for m in measurements:
            if m.pruned:
                continue
            if m.ratio < 1.0 and (best is None or m.ratio < best.ratio):
                best = m
        if best is not None:
            winner = space_lib.candidate_from_config(best.config)
            us_tuned, us_default = best.us, best.us_default
        else:
            # no candidate strictly beat the default: the default IS the
            # tuned decision (its paired time comes from the closest pair)
            winner = default
            if measurements:
                m = min(measurements, key=lambda m: m.ratio)
                us_tuned = us_default = m.us_default
        origin = "measured"

    key = cache_lib.cache_key(shape)
    path = None
    if cache is None:
        cache = cache_lib.TuningCache.load(cache_path)
    cache.put(key, winner.config(), origin=origin,
              us_tuned=us_tuned, us_default=us_default,
              hardware=hw.name, measured_events=t if measure else 0,
              shape=dataclasses.asdict(shape))
    path = str(cache.save())
    return TuneReport(
        shape=shape, key=key, winner_config=winner.config(), origin=origin,
        us_tuned=us_tuned, us_default=us_default,
        measurements=measurements, cache_path=path,
        n_candidates=len(candidates), measured_events=t if measure else 0)
