"""Scenario-space search: gradient-free optimizers over the sweep engine.

The sweep engine evaluates cartesian :class:`~repro.core.counterfactual.
ScenarioGrid`\\ s; the workload the paper motivates is *search* — "what
reserve maximizes revenue subject to cap-out < 10%?". This package closes
that loop: a :class:`SearchSpace` names box bounds over the grid axes
(bid scale × reserve × budget scale), an optimizer proposes scenario
batches, the batched Algorithm-2 sweep evaluates each batch as ONE device
program, and an :class:`EvaluationLedger` accounts for every scenario
evaluation against an explicit budget (no silent over-spend — exceeding it
raises :class:`BudgetExhausted` *before* the sweep runs).

Two optimizers, both derivative-free and deterministic (fixed grids /
coordinate steps — reproducible trajectories, no RNG):

* :func:`successive_halving` — rungs of shrinking boxes: evaluate a
  balanced grid over the current box as one S-batch, keep the top
  ``1/eta`` fraction, re-grid a ``shrink``-factor box around the winner.
  Resolution doubles-plus per rung while the rung cost decays
  geometrically, so reaching grid resolution ``δ`` costs
  O(num_candidates · log(width/δ)) evaluations vs the exhaustive grid's
  O(width/δ).
* :func:`coordinate_hillclimb` — pattern search over the axes: the ±step
  neighborhood is ONE scenario batch per iteration; steps halve when no
  neighbor improves.

Constraints (e.g. :class:`CapRateCeiling`, the delta-table ``num_capped``
rate) enter as feasibility margins: feasible candidates are ranked by
objective, infeasible ones by margin, and a feasible incumbent always
beats an infeasible one.

The driving entry point is
:meth:`repro.core.counterfactual.CounterfactualEngine.search`, which runs
the batched sweep (any driver / resolve / chunking plan) as the inner
evaluation loop. See ``examples/scenario_search.py``.
"""
from repro.search.ledger import BudgetExhausted, EvaluationLedger
from repro.search.objectives import (OBJECTIVES, CapRateCeiling,
                                     as_objective, revenue_objective,
                                     score_sweep, spend_objective)
from repro.search.optimize import (SEARCH_METHODS, SearchResult,
                                   coordinate_hillclimb, successive_halving)
from repro.search.space import SEARCH_AXES, SearchSpace

__all__ = [
    "BudgetExhausted", "EvaluationLedger",
    "OBJECTIVES", "CapRateCeiling", "as_objective", "revenue_objective",
    "spend_objective", "score_sweep",
    "SEARCH_METHODS", "SearchResult", "coordinate_hillclimb",
    "successive_halving",
    "SEARCH_AXES", "SearchSpace",
]
