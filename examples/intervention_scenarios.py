"""Targeted intervention scenarios under the common-random-numbers contract.

Design grids ask "what if the rules changed"; this example asks the targeted
counterfactuals a platform operator actually types: pause a campaign, double
another's bids, delay one to the second half of the day, inject an entrant,
and stress the answer under bid noise — all compiled by
:func:`repro.scenarios.compile_family` into ONE batched sweep where every
scenario shares the same keyed random world, so lane-vs-lane deltas are the
interventions themselves, not sampling noise.

Then :meth:`engine.attribute` Shapley-decomposes a composed what-if
("pause 1 AND boost 2 AND add a reserve — which part moved revenue?") over
the full subset lattice, with the efficiency axiom holding exactly.

    PYTHONPATH=src python examples/intervention_scenarios.py
"""
import time

import jax
import numpy as np

from repro.core import AuctionRule, CounterfactualEngine
from repro.data import make_synthetic_env
from repro.scenarios import (AddEntrant, BidNoise, BoostCampaign,
                             BudgetPacing, PauseCampaign, SetReserve,
                             compile_family)


def main(n_events: int = 16_384, n_campaigns: int = 16) -> None:
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=10)
    engine = CounterfactualEngine(env.values, env.budgets,
                                  AuctionRule.first_price(n_campaigns))
    key = jax.random.PRNGKey(42)

    family = compile_family(
        engine.values, engine.budgets, engine.base_rule,
        [
            PauseCampaign(3),
            BoostCampaign(7, 2.0),
            BudgetPacing(5, start=n_events // 2),        # delayed start
            AddEntrant(budget=float(np.asarray(env.budgets).mean()),
                       slot="entrant"),
            [BidNoise(0.1), PauseCampaign(3)],           # noisy re-ask
        ],
        key=key)
    print(f"N={n_events} events, {n_campaigns} campaigns "
          f"(+{family.num_entrants} entrant slot), "
          f"S={family.num_scenarios} scenarios, "
          f"overlay per_event={family.overlay.per_event}\n")

    t0 = time.perf_counter()
    swept = engine.sweep(family)
    print(swept.format_delta_table())
    print(f"[swept in {time.perf_counter() - t0:.2f}s]\n")

    spend = np.asarray(swept.results.final_spend)
    assert spend[1, 3] == 0.0, "paused campaign must spend nothing"
    assert spend[0, n_campaigns] == 0.0, "entrant is off in the base lane"
    assert spend[4, n_campaigns] > 0.0, "entrant is live in its own lane"

    # CRN in action: the noisy pause lane differs from the noiseless pause
    # lane only through sigma -- same pause, same random world.
    print("pause[3] spend delta, noiseless vs sigma=0.1 lane: "
          f"{spend[5].sum() - spend[1].sum():+.2f} "
          "(intervention shared, noise isolated)\n")

    t0 = time.perf_counter()
    att = engine.attribute(
        {"pause3": PauseCampaign(3), "boost7": BoostCampaign(7, 2.0),
         "reserve": SetReserve(0.1)},
        key=key)
    print(att.format_table())
    print(f"[2^3 subset lattice attributed in "
          f"{time.perf_counter() - t0:.2f}s]")
    assert att.efficiency_gap <= 1e-6 * max(1.0, abs(att.total_delta)), \
        "Shapley efficiency axiom violated"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
