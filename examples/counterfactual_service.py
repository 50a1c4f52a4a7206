"""An always-on counterfactual service over a growing event log.

The platform's day does not arrive at once: events stream in, and the
what-if questions ("what if campaign 3 bid 1.5×?", "what if budgets were
30% tighter?") arrive continuously between the appends. This example runs
that loop end to end with :class:`repro.serve.CounterfactualService`:

* the day's log arrives in aligned slabs (``append`` — bumping the
  monotone ``log_version`` and invalidating the answer cache);
* two scenarios are *registered* for streaming — every append folds ONLY
  the new events into their carried burnout state (O(new events), the
  causal frontier estimate);
* between appends, batched ``ask`` tickets answer exact what-ifs against
  the full log so far, deduped through the ``(log_version, fingerprint)``
  cache;
* at end of day, a service-bound engine replays the same questions —
  entirely from cache — and the answers are asserted BITWISE equal to a
  one-shot ``CounterfactualEngine.sweep`` of the full day.

    PYTHONPATH=src python examples/counterfactual_service.py
"""
import time

import jax
import numpy as np

from repro.core import AuctionRule, CounterfactualEngine, ScenarioGrid
from repro.data import make_synthetic_env
from repro.serve import CounterfactualService


def main(n_events: int = 8_192, n_campaigns: int = 16,
         n_slabs: int = 4) -> None:
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=10)
    base = AuctionRule.first_price(n_campaigns)
    slab_len = n_events // n_slabs
    svc = CounterfactualService(env.budgets, base,
                                events_per_chunk=slab_len // 4)
    print(f"N={n_events} events arriving in {n_slabs} slabs of {slab_len}, "
          f"C={n_campaigns} campaigns\n")

    # watch two designs continuously: every append folds only the new slab
    svc.register("base")
    svc.register("boost3", base.with_multiplier(3, 1.5))

    scenarios = [(base, env.budgets),
                 (base.with_multiplier(3, 1.5), env.budgets),
                 (base, env.budgets * 0.7)]
    labels = ("base", "boost3", "tight budgets")
    grid = ScenarioGrid.from_scenarios(scenarios, labels)
    # one grid = one pricing kind; asks have no such limit — the admission
    # drain groups per kind and runs one batched replay per group
    second = (AuctionRule.second_price(n_campaigns), env.budgets)

    for k in range(n_slabs):
        slab = env.values[k * slab_len:(k + 1) * slab_len]
        t0 = time.perf_counter()
        version = svc.append(slab)
        dt_fold = time.perf_counter() - t0
        frontier = svc.streaming("boost3")
        capped = int((frontier.cap_times <= svc.n_events).sum())
        print(f"slab {k + 1}/{n_slabs}: log_version={version}, "
              f"n_events={svc.n_events}, fold {dt_fold * 1e3:.1f} ms; "
              f"boost3 frontier: spend={frontier.final_spend.sum():.2f}, "
              f"{capped}/{n_campaigns} capped")

        # exact asks against the log so far — one batched replay per
        # pricing kind per drain (first_price lanes pack together; the
        # second_price ask rides in its own batch)
        ask_list = list(zip(scenarios, labels)) + [(second, "second price")]
        tickets = [svc.ask(rule, budgets, label=lbl)
                   for (rule, budgets), lbl in ask_list]
        answers = [t.result() for t in tickets]
        for (_, lbl), ans in zip(ask_list, answers):
            print(f"    ask[{lbl:>14}] v{ans.log_version}: "
                  f"spend={ans.final_spend.sum():8.2f}  "
                  f"capped={int((ans.cap_times <= svc.n_events).sum())}")
    print()

    # end of day: the same questions through a service-bound engine are
    # answered from cache (no new batches), bitwise the one-shot engine
    stats_before = svc.stats
    result = svc.engine().sweep(grid)
    assert svc.stats["batches"] == stats_before["batches"], \
        "end-of-day sweep must be fully cache-served"
    one_shot = CounterfactualEngine(env.values, env.budgets, base).sweep(
        grid)
    assert np.array_equal(np.asarray(result.results.final_spend),
                          np.asarray(one_shot.results.final_spend))
    assert np.array_equal(np.asarray(result.results.cap_times),
                          np.asarray(one_shot.results.cap_times))
    print("end-of-day sweep: cache-served, bitwise equal to the one-shot "
          "engine over the full log\n")
    for row in result.delta_table():
        print(f"{row['scenario']:>14}: revenue={row['revenue']:8.2f} "
              f"(lift {row['revenue_lift']:+7.2%})  "
              f"capped={row['num_capped']}")
    s = svc.stats
    print(f"\nservice stats: {s['appends']} appends -> version "
          f"{s['log_version']}; {s['hits']} hits / {s['misses']} misses in "
          f"{s['batches']} batched replays; {s['registered']} streaming "
          f"scenarios at n={s['n_events']}")
    assert s["hits"] > 0 and s["misses"] > 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
