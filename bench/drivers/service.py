"""What-if asks against a growing log: ``CounterfactualService`` on its exact
path (``ask`` / ``flush``), driven open loop.

Mix parameters:

* ``rate_per_s`` — the asks' mean arrival rate. A run of ``seconds`` holds
  ``round(rate_per_s * seconds)`` asks, a Poisson process conditioned on
  its count: the sorted points of a uniform draw over the window;
* ``pool_share`` of the asks come from a pool of ``pool_size`` dashboard
  designs with Zipf popularity (exponent ``pool_zipf_s``); the rest are
  fresh designs. A design's kind is drawn uniformly from three: one
  campaign's bids times ``bid_multiplier`` (uniform range), all budgets
  times ``budget_factor`` (uniform range), or a reserve (uniform range
  ``reserve``). Fresh asks split evenly over the kinds;
* ``mix_seed`` — draws the arrival times, the designs, the pool's picks
  and the order in which the asks arrive; the run's seed draws the day's
  events, so every seed gets the same asks at the same times, asked of
  another log;
* the service: ``events_per_chunk``, ``max_batch``, ``scenario_chunks``,
  ``store``; at set-up it holds day 1;
* ``slab_due_fractions`` — day 2 arrives as that many equal aligned slabs,
  due at those fractions of the window;
* ``reference_segment`` / ``reference_lane_block`` — the reference replay's
  snapshot interval (a divisor of every log length) and lane padding.

The driver loop is the service's only thread. It appends a slab that is
due, else admits at most ``max_batch`` of the asks that are due and
flushes, else sleeps until the next arrival. An ask's latency runs from the
time it was due until its answer is in host memory; an ask due but not yet
admitted keeps waiting. Asks still pending when the window ends are
answered and counted.

The check compares every answered ask with the plain reference's
sequential replay of the log prefix it was admitted under (the numbers of
``bench.reference.error_numbers`` that the cell's limits name), and
requires each answer to carry the log version its ask was admitted under
(``wrong_version``)."""
from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import compared, error_numbers, replay

N_KINDS = 3
# the warm-up's budgets: above any campaign's spend over the whole log
WARM_BUDGET = 1e9


def make_designs(rng, kinds, base_budgets, traffic) -> list:
    """One design per entry of ``kinds``: ``(multipliers, reserve,
    budgets)`` host float32 arrays."""
    n_campaigns = base_budgets.shape[0]
    designs = []
    for kind in kinds:
        mult = np.ones(n_campaigns, np.float32)
        budgets = base_budgets.copy()
        reserve = np.float32(0.0)
        if kind == 0:
            lo, hi = traffic["bid_multiplier"]
            mult[rng.integers(n_campaigns)] = rng.uniform(lo, hi)
        elif kind == 1:
            lo, hi = traffic["budget_factor"]
            budgets = (budgets * rng.uniform(lo, hi)).astype(np.float32)
        else:
            lo, hi = traffic["reserve"]
            reserve = np.float32(rng.uniform(lo, hi))
        designs.append((mult, reserve, budgets))
    return designs


def make_schedule(seconds: float, traffic, base_budgets):
    """``(due (n,), design_of_ask (n,), designs)``: the mix's arrivals and
    asks, all drawn from ``mix_seed``."""
    mix = np.random.default_rng(int(traffic["mix_seed"]))
    n = max(1, round(traffic["rate_per_s"] * seconds))
    due = np.sort(mix.uniform(0.0, seconds, n))
    pool_size = int(traffic["pool_size"])
    pool = make_designs(mix, np.arange(pool_size) % N_KINDS, base_budgets,
                        traffic)
    n_pool = round(traffic["pool_share"] * n)
    weights = np.arange(1, pool_size + 1, dtype=np.float64) ** (
        -float(traffic["pool_zipf_s"]))
    pool_pick = mix.choice(pool_size, size=n_pool, p=weights / weights.sum())
    n_fresh = n - n_pool
    fresh = make_designs(mix, np.arange(n_fresh) % N_KINDS, base_budgets,
                         traffic)
    is_pool = mix.permutation(np.arange(n) < n_pool)
    design_of_ask = np.empty(n, np.int64)
    design_of_ask[is_pool] = mix.permutation(pool_pick)
    design_of_ask[~is_pool] = pool_size + mix.permutation(n_fresh)
    return due, design_of_ask, pool + fresh


def _service(budgets, traffic, events=None):
    from repro.serve import CounterfactualService
    return CounterfactualService(
        budgets, events=events,
        events_per_chunk=int(traffic["events_per_chunk"]),
        max_batch=int(traffic["max_batch"]),
        scenario_chunks=int(traffic["scenario_chunks"]),
        store=traffic["store"])


def _ask(svc, design):
    from repro.core.types import AuctionRule
    mult, reserve, budgets = design
    return svc.ask(AuctionRule(multipliers=mult, reserve=reserve,
                               kind="first_price"), budgets)


def _warm(ctx, day1, slabs, budgets, host_budgets):
    """Run every program the window uses, on a service of its own: the
    flush of each count 1..max_batch of uncached designs at day 1's length
    (the stacking and padding of the lanes depend on that count, not on
    the log), and at each later log length the concatenation and the flush
    of each padded lane count.

    Its designs have budgets far above any spend (``WARM_BUDGET``), which
    end every replay after one round: the same programs, at a round's
    cost. The budgets stay finite so that every design is distinct: an
    unbounded budget scaled by a factor is the same design again, which
    the service answers from its cache or dedupes, leaving larger lane
    counts cold. Each flush is checked to replay exactly the designs it
    was asked."""
    traffic = ctx.traffic
    rng = np.random.default_rng([ctx.seed, 1])
    host_budgets = np.full_like(host_budgets, WARM_BUDGET)
    max_batch = int(traffic["max_batch"])
    unit = int(traffic["scenario_chunks"])
    svc = _service(budgets, traffic, events=day1)

    def flush_distinct(n):
        misses = svc.misses
        for d in make_designs(rng, np.arange(n) % N_KINDS, host_budgets,
                              traffic):
            _ask(svc, d)
        svc.flush()
        if svc.misses - misses != n:
            raise RuntimeError(f"warm-up: {n} designs asked, "
                               f"{svc.misses - misses} replayed")

    for n in range(1, max_batch + 1):
        flush_distinct(n)
    for slab in slabs:
        svc.append(slab)
        for n in range(unit, max_batch + 1, unit):
            flush_distinct(n)


def day2_slabs(data, traffic) -> list:
    """Day 2 as the mix's equal aligned slabs, one per due fraction."""
    n_slabs = len(traffic["slab_due_fractions"])
    n_day2 = data["day2"].shape[0]
    if n_day2 % n_slabs:
        raise ValueError(f"day 2's {n_day2} events do not split into "
                         f"{n_slabs} equal slabs")
    size = n_day2 // n_slabs
    return [data["day2"][k * size:(k + 1) * size] for k in range(n_slabs)]


def setup(ctx):
    traffic = ctx.traffic
    gen = importlib.import_module(f"bench.gen.{ctx.config['generator']}")
    with ctx.spans("setup.inputs"):
        data = gen.make(ctx.key, ctx.config)
        jax.block_until_ready(data["day2"])
    slabs = day2_slabs(data, traffic)
    host_budgets = np.asarray(data["budgets"])
    due, design_of_ask, designs = make_schedule(ctx.seconds, traffic,
                                                host_budgets)
    with ctx.spans("setup.warmup"):
        _warm(ctx, data["day1"], slabs, data["budgets"], host_budgets)
    svc = _service(data["budgets"], traffic, events=data["day1"])
    return {"ctx": ctx, "data": data, "slabs": slabs, "svc": svc,
            "due": due, "design_of_ask": design_of_ask,
            "designs": designs}


def window(state, seconds):
    ctx, svc = state["ctx"], state["svc"]
    spans, traffic = ctx.spans, ctx.traffic
    due, design_of_ask, designs = (state["due"], state["design_of_ask"],
                                   state["designs"])
    slab_due = [f * seconds for f in traffic["slab_due_fractions"]]
    slabs = list(state["slabs"])
    max_batch = int(traffic["max_batch"])
    n = len(due)
    answered = np.full(n, np.nan)
    admitted = np.full(n, np.nan)
    versions = np.zeros(n, np.int64)
    n_events = np.zeros(n, np.int64)
    answers = [None] * n
    flush_s = []
    failed = 0
    i = 0
    t0 = time.perf_counter()
    with spans("bench.window"):
        while i < n or slabs:
            now = time.perf_counter() - t0
            if slabs and slab_due[0] <= now:
                with spans("svc.append"):
                    svc.append(slabs.pop(0))
                slab_due.pop(0)
                continue
            if i < n and due[i] <= now:
                batch = []
                with spans("svc.ask"):
                    while i < n and due[i] <= now and len(batch) < max_batch:
                        versions[i] = svc.log_version
                        n_events[i] = svc.n_events
                        admitted[i] = now
                        batch.append((i, _ask(svc,
                                              designs[design_of_ask[i]])))
                        i += 1
                f0 = time.perf_counter()
                try:
                    with spans("svc.flush"):
                        svc.flush()
                except Exception as e:  # noqa: BLE001 - counted as failed
                    failed += len(batch)
                    state.setdefault("errors", []).append(repr(e))
                    continue
                flush_s.append(time.perf_counter() - f0)
                with spans("svc.answer"):
                    for k, ticket in batch:
                        answers[k] = ticket.result()
                answered[[k for k, _ in batch]] = time.perf_counter() - t0
                continue
            nxt = min(([due[i]] if i < n else []) + slab_due[:1])
            with spans("driver.idle"):
                time.sleep(max(0.0, nxt - (time.perf_counter() - t0)))
    latency = answered - due
    ok = np.isfinite(latency)
    worst = latency[ok].max(initial=0.0)
    latency = np.where(ok, latency, 2.0 * worst + 1.0)   # failed: slowest
    stats = svc.stats
    hits, misses = stats["hits"], stats["misses"]
    # asks due inside the window but not yet admitted when it closed
    backlog = int(np.sum((due < seconds) & ~(admitted <= seconds)))
    return {"end_to_end": {"ask_p50_ms": 1e3 * np.percentile(latency, 50),
                           "ask_p95_ms": 1e3 * np.percentile(latency, 95)},
            "attempted": n, "failed": failed, "latency_s": latency,
            "versions": versions, "n_events_at_ask": n_events,
            "answers": answers, "flush_s": flush_s, "hits": hits,
            "misses": misses, "window_s": time.perf_counter() - t0,
            "backlog_at_close": backlog,
            "note": (f"{n} asks, {len(flush_s)} flushes, hits {hits}, "
                     f"misses {misses}, backlog at close {backlog}, "
                     f"worst latency {latency.max()!r} s")}


def finish(state, obs):
    pass


def reference_spends(state, obs, **precision):
    """``(asks, spends)``: the answered asks and, for each, the reference
    replay (``precision``: its ``dtype`` / ``spend_dtype``) of its design
    over the log prefix it was admitted under. All distinct designs replay as lanes of one scan,
    with a snapshot every ``reference_segment`` events."""
    traffic = state["ctx"].traffic
    design_of_ask, designs = state["design_of_ask"], state["designs"]
    done = [k for k, a in enumerate(obs["answers"]) if a is not None]
    lanes = sorted({int(design_of_ask[k]) for k in done})
    lane_of = {d: j for j, d in enumerate(lanes)}
    block = int(traffic["reference_lane_block"])
    padded = lanes + [lanes[0]] * (-len(lanes) % block)
    mult = np.stack([designs[d][0] for d in padded])
    reserve = np.array([designs[d][1] for d in padded], np.float32)
    budgets = np.stack([designs[d][2] for d in padded])
    values = jnp.concatenate([state["data"]["day1"], state["data"]["day2"]])
    segment = int(traffic["reference_segment"])
    spend, _ = replay(values, budgets, mult, reserve, segment=segment,
                      **precision)
    del values
    ref = np.stack([spend[obs["n_events_at_ask"][k] // segment - 1,
                         lane_of[int(design_of_ask[k])]] for k in done])
    return done, ref


def check(state, obs, limits):
    done = [k for k, a in enumerate(obs["answers"]) if a is not None]
    wrong_version = sum(obs["answers"][k].log_version != obs["versions"][k]
                        for k in done)
    del state["svc"]                    # the program's state is freed
    state.pop("slabs")
    _, ref = reference_spends(state, obs)
    got = np.stack([obs["answers"][k].final_spend for k in done])
    numbers = error_numbers(got, ref, ask_budgets(state, done))
    return compared(numbers, limits) + [("wrong_version",
                                         float(wrong_version), 0.0)]


def ask_budgets(state, asks) -> np.ndarray:
    designs, design_of_ask = state["designs"], state["design_of_ask"]
    return np.stack([designs[design_of_ask[k]][2] for k in asks])
