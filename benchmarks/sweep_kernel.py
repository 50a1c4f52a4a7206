"""Scenario-batched resolve kernel throughput, tracked as BENCH_sweep.json.

Four layers; the first three for S in a configurable schedule (default
{1, 8, 32}):

* ``resolve`` — one scenario-batched resolve of the full (N, C) valuation
  matrix: the ``sweep_resolve`` Pallas kernel (tile fetched to VMEM once,
  resolved S times) vs the vmapped jnp resolve (matrix streamed once per
  scenario). This is the per-round resolve cost inside the Algorithm-2 sweep
  loop.
* ``round`` — one whole Algorithm-2 round: the FUSED path (resolve + rate
  partials + cap-out prediction + block partials in ONE dispatch, the jnp
  oracle of ``kernels/auction_resolve/round_fused.py`` — per-event
  winners/prices never cross a program boundary) vs the unfused
  resolve-then-reduce path (a resolve dispatch materialising (S, N)
  winners/prices, then a reduce dispatch re-reading them). Rows carry the
  per-scenario Algorithm-2 round counts (and their histogram), since total
  sweep cost is rounds × round. **CI gate:** on CPU the fused oracle must
  not be slower than resolve+reduce at the largest S in the schedule — the
  benchmark exits non-zero if it is.
* ``sweep`` — end-to-end ``sweep_parallel``: the batched state machine with
  ``resolve="pallas"`` vs the vmapped jnp state machine.
* ``stream`` — events/sec vs N at a FIXED chunk size: the event-chunked
  streaming executor (``chunks=``, working set bounded by the chunk) vs the
  in-memory batched driver at S=8, timed with ``common.time_pair``
  interleaved medians (sequential A/B windows swing 2× under load on a
  shared box). Tracks the streaming overhead a bounded working set costs as
  N grows — the chunked path re-resolves each chunk once per reduction
  window, so CPU numbers are an upper bound on the TPU story (where the
  chunk scan is what lets N outgrow HBM at all).
* ``hoststream`` — memory-unbounded sweeps: the host-streamed executor
  (``ChunkSpec(source="host")``, log resident in host RAM, chunks fed
  through per-chunk ``jax.device_put``) at N = 32× a simulated device
  budget of one chunk, comparing the double-buffered pipeline (next
  chunk's transfer issued while the current chunk's step is in flight)
  against synchronous per-chunk puts (``prefetch=False``: block on every
  put and step) and against the device-resident batched driver —
  ``common.time_pair`` interleaved medians, written to its OWN json
  section (``sweep_hoststream``). All three paths are bitwise identical;
  only the wall clock differs. On CPU the H2D put is a near-no-op, so the
  double-buffered margin tracks dispatch pipelining only — a lower bound
  on the accelerator story, where the put is a real transfer the pipeline
  hides behind compute.
* ``search`` — scenario-space search (``engine.search``, successive halving
  over the reserve axis) vs the exhaustive grid at the resolution the
  search converges to, timed with ``common.time_pair`` interleaved medians
  and reported with the evaluation counts from the search ledger. Written
  to its OWN json section (``sweep_search``) so the CI invocation that runs
  only this layer (``--layers search``) does not clobber the kernel rows.
* ``tuned`` — the measured plan autotuner (``repro.tune``): one
  ``autotune`` pass on a tiny trial budget writes the persistent tuning
  cache, then the end-to-end ``execute_sweep`` with the tuned plan
  (resolved THROUGH that cache) is timed against the default plan with
  ``common.time_pair`` interleaved medians, plus the cache-hit resolution
  latency (what every later same-shape sweep pays). Written to its OWN
  json section (``sweep_tuned``) with the winner configs per S. **CI
  gate:** the tuned plan must not be more than 1.10x slower than the
  default — the tuner records the default when nothing beats it, so a
  bigger gap means resolution itself regressed; the benchmark exits
  non-zero.
* ``service`` — the always-on service's incremental-append streaming fold
  (``execute_sweep_resumable`` over the newest slab only, the O(new
  events) causal-frontier update) vs a full-log exact replay
  (``execute_sweep``) at the same S=8 design batch, for N in {2048, 8192}
  with quarter-log slabs — ``common.time_pair`` interleaved medians,
  written to its OWN json section (``sweep_service``) for the same
  no-clobber reason as ``search``.

``--layers`` selects a subset (default: all).

Besides the usual CSV rows on stdout, merges a JSON perf section (default
``BENCH_sweep.json``, key ``sweep_kernel``, tagged with ``device_count``)
with scenarios/sec per (S, layer, path) so the trajectory is comparable
across commits; CI uploads it as an artifact. On CPU the Pallas kernels run
in interpret mode — those numbers track correctness cost, not TPU speed
(which is why ``resolve="auto"`` routes around them; the ``round`` layer
times the jnp realizations that actually run on CPU).
``benchmarks/sweep_scaling.py`` writes the multi-device rows of the same
file.

    PYTHONPATH=src python -m benchmarks.sweep_kernel
"""
from __future__ import annotations

import functools

from benchmarks.common import (bench_report, emit, sweep_argparser,
                               time_call, time_pair, update_bench_json)


LAYERS = ("resolve", "round", "sweep", "stream", "hoststream", "search",
          "service", "tuned")


def main(n_events: int = 2048, n_campaigns: int = 32,
         s_values=(1, 8, 32), block_t: int = 256,
         out: str = "BENCH_sweep.json",
         stream_n_values=(2048, 4096, 8192),
         stream_chunk: int = 1024,
         hoststream_n_values=(8192, 16384, 32768),
         layers=LAYERS) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import (AuctionRule, ScenarioGrid, auction,
                            sweep_parallel, sweep_state_machine)
    from repro.core import segments as seg_lib
    from repro.core.parallel import lane_predict
    from repro.data import make_synthetic_env
    from repro.kernels import on_tpu
    from repro.kernels.auction_resolve import sweep_resolve

    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=8)
    base = AuctionRule.first_price(n_campaigns)
    records = []

    def record(s_count, layer, path, us, **extra):
        scn_per_sec = s_count / (us * 1e-6)
        emit(f"{layer}_S{s_count}_{path}", us,
             f"scn_per_sec={scn_per_sec:.2f}")
        records.append({"S": s_count, "layer": layer, "path": path,
                        "us_per_call": round(us, 1),
                        "scenarios_per_sec": round(scn_per_sec, 2), **extra})

    # --- one Algorithm-2 round, fused vs resolve+reduce (jnp realizations,
    # i.e. what actually runs on CPU; the Pallas variants are the `resolve`
    # and `sweep` layers' subject) -----------------------------------------
    lane_pred = functools.partial(lane_predict, n_events=n_events)

    def _reduce(winners, prices, b, s_hat, act, n_hat):
        sums = jax.vmap(
            lambda w, p, nh: seg_lib.block_from_events(w, p, n_campaigns, nh,
                                                       n_events)
        )(winners, prices, n_hat)
        c_next, no_cap, n_next = jax.vmap(lane_pred)(sums, b, s_hat, act,
                                                     n_hat)
        blk = jax.vmap(
            lambda w, p, lo, hi: seg_lib.block_from_events(
                w, p, n_campaigns, lo, hi))(winners, prices, n_hat, n_next)
        return blk, c_next, no_cap, n_next

    @jax.jit
    def resolve_dispatch(act, rules):
        return jax.vmap(lambda a, r: auction.resolve(env.values, a, r),
                        in_axes=(0, 0))(act, rules)

    @jax.jit
    def reduce_dispatch(winners, prices, b, s_hat, act, n_hat):
        return _reduce(winners, prices, b, s_hat, act, n_hat)

    @jax.jit
    def fused_round_dispatch(act, rules, b, s_hat, n_hat):
        winners, prices = jax.vmap(
            lambda a, r: auction.resolve(env.values, a, r),
            in_axes=(0, 0))(act, rules)
        return _reduce(winners, prices, b, s_hat, act, n_hat)

    round_gate = {}
    kernel_layers = {"resolve", "round", "sweep"} & set(layers)
    for s_count in (s_values if kernel_layers else ()):
        scales = [1.0 + 0.02 * i for i in range(s_count)]
        grid = ScenarioGrid.product(base, env.budgets, bid_scales=scales)
        act = jnp.ones((s_count, n_campaigns), bool)

        if "resolve" in layers:
            _, us = time_call(lambda: sweep_resolve(
                env.values, grid.rules.multipliers, act, grid.rules.reserve,
                block_t=block_t)[2], repeats=2, warmup=1)
            record(s_count, "resolve", "pallas", us)

            _, us = time_call(lambda: jax.vmap(
                lambda a, r: auction.resolve(env.values, a, r),
                in_axes=(0, 0))(act, grid.rules)[1], repeats=2, warmup=1)
            record(s_count, "resolve", "vmap_jnp", us)

        if "round" in layers:
            # round layer: mid-sweep state (everyone active, frontier at 0)
            b = grid.budgets.astype(jnp.float32)
            s_hat = jnp.zeros((s_count, n_campaigns), jnp.float32)
            n_hat = jnp.zeros((s_count,), jnp.int32)
            rounds = sweep_state_machine(env.values, grid.budgets,
                                         grid.rules, resolve="jnp")[4]
            counts = [int(r) for r in rounds]
            hist = {}
            for r in counts:
                hist[str(r)] = hist.get(str(r), 0) + 1

            def fused():
                return fused_round_dispatch(act, grid.rules, b, s_hat,
                                            n_hat)[0]

            def unfused():
                winners, prices = resolve_dispatch(act, grid.rules)
                return reduce_dispatch(winners, prices, b, s_hat, act,
                                       n_hat)[0]

            # interleaved pairwise timing: load drift on a shared machine
            # hits both paths alike, so the medians stay comparable (a
            # sequential A-then-B measurement here can swing either way 2x)
            us_fused, us_unfused = time_pair(fused, unfused, repeats=15,
                                             warmup=2)
            record(s_count, "round", "fused_oracle", us_fused,
                   round_counts=counts, round_count_hist=hist)
            record(s_count, "round", "resolve+reduce", us_unfused,
                   round_counts=counts, round_count_hist=hist)
            round_gate[s_count] = (us_fused, us_unfused)

        if "sweep" in layers:
            _, us = time_call(lambda: sweep_parallel(
                env.values, grid.budgets, grid.rules,
                resolve="pallas").final_spend, repeats=1, warmup=1)
            record(s_count, "sweep", "pallas", us)

            _, us = time_call(lambda: sweep_parallel(
                env.values, grid.budgets, grid.rules,
                resolve="jnp").final_spend, repeats=1, warmup=1)
            record(s_count, "sweep", "vmap_jnp", us)

    # --- stream layer: events/sec vs N at a fixed chunk size ---------------
    stream_s = 8
    for n_stream in (stream_n_values if "stream" in layers else ()):
        env_n = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_stream,
                                   n_campaigns=n_campaigns, emb_dim=8)
        grid_n = ScenarioGrid.product(
            base, env_n.budgets,
            bid_scales=[1.0 + 0.02 * i for i in range(stream_s)])

        def chunked():
            return sweep_parallel(env_n.values, grid_n.budgets,
                                  grid_n.rules, resolve="jnp",
                                  chunks=stream_chunk).final_spend

        def in_memory():
            return sweep_parallel(env_n.values, grid_n.budgets,
                                  grid_n.rules, resolve="jnp").final_spend

        us_c, us_m = time_pair(chunked, in_memory, repeats=7, warmup=1)
        for path, us in (("chunked", us_c), ("in_memory", us_m)):
            ev_per_sec = n_stream / (us * 1e-6)
            emit(f"stream_N{n_stream}_{path}", us,
                 f"events_per_sec={ev_per_sec:.0f}")
            records.append({
                "S": stream_s, "N": n_stream, "layer": "stream",
                "path": path, "events_per_chunk": stream_chunk,
                "us_per_call": round(us, 1),
                "events_per_sec": round(ev_per_sec, 1)})

    # --- hoststream layer: double-buffered vs synchronous-put vs resident --
    if "hoststream" in layers:
        from repro.core import execute_sweep
        from repro.core.executor import ChunkSpec, HostStream, SweepPlan

        hs_s = 8
        hs_records = []
        for n_hs in hoststream_n_values:
            # simulated device budget: one chunk resident = N/32 events
            # (the smallest aligned chunk — whole canonical blocks), so the
            # log is 32x past what the "device" holds
            hs_chunk = n_hs // 32
            env_n = make_synthetic_env(jax.random.PRNGKey(0),
                                       n_events=n_hs,
                                       n_campaigns=n_campaigns, emb_dim=8)
            grid_n = ScenarioGrid.product(
                base, env_n.budgets,
                bid_scales=[1.0 + 0.02 * i for i in range(hs_s)])
            stream = HostStream.from_array(env_n.values)

            def hs_run(prefetch):
                plan = SweepPlan(placement="batched", resolve="jnp",
                                 chunks=ChunkSpec(hs_chunk, source="host",
                                                  prefetch=prefetch))
                return execute_sweep(stream, grid_n.budgets, grid_n.rules,
                                     plan)[0]

            def hs_resident():
                return execute_sweep(env_n.values, grid_n.budgets,
                                     grid_n.rules,
                                     SweepPlan(placement="batched",
                                               resolve="jnp"))[0]

            us_db, us_sync = time_pair(lambda: hs_run(True),
                                       lambda: hs_run(False), repeats=5,
                                       warmup=1)
            us_db2, us_res = time_pair(lambda: hs_run(True), hs_resident,
                                       repeats=5, warmup=1)
            pipeline_speedup = us_sync / us_db
            for path, us in (("double_buffered", us_db),
                             ("synchronous_put", us_sync),
                             ("device_resident", us_res)):
                ev_per_sec = n_hs / (us * 1e-6)
                emit(f"hoststream_N{n_hs}_{path}", us,
                     f"events_per_sec={ev_per_sec:.0f}")
                hs_records.append({
                    "S": hs_s, "N": n_hs, "layer": "hoststream",
                    "path": path, "events_per_chunk": hs_chunk,
                    "us_per_call": round(us, 1),
                    "events_per_sec": round(ev_per_sec, 1)})
            hs_records[-3]["pipeline_speedup_vs_sync"] = round(
                pipeline_speedup, 3)
            hs_records[-3]["us_vs_resident"] = round(us_db2, 1)
            print(f"hoststream N={n_hs}: double-buffered "
                  f"{pipeline_speedup:.2f}x the synchronous-put pipeline "
                  f"({us_db / 1e3:.0f}ms vs {us_sync / 1e3:.0f}ms; "
                  f"device-resident {us_res / 1e3:.0f}ms)")
        update_bench_json(out, "sweep_hoststream", bench_report(
            hs_records, n_campaigns=n_campaigns,
            simulated_device_budget_chunks=32))

    # --- search layer: optimizer vs exhaustive grid at equal resolution ----
    if "search" in layers:
        import numpy as np

        from repro.core import CounterfactualEngine
        from repro.search import SearchSpace

        engine = CounterfactualEngine(env.values, env.budgets,
                                      base_rule=base)
        space = SearchSpace(reserve=(0.0, 0.4))
        xatol = 0.05                       # -> 1/xatol + 1 = 21 grid points
        grid_pts = list(np.linspace(0.0, 0.4, int(round(1 / xatol)) + 1))

        def run_search():
            return engine.search(space, method="halving", budget=64,
                                 num_candidates=8, xatol=xatol)

        def run_grid():
            g = engine.grid(reserves=grid_pts)
            return engine.sweep(g).results.revenue.block_until_ready()

        res = run_search()                 # evaluation counts off-clock
        us_s, us_g = time_pair(run_search, run_grid, repeats=7, warmup=1)
        search_records = []
        for path, us, n_evals in (("halving", us_s, res.evaluations),
                                  ("exhaustive_grid", us_g,
                                   len(grid_pts))):
            emit(f"search_{path}", us, f"evaluations={n_evals}")
            search_records.append({
                "layer": "search", "path": path, "us_per_call": round(us, 1),
                "evaluations": n_evals,
                "evals_per_sec": round(n_evals / (us * 1e-6), 2)})
        print(f"search: {res.evaluations} evaluations vs "
              f"{len(grid_pts)}-point grid, best reserve "
              f"{res.best_point['reserve']:.3f} "
              f"(converged={res.converged})")
        update_bench_json(out, "sweep_search", bench_report(
            search_records, n_events=n_events, n_campaigns=n_campaigns,
            search_budget=64, xatol=xatol))

    # --- service layer: incremental append fold vs full-log replay --------
    if "service" in layers:
        from repro.core import execute_sweep, execute_sweep_resumable
        from repro.core.executor import SweepPlan

        service_s = 8
        plan = SweepPlan(placement="batched", resolve="jnp")
        service_records = []
        for n_service in (2048, 8192):
            env_n = make_synthetic_env(jax.random.PRNGKey(0),
                                       n_events=n_service,
                                       n_campaigns=n_campaigns, emb_dim=8)
            grid_n = ScenarioGrid.product(
                base, env_n.budgets,
                bid_scales=[1.0 + 0.02 * i for i in range(service_s)])
            slab = n_service // 4
            # catch the carry up over the first three slabs off-clock —
            # the appends a long-lived service has already folded
            carry = None
            for k in range(3):
                _, carry = execute_sweep_resumable(
                    env_n.values[k * slab:(k + 1) * slab], grid_n.budgets,
                    grid_n.rules, plan, carry=carry)
            last = env_n.values[3 * slab:]

            def fold_last():
                outs, _ = execute_sweep_resumable(last, grid_n.budgets,
                                                  grid_n.rules, plan,
                                                  carry=carry)
                return outs[0]

            def full_replay():
                return execute_sweep(env_n.values, grid_n.budgets,
                                     grid_n.rules, plan)[0]

            us_i, us_f = time_pair(fold_last, full_replay, repeats=7,
                                   warmup=1)
            for path, us, n_ev in (("incremental_append", us_i, slab),
                                   ("full_replay", us_f, n_service)):
                ev_per_sec = n_ev / (us * 1e-6)
                emit(f"service_N{n_service}_{path}", us,
                     f"events_per_sec={ev_per_sec:.0f}")
                service_records.append({
                    "S": service_s, "N": n_service, "layer": "service",
                    "path": path, "events_per_slab": slab,
                    "us_per_call": round(us, 1),
                    "events_per_sec": round(ev_per_sec, 1)})
        update_bench_json(out, "sweep_service", bench_report(
            service_records, n_campaigns=n_campaigns, slabs=4))

    # --- tuned layer: autotuned plan vs the default plan, via the cache ----
    tuned_gate = {}
    if "tuned" in layers:
        import time

        from repro.core import execute_sweep
        from repro.core.executor import SweepPlan
        from repro.tune import autotune, resolve_plan, shared_cache

        tuned_records = []
        for s_count in s_values:
            grid_s = ScenarioGrid.product(
                base, env.budgets,
                bid_scales=[1.0 + 0.02 * i for i in range(s_count)])
            plan = SweepPlan(block_t="auto", tuned=True)
            report = autotune(env.values, grid_s.budgets, grid_s.rules,
                              plan, trials=5, quick_trials=2, top_k=3,
                              max_events=min(n_events, 4096))
            # cache-hit resolution latency: what every later same-shape
            # sweep pays before its first trace (file stat + memo lookup)
            cache = shared_cache(report.cache_path)
            t0 = time.perf_counter()
            for _ in range(100):
                tuned_plan = resolve_plan(
                    plan, n_events=n_events, n_campaigns=n_campaigns,
                    n_scenarios=s_count, cache=cache)
            resolve_us = (time.perf_counter() - t0) / 100 * 1e6

            def run_tuned():
                return execute_sweep(env.values, grid_s.budgets,
                                     grid_s.rules, tuned_plan)[0]

            def run_default():
                return execute_sweep(env.values, grid_s.budgets,
                                     grid_s.rules, SweepPlan())[0]

            us_t, us_d = time_pair(run_tuned, run_default, repeats=15,
                                   warmup=2)
            tuned_gate[s_count] = (us_t, us_d)
            for path, us in (("tuned", us_t), ("default", us_d)):
                record(s_count, "tuned", path, us)
                tuned_records.append(records.pop())
            tuned_records[-2].update(
                winner_config=report.winner_config, origin=report.origin,
                n_candidates=report.n_candidates,
                cache_hit_resolve_us=round(resolve_us, 1),
                cache_path=str(report.cache_path))
            print(f"tuned S={s_count}: winner {report.winner_config} "
                  f"({report.origin}, {report.n_candidates} candidates), "
                  f"cache-hit resolve {resolve_us:.0f}us")
        update_bench_json(out, "sweep_tuned", bench_report(
            tuned_records, n_events=n_events, n_campaigns=n_campaigns))

    if records:
        update_bench_json(out, "sweep_kernel", bench_report(
            records, n_events=n_events, n_campaigns=n_campaigns,
            block_t=block_t, pallas_interpret=not on_tpu()))

    # CI gate: the fused round oracle must beat (or at worst match) the
    # unfused resolve+reduce dispatch pair at the largest S on CPU — if
    # fusing ever regresses the round, the sweep hot path regressed. The
    # 15% headroom keeps a loaded shared runner's scheduler stalls (which
    # survive even the median-of-15) from failing the build; a genuine
    # fusion regression shows up far past it (quiet-machine wins measured
    # at 1.5–2.9×).
    if not on_tpu() and round_gate:
        s_gate = max(round_gate)
        us_fused, us_unfused = round_gate[s_gate]
        if us_fused > 1.15 * us_unfused:
            raise SystemExit(
                f"FUSED ROUND REGRESSION: fused oracle {us_fused:.0f}us > "
                f"resolve+reduce {us_unfused:.0f}us (+15% headroom) at "
                f"S={s_gate} on CPU")
        print(f"round gate ok at S={s_gate}: fused {us_fused:.0f}us vs "
              f"resolve+reduce {us_unfused:.0f}us")

    # CI gate: the tuned plan must stay within 10% of the default plan at
    # every S — the tuner falls back to the default config when nothing
    # strictly beats it, so a bigger gap means plan resolution itself
    # (cache consult / cost-model ranking) regressed the hot path.
    for s_gate, (us_t, us_d) in sorted(tuned_gate.items()):
        if us_t > 1.10 * us_d:
            raise SystemExit(
                f"TUNED PLAN REGRESSION: tuned sweep {us_t:.0f}us > "
                f"default {us_d:.0f}us (+10% headroom) at S={s_gate}")
        print(f"tuned gate ok at S={s_gate}: tuned {us_t:.0f}us vs "
              f"default {us_d:.0f}us")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = sweep_argparser(__doc__.splitlines()[0], n_events=2048,
                         n_campaigns=32, s_values=(1, 8, 32), block_t=256,
                         out="BENCH_sweep.json")
    ap.add_argument("--layers", nargs="+", default=list(LAYERS),
                    choices=list(LAYERS))
    args = ap.parse_args()
    main(n_events=args.n_events, n_campaigns=args.n_campaigns,
         s_values=tuple(args.s_values), block_t=args.block_t, out=args.out,
         layers=tuple(args.layers))
