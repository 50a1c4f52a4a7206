"""Scenario-sweep throughput: batched device program vs per-scenario loop,
plus the mesh-batched sharded driver.

For S in a doubling schedule, measure scenarios/sec of

* ``loop_host``   — the reference host Algorithm-2 driver called once per
  scenario (two device round-trips per cap-out round, per scenario);
* ``loop_device`` — the device-resident driver called once per scenario
  (no round-trips, but S separate dispatches and no cross-scenario fusion);
* ``batched``     — one vmapped ``parallel_state_machine`` over all S;
* ``sharded``     — (multi-device runs only) ``driver="sharded"``: the same
  batched loop under ``shard_map`` with the event axis sharded over every
  visible device;
* ``host_stream`` — (``--host-stream``) the double-buffered host-streamed
  pipeline: the log lives in host RAM as a :class:`HostStream` and is fed
  chunk-by-chunk via ``jax.device_put`` (chunk size = one canonical
  reduction block, i.e. a simulated device budget of N/32 events), bitwise
  identical to ``batched`` by the host-stream contract.

Emits ``sweep_S{S}_{path},us_per_sweep,scn_per_sec`` rows and merges a
``sweep_scaling`` section — tagged with ``device_count`` so the perf
trajectory distinguishes 1- vs multi-device runs — into BENCH_sweep.json.

Single device:

    PYTHONPATH=src python -m benchmarks.sweep_scaling

Multi-device (fake CPU devices; the flag must precede jax init, which the
``--device-count`` option handles internally — the env var spelling
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` works too):

    PYTHONPATH=src python -m benchmarks.sweep_scaling --device-count 8
"""
from __future__ import annotations

from benchmarks.common import (bench_report, emit, force_host_devices,
                               sweep_argparser, time_call, update_bench_json)


def main(n_events: int = 16_384, n_campaigns: int = 16,
         max_scenarios: int = 16, host_stream: bool = False,
         out: str = "BENCH_sweep.json") -> None:
    # deferred so --device-count can still grow the platform (see common.py)
    import jax

    from repro.core import CounterfactualEngine, parallel_simulate, \
        sweep_parallel
    from repro.core.executor import (ChunkSpec, HostStream, SweepPlan,
                                     execute_sweep)
    from repro.data import make_synthetic_env
    from repro.launch.mesh import SweepMeshSpec

    n_devices = len(jax.devices())
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=8)
    engine = CounterfactualEngine(env.values, env.budgets)
    spec = SweepMeshSpec.for_devices() if n_devices > 1 else None
    records = []

    def record(s_count, path, us):
        scn_per_sec = s_count / (us * 1e-6)
        emit(f"sweep_S{s_count}_{path}", us,
             f"scn_per_sec={scn_per_sec:.2f}")
        records.append({"S": s_count, "path": path,
                        "us_per_call": round(us, 1),
                        "scenarios_per_sec": round(scn_per_sec, 2)})

    s_values = []
    s = 1
    while s <= max_scenarios:
        s_values.append(s)
        s *= 2

    for s_count in s_values:
        # bid scalings around 1.0; scenario 0 is the base design
        scales = [1.0 + 0.02 * i for i in range(s_count)]
        grid = engine.grid(bid_scales=scales)

        def loop(driver):
            outs = []
            for i in range(grid.num_scenarios):
                rule, budgets = grid.scenario(i)
                outs.append(parallel_simulate(env.values, budgets, rule,
                                              driver=driver).final_spend)
            return outs

        _, us = time_call(lambda: loop("host"), repeats=1, warmup=1)
        record(s_count, "loop_host", us)
        _, us = time_call(lambda: loop("device"), repeats=1, warmup=1)
        record(s_count, "loop_device", us)
        _, us = time_call(
            lambda: sweep_parallel(env.values, grid.budgets, grid.rules)
            .final_spend, repeats=1, warmup=1)
        record(s_count, "batched", us)
        if spec is not None:
            try:
                _, us = time_call(
                    lambda: sweep_parallel(env.values, grid.budgets,
                                           grid.rules, driver="sharded",
                                           mesh=spec)
                    .final_spend, repeats=1, warmup=1)
            except ValueError as e:   # shard/grid alignment contract
                print(f"# sharded path skipped: {e}")
                spec = None
            else:
                record(s_count, "sharded", us)
        if host_stream:
            stream = HostStream.from_array(env.values)
            plan = SweepPlan(placement="batched",
                             chunks=ChunkSpec(n_events // 32,
                                              source="host"))
            _, us = time_call(
                lambda: execute_sweep(stream, grid.budgets, grid.rules,
                                      plan)[0], repeats=1, warmup=1)
            record(s_count, "host_stream", us)

    update_bench_json(out, "sweep_scaling", bench_report(
        records, n_events=n_events, n_campaigns=n_campaigns))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = sweep_argparser(__doc__.splitlines()[0], n_events=16_384,
                         n_campaigns=16, out="BENCH_sweep.json",
                         device_count=True)
    ap.add_argument("--max-scenarios", type=int, default=16)
    ap.add_argument("--host-stream", action="store_true",
                    help="also time the host-streamed double-buffered path")
    args = ap.parse_args()
    force_host_devices(args.device_count)
    main(n_events=args.n_events, n_campaigns=args.n_campaigns,
         max_scenarios=args.max_scenarios, host_stream=args.host_stream,
         out=args.out)
