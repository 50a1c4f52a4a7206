"""Smoke run of the counterfactual sweep engine on a TPU chip.

Drives the system's main path once, through the entry points a user calls,
at the paper's §7.1 synthetic scale (``PAPER_SYNTHETIC_FULL``: N=10^6
events, C=100 campaigns, d=10, b_base=70 — an (N, C) float32 log of 400 MB
on the device), with data made from ``--seed``:

* device — JAX's default backend must be a TPU; there is no CPU fallback;
* sweep — ``CounterfactualEngine.sweep`` over an S=32 design grid (8 bid
  scales x 4 reserves) with the default ``resolve="auto"``: the compiled
  program must hold the fused Pallas round kernel, and every lane must stay
  within the bound the tests hold Algorithm 2 to against the sequential
  oracle (``method="sequential"`` on the same device);
* back-ends — the fused one-launch round must be bitwise the chunked
  two-pass round (the per-shard program of the mesh driver); how far
  ``resolve="fused"`` and ``resolve="jnp"`` differ is reported, not gated;
* service — a ``CounterfactualService`` over the same log, appended in
  aligned slabs, answers 8 what-ifs bitwise equal to ``engine.sweep`` over
  the full log (the service's own contract).

``--chips 4`` runs only the sharded path and what it is compared with: the
same grid with ``driver="sharded"`` on a 4-device mesh (250,000 events per
shard), asserted bitwise equal to the batched sweep on one device, with the
event shards resident on 4 distinct devices.

Times are printed for information, each labelled with the device kind. The
last line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

A failed phase exits non-zero and prints no such line.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on four chips
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.paper_auction import PAPER_SYNTHETIC_FULL
from repro.core import (AuctionRule, CounterfactualEngine,
                        ScenarioGrid, sweep_parallel)
from repro.core.metrics import spend_weighted_relative_error
from repro.data import make_synthetic_env
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import SweepMeshSpec
from repro.serve import CounterfactualService

# Algorithm 2 vs the exact sequential replay: the bound
# tests/test_core_parallel.py::test_parallel_close_to_oracle holds the mean
# relative spend error to; here each lane's spend-weighted relative error.
ORACLE_BOUND = 0.08
BID_SCALES = (1.0, 0.8, 0.9, 1.1, 1.2, 1.3, 1.5, 2.0)
RESERVES = (0.0, 0.15, 0.2, 0.25)


def make_env(seed: int, *, n_events: int, n_campaigns: int, emb_dim: int,
             b_base):
    """The §7.1 synthetic log, built on the default device. Returns
    ``(env, seconds)`` — set-up time, not part of any sweep."""
    t0 = time.perf_counter()
    env = make_synthetic_env(jax.random.PRNGKey(seed), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=emb_dim,
                             b_base=b_base)
    jax.block_until_ready(env.values)
    return env, time.perf_counter() - t0


def _equal(a, b) -> bool:
    """Bitwise equality of two results' final spends and cap times."""
    return (np.array_equal(np.asarray(a.final_spend),
                           np.asarray(b.final_spend))
            and np.array_equal(np.asarray(a.cap_times),
                               np.asarray(b.cap_times)))


def phase_sweep(engine, grid, *, require_kernel: bool) -> dict:
    """The batched sweep with the default plan: compile it (timed), check
    the program for the fused kernel, then run it through
    ``engine.sweep`` (warm-up, then a timed call) and check that answer is
    bitwise the compiled program's."""
    values, budgets, rules = engine.values, grid.budgets, grid.rules
    t0 = time.perf_counter()
    compiled = sweep_parallel.lower(values, budgets, rules).compile()
    compile_s = time.perf_counter() - t0
    kernel = "tpu_custom_call" in compiled.as_text()
    assert kernel or not require_kernel, (
        "the compiled sweep program holds no Pallas kernel: resolve='auto' "
        "did not pick the fused round on this device")
    direct = jax.block_until_ready(compiled(values, budgets, rules))
    jax.block_until_ready(engine.sweep(grid).results.final_spend)
    t0 = time.perf_counter()
    result = engine.sweep(grid)
    jax.block_until_ready(result.results.final_spend)
    sweep_s = time.perf_counter() - t0
    assert _equal(result.results, direct), \
        "engine.sweep differs from the compiled sweep program"
    n_scenarios = grid.num_scenarios
    return dict(result=result.results, kernel=kernel, compile_s=compile_s,
                sweep_s=sweep_s,
                scenario_events_per_s=n_scenarios * engine.n_events / sweep_s)


def phase_oracle(engine, grid, parallel) -> dict:
    """Every lane of the parallel sweep against the exact sequential replay
    on the same device: spend-weighted relative error below
    :data:`ORACLE_BOUND`."""
    t0 = time.perf_counter()
    seq = engine.sweep(grid, method="sequential").results
    jax.block_until_ready(seq.final_spend)
    seq_s = time.perf_counter() - t0
    errors = [float(spend_weighted_relative_error(parallel.final_spend[s],
                                                  seq.final_spend[s]))
              for s in range(grid.num_scenarios)]
    bad = [(grid.labels[s], e) for s, e in enumerate(errors)
           if not e < ORACLE_BOUND]
    assert not bad, f"lanes past the oracle bound {ORACLE_BOUND}: {bad}"
    return dict(errors=errors, sequential_s=seq_s)


def phase_backends(engine, grid, fused, *, events_per_chunk: int) -> dict:
    """The same sweep on other plan cells. The fused round chunked into
    ``events_per_chunk`` slices (whole canonical blocks) runs the two-pass
    program a mesh shard runs, and must be bitwise the one-launch round.
    ``resolve="jnp"`` (chunked the same way, so its (S, N, C) resolve fits
    the device) is compared and reported only."""
    chunked = engine.sweep(grid, chunks=events_per_chunk).results
    assert _equal(chunked, fused), (
        "fused one-launch round and fused chunked two-pass round differ")
    jnp_result = engine.sweep(grid, resolve="jnp",
                              chunks=events_per_chunk).results
    spend_diff = np.abs(np.asarray(jnp_result.final_spend, np.float64)
                        - np.asarray(fused.final_spend, np.float64))
    caps_differ = int((np.asarray(jnp_result.cap_times)
                       != np.asarray(fused.cap_times)).sum())
    return dict(max_spend_diff=float(spend_diff.max()),
                cap_times_differ=caps_differ,
                cap_times_total=int(np.asarray(fused.cap_times).size))


def what_ifs(base: AuctionRule, budgets):
    """Eight design questions a platform would ask of the day's log."""
    return [
        ("base", base, budgets),
        ("all bids x1.2", base.scaled(1.2), budgets),
        ("campaign 0 bids x1.5", base.with_multiplier(0, 1.5), budgets),
        ("campaign 1 bids x0.5", base.with_multiplier(1, 0.5), budgets),
        ("budgets x0.8", base, budgets * 0.8),
        ("budgets x1.25", base, budgets * 1.25),
        ("reserve 0.02", AuctionRule.first_price(budgets.shape[0], 0.02),
         budgets),
        ("last campaign budget x2", base, budgets.at[-1].multiply(2.0)),
    ]


def phase_service(env, base: AuctionRule, asks, *, n_slabs: int) -> dict:
    """A service fed the log in ``n_slabs`` aligned appends answers the
    asks bitwise equal to one ``engine.sweep`` over the full log with the
    same (default) plan."""
    slab = env.n_events // n_slabs
    svc = CounterfactualService(env.budgets, base, events_per_chunk=slab)
    for k in range(n_slabs):
        svc.append(env.values[k * slab:(k + 1) * slab])
    tickets = [svc.ask(rule, budgets, label=label)
               for label, rule, budgets in asks]
    t0 = time.perf_counter()
    svc.flush()
    flush_s = time.perf_counter() - t0
    grid = ScenarioGrid.from_scenarios([(r, b) for _, r, b in asks],
                                       [label for label, _, _ in asks])
    ref = CounterfactualEngine(env.values, env.budgets, base).sweep(grid)
    for i, ticket in enumerate(tickets):
        answer = ticket.result()
        assert np.array_equal(answer.final_spend,
                              np.asarray(ref.results.final_spend[i])) and \
            np.array_equal(answer.cap_times,
                           np.asarray(ref.results.cap_times[i])), \
            f"service answer {ticket.label!r} differs from engine.sweep"
    return dict(asks=len(tickets), flush_s=flush_s, stats=svc.stats)


def phase_sharded(env, grid, devices) -> dict:
    """The grid on an event-sharded mesh over ``devices``, bitwise equal to
    the batched sweep on one device, with the log's shards on distinct
    devices."""
    batched = CounterfactualEngine(env.values, env.budgets).sweep(grid)
    spec = SweepMeshSpec.for_devices(len(devices))
    values = jax.device_put(
        env.values, NamedSharding(spec.mesh, P(spec.event_axes, None)))
    shards = values.addressable_shards
    shard_devices = sorted({s.device.id for s in shards})
    assert len(shard_devices) == len(devices), (
        f"event shards on devices {shard_devices}, expected "
        f"{len(devices)} distinct devices")
    bytes_in_use = {}
    for d in devices:
        stats = d.memory_stats()
        bytes_in_use[d.id] = None if stats is None else \
            stats.get("bytes_in_use")
    engine = CounterfactualEngine(values, env.budgets)
    sharded = engine.sweep(grid, driver="sharded", mesh=spec)
    jax.block_until_ready(sharded.results.final_spend)
    t0 = time.perf_counter()
    sharded = engine.sweep(grid, driver="sharded", mesh=spec)
    jax.block_until_ready(sharded.results.final_spend)
    sharded_s = time.perf_counter() - t0
    assert _equal(sharded.results, batched.results), \
        "sharded sweep differs from the batched sweep on one device"
    return dict(shard_rows=[int(s.data.shape[0]) for s in shards],
                shard_devices=shard_devices, bytes_in_use=bytes_in_use,
                sharded_s=sharded_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Smoke run of the sweep engine on a TPU chip.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic log")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        print(f"chip_smoke: JAX's default backend is {platform!r} "
              f"({kind}), not a TPU; this smoke run needs the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    enable_compile_cache()
    tag = f"[{kind}]"

    cfg = PAPER_SYNTHETIC_FULL
    env, setup_s = make_env(args.seed, n_events=cfg.n_events,
                            n_campaigns=cfg.n_campaigns,
                            emb_dim=cfg.emb_dim, b_base=cfg.b_base)
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=BID_SCALES, reserves=RESERVES)
    print(f"{tag} set-up: §7.1 log N={env.n_events} C={env.n_campaigns} "
          f"d={cfg.emb_dim} b_base={cfg.b_base} seed={args.seed}, S="
          f"{grid.num_scenarios} design grid; built in {setup_s!r} s")

    if args.chips == 4:
        rep = phase_sharded(env, grid, devices[:4])
        print(f"{tag} sharded: event shards of {rep['shard_rows']} rows on "
              f"devices {rep['shard_devices']}; bytes in use per device "
              f"{rep['bytes_in_use']}")
        print(f"{tag} sharded: driver='sharded' on 4 chips is bitwise the "
              f"batched sweep on one chip (final_spend, cap_times); "
              f"sharded sweep {rep['sharded_s']!r} s")
    else:
        sweep = phase_sweep(engine, grid, require_kernel=True)
        print(f"{tag} sweep: fused Pallas kernel in the compiled program: "
              f"{sweep['kernel']}; compile {sweep['compile_s']!r} s, "
              f"sweep {sweep['sweep_s']!r} s, "
              f"{sweep['scenario_events_per_s']!r} scenario-events/s")
        oracle = phase_oracle(engine, grid, sweep["result"])
        print(f"{tag} oracle: all {grid.num_scenarios} lanes within "
              f"{ORACLE_BOUND} of the sequential replay; spend-weighted "
              f"relative error max {max(oracle['errors'])!r}, per lane "
              f"{oracle['errors']}; sequential sweep "
              f"{oracle['sequential_s']!r} s")
        backends = phase_backends(engine, grid, sweep["result"],
                                  events_per_chunk=env.n_events // 8)
        print(f"{tag} back-ends: fused one-launch == fused chunked "
              f"two-pass (bitwise); fused vs jnp: max |final_spend diff| "
              f"{backends['max_spend_diff']!r}, cap_times differing "
              f"{backends['cap_times_differ']} of "
              f"{backends['cap_times_total']}")
        service = phase_service(env, engine.base_rule,
                                what_ifs(engine.base_rule, env.budgets),
                                n_slabs=4)
        print(f"{tag} service: {service['asks']} what-ifs over 4 appended "
              f"slabs bitwise equal to engine.sweep; flush "
              f"{service['flush_s']!r} s; stats {service['stats']}")

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
