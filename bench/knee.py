"""Find the highest ask rate the service sustains, by a sweep of rates on
the chip, once: the rate of ``yahoo72.asks`` is fixed at 0.8 of it.

    python3 bench/knee.py --workload yahoo72.asks --seed <n> --seconds <s> \\
        --rates 40,60,80,100,120

Set-up runs once; then each rate runs the cell's window with a fresh
service and a fresh schedule, and prints one JSON line: the rate, the
latency quantiles and the backlog when the window closed (asks due but not
admitted), and the programs compiled or loaded inside the window (there
should be none). A rate is sustained where that backlog is at most one
batch (``max_batch``); the knee is the highest rate at and below which
every rate of the sweep is sustained.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="yahoo72.asks")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import numpy as np
    from bench import harness
    from bench.drivers import service
    from bench.spans import Spans
    device = harness.device_info()
    if device["platform"] != "tpu":
        print("knee: needs the chip", file=sys.stderr)
        return 2
    harness.enable_cache()
    _, _, config, traffic, _ = harness.load_cell(ROOT, args.workload)
    ctx = harness.Context(config=config, traffic=traffic,
                          seed=args.seed, key=harness.key_for(args.seed),
                          spans=Spans(), seconds=args.seconds)
    state = service.setup(ctx)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}),
          flush=True)
    host_budgets = np.asarray(state["data"]["budgets"])
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic_r = dict(traffic, rate_per_s=rate)
        ctx.traffic = traffic_r
        state["due"], state["design_of_ask"], state["designs"] = \
            service.make_schedule(args.seconds, traffic_r, host_budgets)
        state["svc"] = service._service(state["data"]["budgets"], traffic_r,
                                        events=state["data"]["day1"])
        state["slabs"] = service.day2_slabs(state["data"], traffic)
        with harness.CompileCounter() as compiles:
            obs = service.window(state, args.seconds)
        lat = obs["latency_s"]
        print(json.dumps({
            "rate_per_s": rate, "asks": int(obs["attempted"]),
            "p50_ms": obs["end_to_end"]["ask_p50_ms"],
            "p95_ms": obs["end_to_end"]["ask_p95_ms"],
            "max_ms": 1e3 * float(lat.max()),
            "backlog_at_close": obs["backlog_at_close"],
            "flushes": len(obs["flush_s"]),
            "flush_p50_ms": 1e3 * float(np.median(obs["flush_s"])),
            "hits": obs["hits"], "misses": obs["misses"],
            "sustained": obs["backlog_at_close"] <= traffic["max_batch"],
            "window_compiles": compiles.count,
        }), flush=True)
        del state["svc"]
    return 0


if __name__ == "__main__":
    sys.exit(main())
