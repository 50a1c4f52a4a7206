"""Readings for the limits of a cell's correctness check, on the chip at
the cell's own size: for each seed, the number the check compares as the
program gives it (the sound reading) and as the control gives it, the
control being the plain reference computed on a bfloat16 log and bids
(``bench/reference.py``) put in the program's place.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

Prints one JSON line per seed. The benchmark's own runs never run this. A sweep cell needs no window (one sweep is an answer); the service
cell runs its window at the cell's load, so that it compares as many
answers as a run does.
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


def readings(driver, state, seconds):
    """``(sound, control, run)``: each number of
    ``bench.reference.error_numbers`` as the program gives it and as the
    control gives it, for one seed's set-up ``state``. The control's
    second form, ``bf16_all``, also adds the spends in bfloat16."""
    import numpy as np
    from bench.reference import error_numbers
    if driver.__name__.endswith(".sweep"):
        result = state["engine"].sweep(state["grid"]).results
        program = np.asarray(result.final_spend)
        spends = lambda **kw: driver.reference_spend(state, **kw)
        budgets = np.asarray(state["grid"].budgets)
        run = None
    else:
        obs = driver.window(state, seconds)
        done = [k for k, a in enumerate(obs["answers"]) if a is not None]
        program = np.stack([obs["answers"][k].final_spend for k in done])
        spends = lambda **kw: driver.reference_spends(state, obs, **kw)[1]
        budgets = driver.ask_budgets(state, done)
        run = {"asks": len(done), "failed": obs["failed"],
               "p50_ms": obs["end_to_end"]["ask_p50_ms"],
               "p95_ms": obs["end_to_end"]["ask_p95_ms"]}
    ref = spends()
    sound = error_numbers(program, ref, budgets)
    control = error_numbers(spends(dtype="bfloat16"), ref, budgets)
    control_all = error_numbers(
        spends(dtype="bfloat16", spend_dtype="bfloat16"), ref, budgets)
    control["bf16_all"] = control_all
    return sound, control, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the service cell's window (default: run_seconds)")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import importlib
    from bench import harness
    from bench.spans import Spans
    if harness.device_info()["platform"] != "tpu":
        print("control: needs the chip", file=sys.stderr)
        return 2
    harness.enable_cache()
    spec, _, config, traffic, _ = harness.load_cell(ROOT, args.workload)
    seconds = args.seconds or float(spec["run_seconds"])
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        ctx = harness.Context(config=config, traffic=traffic,
                              seed=seed, key=harness.key_for(seed),
                              spans=Spans(), seconds=seconds)
        state = driver.setup(ctx)
        sound, control, run = readings(driver, state, seconds)
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "sound": sound, "control": control, "run": run,
                           "seconds": time.perf_counter() - t0})
        print(line, flush=True)
        del state
    return 0


if __name__ == "__main__":
    sys.exit(main())
