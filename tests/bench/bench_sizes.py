"""Tiny sizes at which the tests drive the benchmark's cells on the CPU.

The program takes no option for them: the tests pass them to the harness
as overrides of the cells' configuration, traffic and limits. At these
sizes Algorithm 2's error against the sequential replay is larger than at
the published ones (its bound grows as C/N), so every number the check
compares gets a limit that holds there. The asks arrive fast enough that
flushes batch several of them, as in the cell on the chip (about three
asks a flush at 80/s), so that a fault in one part of a batch can show."""

TINY_LIMITS = {"max_spend_err": 0.2, "max_uncapped_err": 0.2,
               "max_median_err": 0.2}

TINY = {
    "paper71.grid32": {"config": {"n_events": 8192, "n_campaigns": 16,
                                  "b_base": 6.0}},
    "paper71.single": {"config": {"n_events": 8192, "n_campaigns": 16,
                                  "b_base": 6.0}},
    "yahoo72.asks": {
        "config": {"n_day1": 4096, "n_day2": 6144, "n_campaigns": 32,
                   "n_keywords": 64, "budget": 2.0},
        "traffic": {"rate_per_s": 60.0, "events_per_chunk": 2048,
                    "max_batch": 8, "scenario_chunks": 4,
                    "reference_segment": 2048,
                    "reference_lane_block": 16}},
}


def tiny(cell: str) -> dict:
    out = {k: dict(v) for k, v in TINY[cell].items()}
    out["limits"] = dict(TINY_LIMITS)
    return out
