"""round_ms (executor), read as ``round_ms.<part>`` in the sweep cells:
device busy time in the traced window over the rounds run in it (each
sweep runs as many rounds as its slowest lane)."""


def read(run):
    trace, obs = run["trace"], run["obs"]
    record = obs.get("round_record")
    if trace is None or record is None:
        return None
    rounds = obs["sweeps"] * int(record["num_rounds"].max())
    return 1e3 * trace["busy_s"] / rounds if rounds else None
