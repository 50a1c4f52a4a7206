"""device_idle_pct (device), read as ``device_idle_pct.<part>`` in each cell:
the share of the traced window in which no operation ran on the device, in
percent: 1 - (union of the device operations' intervals) / window."""


def read(run):
    trace = run["trace"]
    if trace is None or trace["window_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
