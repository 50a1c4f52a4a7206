"""collective_share_pct (mesh), read as ``collective_share_pct.x4`` in the
four-chip cell: the own device time of the collectives over device busy
time in the traced window, per chip, in percent: the two psums a round of
the sharded sweep, each an all-reduce of (S, 32, C) float32 partials.

XLA names an all-reduce instruction after the JAX primitive that made it:
``psum.<n>`` (compiled for a v5e 2x2, and so in a device trace); the
opcode names cover instructions that carry them. Silent where the trace
holds no collective, as on one chip."""

KERNELS = {"collectives": ("psum", "all-reduce", "all-reduce-start",
                           "all-reduce-done", "all-gather",
                           "all-gather-start", "all-gather-done")}


def read(run):
    trace = run["trace"]
    if trace is None or trace["busy_s"] <= 0.0:
        return None
    collective_s = trace["kernel_s"].get("collectives", 0.0)
    if collective_s <= 0.0:
        return None
    return 100.0 * collective_s / trace["busy_s"]
