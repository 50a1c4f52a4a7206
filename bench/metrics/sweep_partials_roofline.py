"""sweep_partials_roofline (kernels), read as ``sweep_partials_roofline.x4``
in the four-chip cell: the two-pass round's kernel ``sweep_partials``, its
share of its roofline, in percent: the least time of the window's sweeps
(their log rows at HBM bandwidth, ``bench/roofline.py``, the same count
whatever implements the round), spread over the chips the log is sharded
over, against the kernel's device time per chip in the trace
(``bench/trace.py`` averages it over the device planes).

The kernel's name in the trace: the HLO custom call
``sweep_partials.<n>``, two a round (the rate pass and the block pass).
Silent (no value) where the trace holds no such kernel: the one-launch
round of one chip runs ``round_fused`` instead."""
from bench.peaks import peaks_for
from bench.roofline import sweep_log_bytes

KERNELS = {"sweep_partials": ("sweep_partials",)}


def read(run):
    trace, obs = run["trace"], run["obs"]
    record = obs.get("round_record")
    if trace is None or record is None:
        return None
    kernel_s = trace["kernel_s"].get("sweep_partials", 0.0)
    if kernel_s <= 0.0:
        return None
    hbm = obs.get("shards", 1) * peaks_for(run["device"]["kind"])[
        "hbm_bytes_per_s"]
    least = obs["sweeps"] * sweep_log_bytes(
        record["num_rounds"], record["boundaries"], obs["n_events"],
        obs["n_campaigns"]) / hbm
    return 100.0 * least / kernel_s
