"""round_fused_roofline (kernels), read as ``round_fused_roofline.<part>`` in
the sweep cells: the fused round kernel's share of its roofline, in
percent: the least time of the window's sweeps (their log rows at HBM
bandwidth, ``bench/roofline.py``) over the kernel's device time in the
trace.

The kernel's name in the trace: the HLO custom call ``round_fused.<n>``
(read from a v5e trace). The bound is HBM bandwidth alone: the round is
VPU work, whose v5e rate is not published. Silent (no value) where the
trace holds no such kernel."""
from bench.peaks import peaks_for
from bench.roofline import sweep_log_bytes

KERNELS = {"round_fused": ("round_fused",)}


def read(run):
    trace, obs = run["trace"], run["obs"]
    record = obs.get("round_record")
    if trace is None or record is None:
        return None
    kernel_s = trace["kernel_s"].get("round_fused", 0.0)
    if kernel_s <= 0.0:
        return None
    least = obs["sweeps"] * sweep_log_bytes(
        record["num_rounds"], record["boundaries"], obs["n_events"],
        obs["n_campaigns"]) / peaks_for(run["device"]["kind"])[
            "hbm_bytes_per_s"]
    return 100.0 * least / kernel_s
