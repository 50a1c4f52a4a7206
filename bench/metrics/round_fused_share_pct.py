"""round_fused_share_pct (kernels), read as ``round_fused_share_pct.<part>``
in the sweep cells: the fused round kernel's device time over device busy
time in the traced window, in percent. The rest is the log's relayout and
XLA glue. The kernel's trace name is the HLO custom call
``round_fused.<n>``, as in ``round_fused_roofline``."""

KERNELS = {"round_fused": ("round_fused",)}


def read(run):
    trace = run["trace"]
    if trace is None or trace["busy_s"] <= 0.0:
        return None
    kernel_s = trace["kernel_s"].get("round_fused", 0.0)
    if kernel_s <= 0.0:
        return None
    return 100.0 * kernel_s / trace["busy_s"]
