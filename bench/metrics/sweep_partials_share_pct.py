"""sweep_partials_share_pct (kernels), read as
``sweep_partials_share_pct.x4`` in the four-chip cell: the two-pass
round's kernel ``sweep_partials``, its device time over device busy time
in the traced window, per chip, in percent. The rest is the collectives,
the log's layout and XLA glue. Silent where the trace holds no such
kernel (see ``sweep_partials_roofline``)."""

KERNELS = {"sweep_partials": ("sweep_partials",)}


def read(run):
    trace = run["trace"]
    if trace is None or trace["busy_s"] <= 0.0:
        return None
    kernel_s = trace["kernel_s"].get("sweep_partials", 0.0)
    if kernel_s <= 0.0:
        return None
    return 100.0 * kernel_s / trace["busy_s"]
