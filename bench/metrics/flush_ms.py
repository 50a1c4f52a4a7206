"""flush_ms (service), read as ``flush_ms.serve`` in the service cell: the
median host-clock time of the window's ``flush()`` calls, each with its
replay and the answers' copy to the host."""
import numpy as np


def read(run):
    flush_s = run["obs"].get("flush_s")
    if not flush_s:
        return None
    return 1e3 * float(np.median(flush_s))
