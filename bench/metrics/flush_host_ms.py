"""flush_host_ms (service), read as ``flush_host_ms.serve`` in the service
cell: the median over the window's flushes of a flush's host time, in
milliseconds: its ``serve.flush`` span less the ``serve.fetch`` spans
inside it, where the host waits on the device for the answers and copies
them. What remains is host work during which the device has nothing
queued (``bench/program_spans.py``)."""
import statistics

from bench import program_spans


def read(run):
    records = program_spans.window_records()
    flushes = program_spans.named(records, "serve.flush")
    if not flushes:
        return None
    host = [program_spans.duration_s(f) - sum(
        program_spans.duration_s(r)
        for r in program_spans.descendants(records, f.id, "serve.fetch"))
        for f in flushes]
    return 1e3 * statistics.median(host)
