"""dispatch_ms (executor), read as ``dispatch_ms.serve`` in the service
cell: the median host time of one ``execute_sweep`` call, from entry until
its program is enqueued (plan resolution and dispatch), in milliseconds,
from the program's ``executor.sweep`` spans
(``bench/program_spans.py``)."""
from bench import program_spans


def read(run):
    median = program_spans.median_s(program_spans.window_records(),
                                    "executor.sweep")
    return None if median is None else 1e3 * median
