"""admit_us (service), read as ``admit_us.serve`` in the service cell: the
median time the service took to admit one ask (normalise its design,
fingerprint it, queue it), in microseconds, from the program's
``serve.admit`` spans (``bench/program_spans.py``)."""
from bench import program_spans


def read(run):
    median = program_spans.median_s(program_spans.window_records(),
                                    "serve.admit")
    return None if median is None else 1e6 * median
