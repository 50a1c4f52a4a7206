"""Host spans around the calls the benchmark makes into each layer.

Each span is kept in memory as ``(name, start_s, end_s)`` on the host's
``perf_counter`` clock and is also written into the profiler's trace as a
``jax.profiler.TraceAnnotation``, so that a traced run can say what the
host was doing while the device sat idle."""
from __future__ import annotations

import contextlib
import time

import jax


class Spans:
    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.records.append((name, t0, time.perf_counter()))
