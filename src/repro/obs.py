"""In-program spans, on exactly while a JAX profiler session runs.

An operator turns them on the way a device trace is captured:
``jax.profiler.start_trace(log_dir)`` ... ``jax.profiler.stop_trace()``, or
a capture taken from a running ``jax.profiler.start_server(port)``. While a
session runs, each :func:`span` is

* written into the trace as a ``jax.profiler.TraceAnnotation`` with its
  attributes, on the profiler's clock, beside the device's operations;
* kept in memory as a :class:`Record` on ``time.perf_counter``, with the
  span that encloses it on the same thread as its parent.

:func:`records` returns the newest session's spans: the recorder starts a
fresh buffer at the first span it sees after a time with no session. The
buffer holds at most :data:`MAX_RECORDS`; :func:`dropped` counts what the
bound turned away. Compiles inside a span are recorded under it as
``jax.compile`` spans (attribute ``fun_name``), in memory only, since the
trace shows compiles itself.

While no session runs, :func:`span` returns a shared no-op after one check
and records nothing. Spans never touch the device: their attributes are
host ints and strings the caller already holds. The always-on counters of
the service are ``CounterfactualService.stats``; spans add timing and per
call counts to them, not a second set of totals.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax

MAX_RECORDS = 1 << 16
COMPILE_SPAN = "jax.compile"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_profiling = jax.profiler.TraceAnnotation.is_enabled


class Record(NamedTuple):
    """One finished span: ``t0``/``t1`` in ``time.perf_counter`` seconds;
    ``parent`` is the id of the enclosing span, ``None`` at the top."""

    id: int
    parent: Optional[int]
    name: str
    t0: float
    t1: float
    attrs: dict


class _Off:
    """The span while no profiler session runs."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


class _Recorder:
    """The span buffer: one a process, as the profiler is."""

    def __init__(self, limit: int = MAX_RECORDS):
        self.limit = limit
        self.records: list[Record] = []
        self.dropped = 0
        self.in_session = False
        self.ids = itertools.count(1)
        self._local = threading.local()
        self._listening = False

    def stack(self) -> list[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def begin(self) -> None:
        self.records, self.dropped, self.in_session = [], 0, True
        if not self._listening:
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            self._listening = True

    def add(self, record: Record) -> None:
        if len(self.records) < self.limit:
            self.records.append(record)
        else:
            self.dropped += 1

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event != _COMPILE_EVENT or not _profiling():
            return
        if not self.in_session:
            self.begin()
        stack = self.stack()
        t1 = time.perf_counter()
        self.add(Record(next(self.ids), stack[-1] if stack else None,
                        COMPILE_SPAN, t1 - duration, t1,
                        {"fun_name": str(kwargs.get("fun_name", "?"))}))


_RECORDER = _Recorder()


class _Span:
    """A span while a profiler session runs; ``set`` adds attributes
    learned inside it to its in-memory record."""

    __slots__ = ("name", "attrs", "id", "parent", "t0", "_annotation")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self):
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self.attrs)
        self._annotation.__enter__()
        stack = _RECORDER.stack()
        self.parent = stack[-1] if stack else None
        self.id = next(_RECORDER.ids)
        stack.append(self.id)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        _RECORDER.stack().pop()
        self._annotation.__exit__(*exc)
        _RECORDER.add(Record(self.id, self.parent, self.name, self.t0, t1,
                             self.attrs))
        return None


def span(name: str, **attrs):
    """A context manager timing the block it encloses, as ``name`` with
    ``attrs``, while a profiler session runs; it yields a handle whose
    ``set(**attrs)`` adds attributes learned inside the block."""
    if not _profiling():
        _RECORDER.in_session = False
        return _OFF
    if not _RECORDER.in_session:
        _RECORDER.begin()
    return _Span(name, attrs)


def records() -> list[Record]:
    """The finished spans of the newest profiler session, in the order
    they ended."""
    return list(_RECORDER.records)


def dropped() -> int:
    """Spans of the newest session lost to the bound of the buffer."""
    return _RECORDER.dropped


def clear() -> None:
    """Empty the buffer; the next span in a session starts a fresh one."""
    _RECORDER.records, _RECORDER.dropped = [], 0
    _RECORDER.in_session = False
