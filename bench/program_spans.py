"""The program's own spans (``repro.obs``) of a traced run's window.

The program records spans while a profiler session runs, and the harness
runs set-up and the reads after the window with the profiler off, so the
newest session's spans are exactly the traced window's. There is nothing
to read, and a reader gives no value, where the program has no
``repro.obs`` (a commit before it), where the recorder dropped spans to
its bound, or where the window holds no span of the name asked for."""
from __future__ import annotations

import statistics


def window_records():
    """The window's span records, or ``None`` where there is nothing
    sound to read."""
    try:
        from repro import obs
    except ImportError:
        return None
    if obs.dropped():
        return None
    return obs.records()


def named(records, name: str) -> list:
    return [r for r in records or () if r.name == name]


def duration_s(record) -> float:
    return record.t1 - record.t0


def median_s(records, name: str):
    """The median duration of the spans named ``name``, in seconds."""
    spans = named(records, name)
    if not spans:
        return None
    return statistics.median(duration_s(r) for r in spans)


def descendants(records, root_id: int, name: str) -> list:
    """The spans named ``name`` nested, at any depth, under ``root_id``."""
    children: dict = {}
    for r in records:
        children.setdefault(r.parent, []).append(r)
    found, todo = [], [root_id]
    while todo:
        for r in children.get(todo.pop(), ()):
            if r.name == name:
                found.append(r)
            todo.append(r.id)
    return found
