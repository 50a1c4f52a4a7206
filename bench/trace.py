"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Reads the trace with ``jax.profiler.ProfileData`` alone:

* device planes are the planes named ``/device:TPU:<i>``; their ``XLA Ops``
  line holds one event per operation run on the device, named by its HLO
  text (``%round_fused.6 = (...) custom-call(...)``); an operation is
  known by its instruction name (``round_fused.6``), and a control-flow
  operation (``while.2``) spans the operations of its body, so each
  operation's own time excludes the operations nested in it;
* the window is the host span named ``bench.window``; everything is
  clipped to it;
* busy time is the union of the operation intervals inside the window,
  averaged over the device planes (the chips used);
* a kernel's time is the summed time of the operations whose instruction
  name is one of its trace names, with or without a ``.<n>`` suffix;
* each idle gap of the device inside the window is split among the host
  spans other than the window that overlap it (the benchmark's spans do
  not nest), and what none covers goes to ``(no span)``.

The device's clock and the host's are aligned by the profiler to within
about half a millisecond (on a v5e the device's first operation of a sweep
read 0.44 ms before the host span that launched it), which is below the
resolution the gap attribution is read at.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Iterable

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
NO_SPAN = "(no span)"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):] \
        .isdigit()


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def op_name(hlo_text: str) -> str:
    """``%round_fused.6 = (...) custom-call(...)`` -> ``round_fused.6``."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def _is_kernel(op: str, names) -> bool:
    return any(op == n or (op.startswith(n + ".")
                           and op[len(n) + 1:].isdigit()) for n in names)


def _own_times(events) -> list[tuple[str, float, float, float]]:
    """``(op, start, end, own seconds)`` per event: its duration less the
    events nested inside it on the same line."""
    out = []
    stack: list[list] = []          # [end, index into out]
    for op, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            out[stack[-1][1]][3] -= min(b, stack[-1][0]) - a
        out.append([op, a, b, b - a])
        stack.append([b, len(out) - 1])
    return [tuple(x) for x in out]


def reduce_trace(path: str, *, kernels: dict[str, Iterable[str]] = (),
                 span_names: Iterable[str] | None = None,
                 top: int = 10) -> dict:
    """Returns ``{"busy_s", "window_s", "devices", "kernel_s": {kernel:
    seconds}, "device_ops": [[name, seconds]], "idle_gaps": [[span,
    seconds]]}``. ``kernels`` maps a kernel to the names it has in the
    trace; ``span_names`` limits the host spans that idle gaps go to
    (``None``: every host event). Raises ``ValueError`` where the trace has no window span or no
    device plane."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = list(data.planes)
    host_spans: list[tuple[str, float, float]] = []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                host_spans.append((ev.name, ev.start_ns * 1e-9,
                                   (ev.start_ns + ev.duration_ns) * 1e-9))
    windows = [(a, b) for n, a, b in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace {path} has no {WINDOW_SPAN!r} span")
    w0, w1 = min(a for a, _ in windows), max(b for _, b in windows)
    kernels = {k: tuple(v) for k, v in dict(kernels).items()}

    busy_per_device, kernel_s, op_s = [], {k: 0.0 for k in kernels}, {}
    busy_union: list[tuple[float, float]] = []
    n_devices = 0
    for plane in planes:
        if not _is_device_plane(plane.name):
            continue
        ops = [line for line in plane.lines if line.name == OPS_LINE]
        if not ops:
            continue
        n_devices += 1
        events = []
        for ev in ops[0].events:
            a = ev.start_ns * 1e-9
            b = a + ev.duration_ns * 1e-9
            a, b = max(a, w0), min(b, w1)
            if b > a:
                events.append((op_name(ev.name), a, b))
        for op, _, _, own in _own_times(events):
            op_s[op] = op_s.get(op, 0.0) + own
            for k, names in kernels.items():
                if _is_kernel(op, names):
                    kernel_s[k] += own
        merged = _union([(a, b) for _, a, b in events])
        busy_per_device.append(sum(b - a for a, b in merged))
        if n_devices == 1:
            busy_union = merged
    if not n_devices:
        raise ValueError(f"trace {path} has no device plane with an "
                         f"{OPS_LINE!r} line")

    gaps, prev = [], w0
    for a, b in busy_union:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        gaps.append((prev, w1))
    names = None if span_names is None else set(span_names)
    spans = sorted((a, b, n) for n, a, b in host_spans
                   if n != WINDOW_SPAN and b > w0 and a < w1
                   and (names is None or n in names))
    starts = [a for a, _, _ in spans]
    longest = max((b - a for a, b, _ in spans), default=0.0)
    by_span: dict[str, float] = {}
    for g0, g1 in gaps:
        covered = 0.0
        i = bisect.bisect_right(starts, g1) - 1
        while i >= 0 and starts[i] >= g0 - longest:
            a, b, n = spans[i]
            overlap = min(b, g1) - max(a, g0)
            if overlap > 0.0:
                by_span[n] = by_span.get(n, 0.0) + overlap
                covered += overlap
            i -= 1
        if g1 - g0 > covered:
            by_span[NO_SPAN] = by_span.get(NO_SPAN, 0.0) + (g1 - g0 - covered)
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy_per_device) / n_devices,
        "window_s": w1 - w0,
        "devices": n_devices,
        "kernel_s": {k: v / n_devices for k, v in kernel_s.items()},
        "device_ops": [[n, s / n_devices] for n, s in top_ops],
        "idle_gaps": [[n, s] for n, s in top_gaps],
    }
