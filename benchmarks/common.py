"""Shared benchmark utilities: timing, CSV emission, arg parsing, JSON perf
records.

Every benchmark prints ``name,us_per_call,derived`` rows so
``python -m benchmarks.run`` produces one machine-readable report covering
each paper figure/table. The sweep benchmarks additionally merge a JSON
section into ``BENCH_sweep.json`` (one file, one section per benchmark, each
tagged with ``device_count``) so the perf trajectory across commits
distinguishes 1- from multi-device runs.

NOTE: importing this module (or any ``repro.*`` module) does NOT
initialise the jax backend, so :func:`force_host_devices` can still grow the
fake-CPU device count — but it must be called before any ``jax.devices()``
/ first computation. Each benchmark's ``__main__`` block turns on the
persistent compilation cache
(:func:`repro.launch.compile_cache.enable_compile_cache`).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time
from pathlib import Path
from typing import Callable, Optional, Sequence

import jax


def time_call(fn: Callable, *args, repeats: int = 3, warmup: int = 1):
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
        jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / repeats
    return out, dt * 1e6      # us


def time_pair(fn_a: Callable, fn_b: Callable, repeats: int = 15,
              warmup: int = 2):
    """Median times (us) of two callables measured INTERLEAVED (a, b, a, b,
    …): background load drift hits both alike, so the comparison is stable
    where two sequential :func:`time_call` windows can disagree by 2× on a
    shared machine. Use for CI-gated A/B comparisons."""
    for _ in range(warmup):
        jax.block_until_ready(fn_a())
        jax.block_until_ready(fn_b())
    ta, tb = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        tb.append(time.perf_counter() - t0)
    med = lambda ts: sorted(ts)[len(ts) // 2] * 1e6
    return med(ta), med(tb)


def emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.1f},{derived}")


def sweep_argparser(
    description: str,
    *,
    n_events: int,
    n_campaigns: int,
    s_values: Optional[Sequence[int]] = None,
    block_t: Optional[int] = None,
    out: Optional[str] = None,
    device_count: bool = False,
) -> argparse.ArgumentParser:
    """The sweep benchmarks' shared CLI: problem sizes, scenario schedule,
    output path, and (optionally) a forced host-platform device count."""
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--n-events", type=int, default=n_events)
    ap.add_argument("--n-campaigns", type=int, default=n_campaigns)
    if s_values is not None:
        ap.add_argument("--s-values", type=int, nargs="+",
                        default=list(s_values))
    if block_t is not None:
        ap.add_argument("--block-t", type=int, default=block_t)
    if out is not None:
        ap.add_argument("--out", default=out)
    if device_count:
        ap.add_argument(
            "--device-count", type=int, default=0,
            help="force this many fake CPU devices (XLA host platform); "
                 "0 = whatever is already visible. Must take effect before "
                 "jax initialises, so the benchmark imports repro lazily.")
    return ap


def force_host_devices(n: int) -> None:
    """Grow the CPU platform to ``n`` fake devices (no-op for n <= 1).

    Appends ``--xla_force_host_platform_device_count`` to ``XLA_FLAGS``;
    call before the first jax computation or it silently does nothing.
    """
    if n and n > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = \
            f"{flags} --xla_force_host_platform_device_count={n}".strip()


def bench_report(records: list, **extra) -> dict:
    """A JSON perf section: environment fingerprint + device_count + rows."""
    report = {
        "backend": jax.default_backend(),
        "device_count": len(jax.devices()),
        "jax_version": jax.__version__,
        "machine": platform.machine(),
        **extra,
        "results": records,
    }
    return report


def update_bench_json(path: str, section: str, payload: dict) -> None:
    """Merge ``{section: payload}`` into the JSON report at ``path``.

    Benchmarks own one section each, so re-runs replace their own numbers
    without clobbering the other benchmarks' (e.g. ``sweep_scaling`` appends
    its device_count-tagged rows next to ``sweep_kernel``'s).
    """
    p = Path(path)
    data = {}
    if p.exists():
        try:
            data = json.loads(p.read_text())
        except json.JSONDecodeError:
            data = {}
    if not isinstance(data, dict):
        data = {}
    elif "results" in data:
        # legacy single-benchmark layout: demote it to its own section
        data = {data.get("benchmark", "legacy"): data}
    data[section] = payload
    p.write_text(json.dumps(data, indent=2) + "\n")
    print(f"wrote {path} [{section}]")
