"""The benchmark harness, driven by data.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
harness finds everything else by name:

* the configuration's file (``configs`` entry ``file``), whose
  ``generator`` key names the module of ``bench/gen/`` that makes its
  inputs from the seed;
* ``bench/traffic/<traffic>.json``, the mix's parameters, whose ``driver``
  key names the module of ``bench/drivers/`` that runs it;
* ``bench/limits/<cell>.json``, the limits of the numbers the cell's
  correctness check compares;
* ``bench/metrics/<metric>.py`` for each per-layer metric, a reader with
  ``read(run) -> float | None``.

A metric split by cells, ``<quantity>.<part>`` (``rounds.batch`` in the
grid cell, ``rounds.single`` in the single-design one, each moving its
cell's own end-to-end metric), is read as its quantity where it has no
file or value of its own: the reader ``bench/metrics/<quantity>.py``, and
the driver's end-to-end value ``<quantity>``.

A driver module has ``setup(ctx) -> state``, ``window(state, seconds) ->
obs`` (the measured window), ``finish(state, obs)`` (reads after the
window that compile nothing new) and ``check(state, obs, limits) ->
[(name, value, limit)]``, which frees the program's state and compares
with the plain reference; a number passes when it is at most its limit.
Besides, nothing may compile inside the window (``window_compiles``,
limit 0): a run whose window compiles, or loads a program from the
persistent cache, measures the compiler, and is not correct.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import time
from typing import Any, Optional

import jax
from bench.spans import Spans
from bench.trace import find_xplane, reduce_trace


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    config: dict
    traffic: dict
    seed: int
    key: Any
    spans: Spans
    seconds: float


def key_for(seed: int):
    """A PRNG key from any whole-number seed, all of its bits kept."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(spec: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def cell_metrics(spec: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    def listed(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m for m in spec["end_to_end"] if listed(m)]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]


def quantity(name: str) -> str:
    """The quantity a metric split by cells reads: ``rounds.single`` ->
    ``rounds``."""
    return name.split(".", 1)[0]


def end_to_end_value(values: dict, name: str) -> float:
    """A driver's end-to-end value for the metric ``name``."""
    return values[name] if name in values else values[quantity(name)]


def load_reader(root: str, metric: str):
    """The module of ``bench/metrics/<metric>.py``, else of
    ``bench/metrics/<quantity>.py``: ``read(run)``, and ``KERNELS``
    ({kernel: trace names}) where it reads a kernel's time."""
    folder = os.path.join(root, "bench", "metrics")
    path = os.path.join(folder, f"{metric}.py")
    if not os.path.exists(path):
        path = os.path.join(folder, f"{quantity(metric)}.py")
    name = "bench_metric_" + "".join(c if c.isalnum() else "_"
                                     for c in metric)
    module_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def device_info() -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(n_chips: int) -> int:
    peaks = []
    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


class CompileCounter:
    """Counts, while it is open, the process's XLA compilations: the
    backend compiles, each either a compilation or a load from the
    persistent cache (``loads``), by the name of the function compiled."""

    def __init__(self):
        self.names: dict[str, int] = {}
        self.loads = 0

    @property
    def count(self) -> int:
        return sum(self.names.values())

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            name = str(kwargs.get("fun_name", "?"))
            self.names[name] = self.names.get(name, 0) + 1

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.loads += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def __str__(self) -> str:
        names = "".join(f", {n} x{k}" for n, k in sorted(self.names.items()))
        return f"{self.count} ({self.loads} from the cache{names})"


def load_cell(root: str, workload: str, overrides: Optional[dict] = None):
    """``(spec, cell, config, traffic, limits)`` of a cell, found by name;
    ``overrides`` replaces keys of the last three."""
    overrides = overrides or {}
    spec = load_spec(root)
    cell, config_entry = find_cell(spec, workload)
    config = load_json(os.path.join(root, config_entry["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{cell['traffic']}.json"))
    limits = load_json(os.path.join(root, "bench", "limits",
                                    f"{workload}.json"))
    for part, value in (("config", config), ("traffic", traffic),
                        ("limits", limits)):
        value.update(overrides.get(part, {}))
    return spec, cell, config, traffic, limits


def enable_cache() -> str:
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    # keep every program, small ones too, so that a second run compiles
    # nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_chip: bool = True,
             overrides: Optional[dict] = None, log=print) -> dict:
    """Run one cell; returns the result object of the last output line.

    ``overrides`` (tests only: the program takes no option for it)
    replaces keys of the cell's ``config``, ``traffic`` or ``limits``."""
    spec, cell, config, traffic, limits = load_cell(root, workload,
                                                    overrides)
    device = device_info()
    if require_chip:
        if device["platform"] != "tpu":
            raise NoChip(f"JAX's default backend is {device['platform']!r} "
                         f"({device['kind']}), not a TPU; the benchmark "
                         "measures only on the chip")
        if device["count"] < cell["chips"]:
            raise NoChip(f"the cell asks for {cell['chips']} chips, JAX "
                         f"sees {device['count']}")
        enable_cache()
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    spans = Spans()
    ctx = Context(config=config, traffic=traffic, seed=int(seed),
                  key=key_for(seed), spans=spans, seconds=float(seconds))
    state = driver.setup(ctx)

    trace_dir = os.path.join(root, ".bench_trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    t_window = time.perf_counter()
    with CompileCounter() as compiles:
        obs = driver.window(state, float(seconds))
    summary = None
    readers = {}
    if trace:
        jax.profiler.stop_trace()
        readers = {m["name"]: load_reader(root, m["name"])
                   for m in cell_metrics(spec, workload, "per_layer")}
        kernels = {}
        for module in readers.values():
            kernels.update(getattr(module, "KERNELS", {}))
        summary = reduce_trace(find_xplane(trace_dir), kernels=kernels,
                               span_names={n for n, _, _ in spans.records})
        shutil.rmtree(trace_dir, ignore_errors=True)
    driver.finish(state, obs)
    memory = memory_peak_bytes(cell["chips"])
    checks = driver.check(state, obs, limits) + [
        ("window_compiles", float(compiles.count), 0.0)]
    correct = bool(all(v <= lim for _, v, lim in checks)) \
        and obs["failed"] == 0

    metrics = {}
    if not trace:
        for m in cell_metrics(spec, workload, "end_to_end"):
            value = (t_window - t_start if m["name"] == "setup_s"
                     else end_to_end_value(obs["end_to_end"], m["name"]))
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        run = {"obs": obs, "trace": summary, "cell": cell, "config": config,
               "device": device}
        for m in cell_metrics(spec, workload, "per_layer"):
            value = readers[m["name"]].read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    result = {
        "correct": correct,
        "attempted": int(obs["attempted"]),
        "failed": int(obs["failed"]),
        "metrics": metrics,
        "device": dict(device, memory_peak_bytes=memory),
    }
    if summary is not None:
        result["device"].update(busy_s=summary["busy_s"],
                                window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    phases = ", ".join(f"{n} {t1 - t0:.3f} s" for n, t0, t1 in spans.records
                       if n.startswith("setup."))
    log(f"setup_s {t_window - t_start!r} ({phases}); compilations in the "
        f"window {compiles}; {obs.get('note', '')}")
    result["checks"] = {name: {"value": float(v), "limit": float(lim)}
                        for name, v, lim in checks}
    return result
