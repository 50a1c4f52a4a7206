"""The readers of the program's own spans (``bench/program_spans.py``):
each on hand-built records, silent where there is nothing sound to read,
and all four on a tiny traced window of the service cell on the CPU."""
import os
import sys
import time

import jax
import pytest

from bench import harness
from bench_sizes import tiny
from repro import obs

ROOT = harness.os.path.dirname(harness.os.path.dirname(
    harness.os.path.abspath(harness.__file__)))
SPEC = harness.load_spec(ROOT)
SPAN_METRICS = ["admit_us.serve", "flush_host_ms.serve",
                "lanes_useful_pct.serve", "dispatch_ms.serve"]


def _rec(id_, parent, name, t0, t1, **attrs):
    return obs.Record(id_, parent, name, t0, t1, attrs)


# two asks and one flush of a batch of 3 lanes padded to 8, then a flush of
# 8 lanes padded to 8: host time 10 - 4 = 6 ms and 20 - 12 = 8 ms
RECORDS = [
    _rec(1, None, "serve.admit", 0.0, 0.0004, seq=0),
    _rec(2, None, "serve.admit", 0.001, 0.0016, seq=1),
    _rec(6, 5, "executor.sweep", 0.003, 0.0032, placement="batched",
         lanes=8, events=100),
    _rec(7, 5, "serve.fetch", 0.004, 0.008),
    _rec(5, 4, "serve.replay", 0.002, 0.009, lanes=3, padded_to=8,
         events=100),
    _rec(4, None, "serve.flush", 0.0, 0.010, first_seq=0, tickets=2,
         hits=0, misses=2),
    _rec(10, 9, "executor.sweep", 0.102, 0.1026, placement="batched",
         lanes=8, events=100),
    _rec(11, 9, "serve.fetch", 0.103, 0.115),
    _rec(9, 8, "serve.replay", 0.101, 0.116, lanes=8, padded_to=8,
         events=100),
    _rec(8, None, "serve.flush", 0.100, 0.120, first_seq=2, tickets=8,
         hits=0, misses=8),
]


def _read(name):
    return harness.load_reader(ROOT, name).read({"obs": {}, "trace": None})


@pytest.fixture
def recorded(monkeypatch):
    def use(records, dropped=0):
        monkeypatch.setattr(obs, "records", lambda: list(records))
        monkeypatch.setattr(obs, "dropped", lambda: dropped)
    return use


def test_readers_on_hand_built_records(recorded):
    recorded(RECORDS)
    assert _read("admit_us.serve") == pytest.approx(500.0)
    assert _read("flush_host_ms.serve") == pytest.approx(7.0)
    assert _read("lanes_useful_pct.serve") == pytest.approx(
        100.0 * 11 / 16)
    assert _read("dispatch_ms.serve") == pytest.approx(0.4)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_readers_are_silent_with_nothing_sound_to_read(recorded,
                                                       monkeypatch, name):
    recorded([])                                 # no span in the window
    assert _read(name) is None
    recorded(RECORDS, dropped=1)                 # the bound dropped spans
    assert _read(name) is None
    recorded(RECORDS)
    import repro                                 # a program without obs
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _read(name) is None


def test_the_service_cell_lists_the_span_metrics():
    names = {m["name"] for m in harness.cell_metrics(SPEC, "yahoo72.asks",
                                                     "per_layer")}
    assert set(SPAN_METRICS) <= names
    for cell in ("paper71.grid32", "paper71.single"):
        names = {m["name"] for m in harness.cell_metrics(SPEC, cell,
                                                         "per_layer")}
        assert not set(SPAN_METRICS) & names


def test_readers_read_a_traced_service_window(tmp_path):
    """A tiny window of the service cell under a profiler session, as a
    ``--trace 1`` run takes it: every reader finds its spans."""
    from bench.drivers import service
    _, _, config, traffic, _ = harness.load_cell(ROOT, "yahoo72.asks",
                                                 tiny("yahoo72.asks"))
    seed = 2 ** 33 + 11
    ctx = harness.Context(config=config, traffic=traffic, seed=seed,
                          key=harness.key_for(seed), spans=harness.Spans(),
                          seconds=1.0)
    state = service.setup(ctx)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    t0 = time.perf_counter()
    try:
        window = service.window(state, 1.0)
    finally:
        jax.profiler.stop_trace()
    elapsed = time.perf_counter() - t0
    service.finish(state, window)
    values = {name: _read(name) for name in SPAN_METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert obs.dropped() == 0
    assert values["lanes_useful_pct.serve"] <= 100.0
    flushes = [r for r in obs.records() if r.name == "serve.flush"]
    assert len(flushes) == len(window["flush_s"])
    admits = [r for r in obs.records() if r.name == "serve.admit"]
    assert len(admits) == window["attempted"]
    assert sum(r.t1 - r.t0 for r in admits) < elapsed
    assert os.listdir(tmp_path / "trace")
