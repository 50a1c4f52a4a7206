"""In-program spans (``repro.obs``): off without a profiler session, the
service and executor span tree under one, answers unchanged by tracing."""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import AuctionRule, CounterfactualEngine
from repro.data import make_synthetic_env
from repro.serve import CounterfactualService

_N, _C, _EPC = 512, 8, 128
SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
# the benchmark's own host spans (bench/): a program span of one of these
# names would be counted twice where the benchmark splits idle gaps
BENCH_SPANS = {"bench.window", "engine.sweep", "block_until_ready",
               "svc.ask", "svc.flush", "svc.append", "svc.answer",
               "driver.idle"}


@pytest.fixture(scope="module")
def env():
    return make_synthetic_env(jax.random.PRNGKey(4), n_events=_N,
                              n_campaigns=_C, emb_dim=6)


@pytest.fixture
def traced(tmp_path):
    """A profiler session around the test's body."""
    obs.clear()
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _service_run(env, **kwargs):
    """Register, ask (one duplicate), flush, append (a fold), ask again,
    and a one-shot engine sweep: every answer, host arrays."""
    base = AuctionRule.first_price(_C)
    svc = CounterfactualService(env.budgets, events=env.values[:256],
                                events_per_chunk=_EPC, **kwargs)
    svc.register("base")
    tickets = [svc.ask(base), svc.ask(base.with_multiplier(2, 1.5)),
               svc.ask(base)]
    svc.flush()
    svc.append(env.values[256:])
    tickets.append(svc.ask(base, env.budgets * 0.5))
    answers = [t.result() for t in tickets]
    engine = CounterfactualEngine(env.values, env.budgets)
    swept = engine.sweep(engine.grid(bid_scales=(1.0, 1.25)))
    out = [np.asarray(a.final_spend) for a in answers]
    out += [np.asarray(a.cap_times) for a in answers]
    out += [np.asarray(svc.streaming("base").final_spend),
            np.asarray(swept.results.final_spend)]
    return out


def _by_name(records, name):
    return [r for r in records if r.name == name]


def _children(records, parent):
    return [r.name for r in sorted(records, key=lambda r: r.t0)
            if r.parent == parent.id]


def test_spans_are_off_without_a_profiler_session(env):
    obs.clear()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    _service_run(env, max_batch=8, scenario_chunks=4)
    with obs.span("anything", seq=1) as span:
        span.set(hits=2)
    assert obs.records() == [] and obs.dropped() == 0


def test_service_span_tree_under_a_profiler_session(env, tmp_path):
    obs.clear()
    untraced = _service_run(env, max_batch=8, scenario_chunks=4)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        traced = _service_run(env, max_batch=8, scenario_chunks=4)
    finally:
        jax.profiler.stop_trace()
    for a, b in zip(untraced, traced):
        np.testing.assert_array_equal(a, b)     # bitwise
    records = obs.records()
    assert obs.dropped() == 0
    assert all(r.t0 <= r.t1 for r in records)

    admits = _by_name(records, "serve.admit")
    assert [r.attrs["seq"] for r in admits] == [0, 1, 2, 3]
    assert all(r.parent is None for r in admits)

    first, second = _by_name(records, "serve.flush")
    assert first.attrs == {"first_seq": 0, "tickets": 3, "hits": 1,
                           "misses": 2}
    assert second.attrs == {"first_seq": 3, "tickets": 1, "hits": 0,
                            "misses": 1}
    assert _children(records, first) == ["serve.stack", "serve.replay",
                                         "serve.route"]
    stack = _by_name(records, "serve.stack")[0]
    assert stack.attrs == {"lanes": 2}
    replays = _by_name(records, "serve.replay")
    assert [(r.attrs["lanes"], r.attrs["padded_to"], r.attrs["events"])
            for r in replays] == [(2, 4, 256), (1, 4, _N)]
    # day 1 is one slab (no concatenation); after the append it is two
    assert _children(records, replays[0]) == ["serve.pad", "executor.sweep",
                                              "serve.fetch"]
    assert _children(records, replays[1]) == ["serve.pad", "serve.concat",
                                              "executor.sweep",
                                              "serve.fetch"]
    assert _by_name(records, "serve.concat")[0].attrs == {"rows": _N}
    sweeps = _by_name(records, "executor.sweep")
    assert [(s.attrs["lanes"], s.attrs["events"]) for s in sweeps] == [
        (4, 256), (4, _N), (2, _N)]
    assert sweeps[-1].parent is None and \
        sweeps[-1].attrs["placement"] == "batched"

    appends = _by_name(records, "serve.append")      # day 1, then the rest
    assert [a.attrs for a in appends] == [{"rows": 256, "version": 1},
                                          {"rows": _N - 256, "version": 2}]
    append = appends[1]
    folds = _by_name(records, "serve.fold")
    assert [f.attrs["lanes"] for f in folds] == [1, 1]
    assert folds[0].parent is None                      # register()
    assert folds[1].parent == append.id                 # the append's fold
    assert second.parent is None                        # result() flushed
    assert second.t0 > append.t1


def test_host_store_replays_record_host_rounds(env, traced):
    base = AuctionRule.first_price(_C)
    svc = CounterfactualService(env.budgets, events=env.values,
                                events_per_chunk=_EPC, store="host")
    svc.ask(base).result()
    records = obs.records()
    sweep = _by_name(records, "executor.sweep")[0]
    rounds = _by_name(records, "executor.round")
    syncs = _by_name(records, "executor.sync")
    assert rounds and all(r.parent == sweep.id for r in rounds)
    # one sync before the first round, one ending each round
    assert len(syncs) == len(rounds) + 1
    assert syncs[0].parent == sweep.id
    assert sorted(s.parent for s in syncs[1:]) == sorted(r.id
                                                         for r in rounds)


@pytest.mark.parametrize("chunks,layout", [(None, "once"),
                                           (_EPC, "per_chunk")])
def test_sweep_span_says_where_the_log_is_laid_out(env, traced, chunks,
                                                   layout):
    """``executor.sweep`` carries ``layout``: "once" where the round loop
    reads tiles laid out before it, "per_chunk" where an event-chunk scan
    lays each chunk out inside its step."""
    engine = CounterfactualEngine(env.values, env.budgets)
    engine.sweep(engine.grid(bid_scales=(1.0, 1.25)), chunks=chunks)
    sweep, = _by_name(obs.records(), "executor.sweep")
    assert sweep.attrs["layout"] == layout


@pytest.mark.parametrize("resolve,interpret,tile_skip", [
    ("jnp", None, None), ("fused", True, "window")])
def test_sweep_span_says_whether_the_kernels_skip_tiles(env, traced, resolve,
                                                        interpret, tile_skip):
    """``executor.sweep`` carries ``tile_skip="window"`` where the plan runs
    the fused kernels, which skip every grid step whose tile holds no row
    of its lane's window; no ``tile_skip`` where no kernel runs."""
    from repro.core.executor import execute_sweep, plan_for_driver
    engine = CounterfactualEngine(env.values, env.budgets)
    grid = engine.grid(bid_scales=(1.0, 1.25))
    execute_sweep(env.values, grid.budgets, grid.rules,
                  plan_for_driver("batched", resolve=resolve,
                                  interpret=interpret))
    sweep, = _by_name(obs.records(), "executor.sweep")
    assert sweep.attrs.get("tile_skip") == tile_skip


@pytest.mark.parametrize("driver", ["batched", "sharded"])
def test_sweep_span_says_how_the_log_is_sharded(env, traced, driver):
    """Under ``placement="sharded"`` ``executor.sweep`` also carries
    ``shards`` (devices on the event axes) and ``local_events`` (rows a
    shard holds); elsewhere its attributes are as they were."""
    from repro.launch.mesh import SweepMeshSpec
    mesh = SweepMeshSpec.for_devices() if driver == "sharded" else None
    engine = CounterfactualEngine(env.values, env.budgets)
    engine.sweep(engine.grid(bid_scales=(1.0, 1.25)), driver=driver,
                 mesh=mesh)
    sweep, = _by_name(obs.records(), "executor.sweep")
    want = {"placement": driver, "lanes": 2, "events": _N, "layout": "once"}
    if mesh is not None:
        shards = mesh.event_device_count
        want.update(shards=shards, local_events=_N // shards)
    assert sweep.attrs == want


def test_a_new_session_starts_a_fresh_buffer(tmp_path):
    obs.clear()
    jax.profiler.start_trace(str(tmp_path / "one"))
    with obs.span("first"):
        pass
    jax.profiler.stop_trace()
    with obs.span("between"):       # off: marks the session's end
        pass
    assert [r.name for r in obs.records()] == ["first"]
    jax.profiler.start_trace(str(tmp_path / "two"))
    with obs.span("second"):
        pass
    jax.profiler.stop_trace()
    assert [r.name for r in obs.records()] == ["second"]


def test_the_bound_counts_dropped_spans(traced, monkeypatch):
    monkeypatch.setattr(obs._RECORDER, "limit", 3)
    for k in range(5):
        with obs.span("s", k=k):
            pass
    assert [r.attrs["k"] for r in obs.records()] == [0, 1, 2]
    assert obs.dropped() == 2
    assert obs.MAX_RECORDS >= 2 ** 16


def test_a_compile_inside_a_span_is_recorded_under_it(traced):
    fresh = jax.jit(lambda x: x * 3.0 + 0.25)
    with obs.span("outer") as outer:
        fresh(jnp.ones(7)).block_until_ready()
    compiles = _by_name(obs.records(), obs.COMPILE_SPAN)
    assert compiles, "no compile recorded"
    assert all(c.parent == outer.id for c in compiles)
    assert any("lambda" in c.attrs["fun_name"] for c in compiles)


def test_set_adds_attributes_to_the_record(traced):
    with obs.span("outer", a=1) as span:
        with obs.span("inner"):
            pass
        span.set(b=2)
    inner, outer = obs.records()
    assert outer.attrs == {"a": 1, "b": 2} and inner.parent == outer.id


def test_no_program_span_shares_a_benchmark_span_name():
    names = set()
    for path in SRC.rglob("*.py"):
        names.update(re.findall(r'obs\.span\(\s*"([^"]+)"',
                                path.read_text()))
    assert {"serve.admit", "serve.flush", "serve.replay",
            "executor.sweep"} <= names
    assert not names & BENCH_SPANS
    assert not any(n.startswith("setup.") for n in names)
