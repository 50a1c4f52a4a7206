"""The main path's refusals: one case per ``ValueError`` site.

The executor (``core/executor.py``), the engine (``core/counterfactual.py``)
and the service (``serve/counterfactual.py``) refuse plan-axis combinations
that do not compose and inputs that break a contract. Each case below builds
one refused input at a tiny shape and checks that the refusal is the site's
own, by a fragment of its message. Most raise before anything compiles.

Refusals asserted elsewhere stay there: those whose message must be the same
from every entry point (``tests/test_scenario_sweep.py``), and the mesh
refusals on four devices (``tests/test_sharded_sweep.py``). Cases that need
several devices skip where fewer exist; run this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to include them.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CounterfactualEngine, ScenarioGrid, ScenarioOverlay
from repro.core.executor import (ChunkSpec, HostStream, SweepPlan,
                                 execute_s2a_sweep, execute_sweep,
                                 execute_sweep_resumable, initial_carry)
from repro.data import make_synthetic_env
from repro.launch.mesh import SweepMeshSpec
from repro.serve import CounterfactualService

N, C, S = 1024, 8, 2
EPC = 128


class _Ctx:
    """The tiny log, design grid and engine every case draws from."""

    def __init__(self):
        env = make_synthetic_env(jax.random.PRNGKey(5), n_events=N,
                                 n_campaigns=C, emb_dim=4)
        self.values, self.budgets, self.rule = env.values, env.budgets, \
            env.rule
        self.engine = CounterfactualEngine(env.values, env.budgets, env.rule)
        self.grid = self.engine.grid(bid_scales=[1.0, 1.2])
        self.one = SweepMeshSpec.for_devices(1)
        # full live windows: a static overlay every back-end accepts
        start = jnp.zeros((S, C), jnp.int32)
        self.live = ScenarioOverlay(live_start=start, live_stop=start + N)

    def sweep(self, plan, values=None, budgets=None, overlay=None):
        return execute_sweep(self.values if values is None else values,
                             self.grid.budgets if budgets is None
                             else budgets, self.grid.rules, plan,
                             overlay=overlay)

    def fold(self, plan, values=None, carry=None):
        return execute_sweep_resumable(
            self.values if values is None else values, self.grid.budgets,
            self.grid.rules, plan, carry=carry)

    def service(self, **kwargs):
        return CounterfactualService(self.budgets, self.rule,
                                     events_per_chunk=EPC, **kwargs)


@pytest.fixture(scope="module")
def ctx():
    return _Ctx()


def _register_twice(c):
    svc = c.service()
    svc.register("x")
    svc.register("x")


_HOST = ChunkSpec(256, source="host")

# (id, the refused call, a fragment of the site's own message)
CASES = [
    # -- executor: plan construction ---------------------------------------
    ("chunk-source", lambda c: ChunkSpec(256, source="disk"),
     "unknown chunk source: 'disk'"),
    ("placement", lambda c: SweepPlan(placement="cluster"),
     "unknown placement: 'cluster'"),
    # -- executor: host streams --------------------------------------------
    ("hoststream-empty", lambda c: HostStream([]),
     "HostStream needs at least one event slab"),
    ("hoststream-widths",
     lambda c: HostStream([np.zeros((4, C)), np.zeros((4, C - 1))]),
     "HostStream slabs must be non-empty (n, C) valuation blocks"),
    ("hoststream-window",
     lambda c: HostStream([np.zeros((4, C))]).chunk(0, 5),
     "chunk window [0, 5) outside the stream's 4 events"),
    ("hoststream-no-chunks",
     lambda c: c.sweep(SweepPlan(), values=HostStream.from_array(c.values)),
     "host-streamed execution needs chunks="),
    ("hoststream-x-mesh",
     lambda c: c.sweep(SweepPlan(placement="sharded", mesh=c.one,
                                 chunks=_HOST)),
     "host-streamed chunks run placement='device'/'batched' only"),
    ("hoststream-x-scenario-chunks",
     lambda c: c.sweep(SweepPlan(chunks=_HOST, scenario_chunks=1)),
     "scenario_chunks= does not compose with host-streamed chunks"),
    ("hoststream-x-overlay",
     lambda c: c.sweep(SweepPlan(chunks=_HOST), overlay=c.live),
     "overlays are not supported with host-streamed chunks"),
    # -- executor: batch shapes --------------------------------------------
    ("unbatched-budgets",
     lambda c: c.sweep(SweepPlan(), budgets=c.budgets),
     "sweep inputs must be batched"),
    ("batch-width",
     lambda c: c.sweep(SweepPlan(), budgets=c.grid.budgets[:, :C - 1]),
     "scenario batch mismatch"),
    # -- executor: overlays ------------------------------------------------
    ("overlay-shape",
     lambda c: c.sweep(SweepPlan(resolve="jnp"),
                       overlay=ScenarioOverlay(
                           bid_sigma=jnp.zeros((S, C - 1)),
                           key=jax.random.PRNGKey(0))),
     "ScenarioOverlay.bid_sigma must be (S, C)=(2, 8), got (2, 7)"),
    ("overlay-half-window",
     lambda c: c.sweep(SweepPlan(resolve="jnp"),
                       overlay=ScenarioOverlay(
                           live_start=c.live.live_start)),
     "live windows need BOTH live_start and live_stop"),
    ("overlay-time-varying",
     lambda c: c.sweep(SweepPlan(resolve="jnp"),
                       overlay=ScenarioOverlay(
                           part_prob=jnp.ones((S, C)),
                           key=jax.random.PRNGKey(0), time_varying=True)),
     "time_varying=True without live windows"),
    ("overlay-keyless",
     lambda c: c.sweep(SweepPlan(resolve="jnp"),
                       overlay=ScenarioOverlay(
                           bid_sigma=jnp.zeros((S, C)))),
     "stochastic overlay fields (bid_sigma / part_prob) need "
     "ScenarioOverlay.key"),
    ("multihost-x-overlay",
     lambda c: c.sweep(SweepPlan(placement="multihost",
                                 mesh=SweepMeshSpec.for_processes()),
                       overlay=c.live),
     "overlays are not supported with placement='multihost'"),
    # -- executor: resumable folds -----------------------------------------
    ("fold-placement",
     lambda c: c.fold(SweepPlan(placement="sharded", mesh=c.one)),
     "execute_sweep_resumable runs placement='batched' only"),
    ("fold-scenario-chunks",
     lambda c: c.fold(SweepPlan(scenario_chunks=1)),
     "scenario_chunks= is not supported by execute_sweep_resumable"),
    ("fold-empty",
     lambda c: c.fold(SweepPlan(), values=c.values[:0]),
     "resumable fold needs at least one new event row"),
    ("fold-carry",
     lambda c: c.fold(SweepPlan(), carry=initial_carry(S + 1, C)),
     "carry/batch mismatch"),
    # -- executor: the SORT2AGGREGATE sweep's plan -------------------------
    ("s2a-multihost",
     lambda c: c.engine.sweep(c.grid, method="sort2aggregate",
                              driver="multihost",
                              mesh=SweepMeshSpec.for_processes()),
     "placement='multihost' runs method='parallel' sweeps only"),
    ("s2a-sharded-x-chunks",
     lambda c: c.engine.sweep(c.grid, method="sort2aggregate",
                              driver="sharded", mesh=c.one, chunks=256),
     "chunks= does not compose with the sharded sort2aggregate sweep"),
    ("s2a-host-chunks",
     lambda c: c.engine.sweep(c.grid, method="sort2aggregate",
                              chunks=_HOST),
     "host-streamed chunks apply to method='parallel' sweeps only"),
    ("s2a-chunks-x-record-events",
     lambda c: c.engine.sweep(c.grid, method="sort2aggregate", chunks=256,
                              record_events=True),
     "record_events is not supported with chunks= on the sort2aggregate"),
    ("s2a-scenario-chunks",
     lambda c: execute_s2a_sweep(c.values, c.grid.budgets, c.grid.rules,
                                 SweepPlan(scenario_chunks=1)),
     "drop scenario_chunks= for the sort2aggregate sweep"),
    ("s2a-sharded-x-record-events",
     lambda c: c.engine.sweep(c.grid, method="sort2aggregate",
                              driver="sharded", mesh=c.one,
                              record_events=True),
     "record_events is not supported with driver='sharded'"),
    # -- engine ------------------------------------------------------------
    ("grid-rows",
     lambda c: ScenarioGrid(rules=c.grid.rules,
                            budgets=c.grid.budgets[:1],
                            labels=c.grid.labels),
     "inconsistent grid: 2 rules, 1 budget rows, 2 labels"),
    ("simulate-method",
     lambda c: c.engine.simulate(method="oracle"),
     "unknown method: oracle"),
    ("sweep-method",
     lambda c: c.engine.sweep(c.grid, method="oracle"),
     "unknown sweep method: oracle"),
    ("sequential-x-chunks",
     lambda c: c.engine.sweep(c.grid, method="sequential", chunks=256),
     "chunks= (event-chunked streaming) applies to method='parallel' and "
     "method='sort2aggregate' sweeps"),
    ("s2a-x-scenario-chunks",
     lambda c: c.engine.sweep(c.grid, method="sort2aggregate",
                              scenario_chunks=2),
     "drop scenario_chunks= for method='sort2aggregate'"),
    ("sequential-sharded",
     lambda c: c.engine.sweep(c.grid, method="sequential", driver="sharded",
                              mesh=c.one),
     "method='sequential' is the O(N)-serial validation oracle"),
    # -- service -----------------------------------------------------------
    ("service-budgets",
     lambda c: CounterfactualService(c.budgets[None, :], c.rule),
     "service budgets are the (C,) base design"),
    ("service-max-batch", lambda c: c.service(max_batch=0),
     "max_batch must be >= 1, got 0"),
    ("service-store", lambda c: c.service(store="disk"),
     "unknown store: 'disk'"),
    ("service-host-x-mesh",
     lambda c: c.service(store="host", placement="sharded", mesh=c.one),
     "store='host' replays through the host-stream pipeline"),
    ("service-host-x-scenario-chunks",
     lambda c: c.service(store="host", scenario_chunks=2),
     "store='host' does not compose with scenario_chunks="),
    ("service-host-chunk",
     lambda c: CounterfactualService(c.budgets, c.rule, store="host",
                                     events_per_chunk=48),
     "48 is not a multiple of REDUCE_BLOCKS=32"),
    ("service-empty-log", lambda c: c.service().ask().result(),
     "empty log: append events before asking the service"),
    ("service-append-width",
     lambda c: c.service().append(c.values[:, :C // 2]),
     "append expects (n, C=8) event rows, got shape (1024, 4)"),
    ("service-append-empty",
     lambda c: c.service().append(c.values[:0]),
     "append needs at least one event row"),
    ("service-ask-shape",
     lambda c: c.service().ask(budgets=c.budgets[:C // 2]),
     "scenario shape mismatch: service serves C=8 campaigns"),
    ("service-register-twice", _register_twice,
     "streaming scenario 'x' already registered"),
    ("service-unknown-stream", lambda c: c.service().streaming("y"),
     "unknown streaming scenario: 'y'"),
]


@pytest.mark.parametrize("build,fragment",
                         [pytest.param(b, f, id=i) for i, b, f in CASES])
def test_refused(ctx, build, fragment):
    with pytest.raises(ValueError, match=re.escape(fragment)):
        build(ctx)


@pytest.mark.skipif(len(jax.devices()) < 3,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=3 or more")
def test_refused_event_devices_that_cannot_divide_the_grid(ctx):
    """Three event devices can never hold whole canonical blocks (32 of
    them), whatever N: the sharded sweep refuses the mesh itself."""
    spec = SweepMeshSpec.for_devices(num_event_devices=3)
    n = 3 * 341           # shards of 341 events, blocks of 32
    with pytest.raises(ValueError, match=re.escape(
            "3 event-axis devices cannot divide the canonical reduction "
            "grid")):
        ctx.sweep(SweepPlan(placement="sharded", mesh=spec),
                  values=ctx.values[:n])
