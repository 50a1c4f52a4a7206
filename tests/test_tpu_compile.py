"""Compiles for a described TPU v5e, at the paper's §7.1 widths.

The TPU compiler is installed without the chip: it compiles for a topology
that is described, not attached, and refuses what the chip would refuse
(unaligned blocks, unsupported vector ops, VMEM or HBM overflow). Nothing
runs, so these tests say nothing about results or times — the CPU tests
and ``chip_smoke.py`` on the chip do.

§7.1: N = 10^6 events, C = 100 campaigns (padded to 128 lanes), S = 32
scenario lanes, and S = 1.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import repro.kernels
from repro.core import AuctionRule, executor, sweep_parallel
from repro.kernels.auction_resolve import (round_fused, sweep_partials,
                                           sweep_resolve)
from repro.launch.mesh import SweepMeshSpec

N, C = 1_000_000, 100
HBM_BYTES = 16 * 10 ** 9         # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("s", [32, 1])
def test_round_fused_compiles(one_chip, s):
    a = lambda shape, dt=jnp.float32: _sds(shape, one_chip, dt)
    _compile(lambda *x: round_fused(*x, reduce_blocks=32, interpret=False),
             a((N, C)), a((s, C)), a((s, C), bool), a((s,)), a((s, C)),
             a((s, C)), a((s,), jnp.int32), a((s,), bool))


@pytest.mark.parametrize("s", [32, 1])
def test_sweep_partials_compiles(one_chip, s):
    """One 4-chip shard's pass: 250,000 rows placed on the global grid."""
    a = lambda shape, dt=jnp.float32: _sds(shape, one_chip, dt)
    _compile(lambda *x: sweep_partials(*x, n_events_global=N,
                                       reduce_blocks=32, interpret=False),
             a((N // 4, C)), a((s, C)), a((s, C), bool), a((s,)),
             a((s,), jnp.int32), a((s,), jnp.int32), a((s,), bool),
             a((), jnp.int32))


@pytest.mark.parametrize("s", [32, 1])
def test_sweep_resolve_compiles(one_chip, s):
    a = lambda shape, dt=jnp.float32: _sds(shape, one_chip, dt)
    _compile(lambda *x: sweep_resolve(*x, interpret=False),
             a((N, C)), a((s, C)), a((s, C), bool), a((s,)))


@pytest.mark.parametrize("c,block_t", [(1024, 256), (2048, 256)])
def test_vmem_gate_admits_only_what_compiles(one_chip, c, block_t):
    """The one-launch gate (executor.round_fused_fits) must never admit a
    lane count the compiler refuses for lack of VMEM."""
    s = max(k for k in range(1, 512)
            if executor.round_fused_fits(k, c, block_t))
    a = lambda shape, dt=jnp.float32: _sds(shape, one_chip, dt)
    _compile(lambda *x: round_fused(*x, reduce_blocks=32, block_t=block_t,
                                    interpret=False),
             a((8192, c)), a((s, c)), a((s, c), bool), a((s,)), a((s, c)),
             a((s, c)), a((s,), jnp.int32), a((s,), bool))


@pytest.fixture
def as_if_on_tpu(monkeypatch):
    """Trace the executor's TPU branch (the fused kernels, compiled): the
    backend here is the CPU, so the platform check is steered in the test.
    Traces made under it are dropped afterwards."""
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    yield
    jax.clear_caches()


def _rules(s, sharding):
    return AuctionRule(multipliers=_sds((s, C), sharding),
                       reserve=_sds((s,), sharding), kind="first_price")


def test_batched_sweep_program_fits_one_chip(one_chip, as_if_on_tpu):
    """The whole default sweep program (while loop + fused round kernel)
    at §7.1, S=32, on one chip: the kernel is in it and it fits HBM."""
    compiled = _compile(
        lambda v, b, r: sweep_parallel(v, b, r).final_spend,
        _sds((N, C), one_chip), _sds((32, C), one_chip),
        _rules(32, one_chip))
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < HBM_BYTES, used


def test_sharded_sweep_program_compiles_for_four_chips(topo, as_if_on_tpu):
    """driver="sharded" on the 2x2 host: events over 4 chips, 250,000 per
    shard, the fused partials kernel on each, one psum per reduction."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    spec = SweepMeshSpec(mesh, event_axes=("data",))
    rep = NamedSharding(mesh, P())
    compiled = _compile(
        lambda v, b, r: sweep_parallel(v, b, r, driver="sharded",
                                       mesh=spec).final_spend,
        _sds((N, C), NamedSharding(mesh, P("data", None))),
        _sds((32, C), rep), _rules(32, rep))
    assert "all-reduce" in compiled.as_text()
