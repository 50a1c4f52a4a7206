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
import re

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


def _computations(text):
    """HLO text -> {computation name: its instruction lines}."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _called(line):
    names = re.findall(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)",
                       line)
    for group in re.findall(
            r"(?:called_computations|branch_computations)=\{([^}]*)\}",
            line):
        names += [x.strip().lstrip("%") for x in group.split(",")]
    return names


def _relayout_in_and_out_of_loops(compiled):
    """Instructions scoped ``relayout``: (inside a while loop's body or a
    computation it calls, elsewhere). A scenario-chunk ``lax.map`` is a
    while loop too, so "inside" covers it."""
    comps = _computations(compiled.as_text())
    stack = [b for lines in comps.values() for line in lines
             for b in re.findall(r"body=%?([\w.\-]+)", line)]
    assert stack, "no while loop in the program"
    looped = set()
    while stack:
        name = stack.pop()
        if name in looped or name not in comps:
            continue
        looped.add(name)
        stack += [c for line in comps[name] for c in _called(line)]
    scoped = lambda names: [line.strip() for n in names for line in comps[n]
                            if re.search(r'op_name="[^"]*/relayout/', line)]
    return scoped(looped), scoped(set(comps) - looped)


@pytest.mark.parametrize("s", [32, 1])
def test_one_launch_program_names_its_kernel_and_relayout(one_chip,
                                                          as_if_on_tpu, s):
    """A device trace knows the fused round kernel by its instruction name,
    ``round_fused`` or ``round_fused.<n>``, and the log's layout into
    block tiles, made once a sweep before the round loop, by the
    ``relayout`` scope in its instructions' metadata."""
    compiled = _compile(
        lambda v, b, r: sweep_parallel(v, b, r).final_spend,
        _sds((N, C), one_chip), _sds((s, C), one_chip),
        _rules(s, one_chip))
    text = compiled.as_text()
    kernels = re.findall(r"%(round_fused(?:\.\d+)?) = [^\n]*"
                         r'custom_call_target="tpu_custom_call"', text)
    assert kernels, "no round_fused kernel instruction"
    assert re.search(r'op_name="[^"]*/relayout/', text)


@pytest.mark.parametrize("s,scenario_chunks", [(32, None), (1, None),
                                               (32, 8)])
def test_one_launch_program_lays_the_log_out_outside_its_loops(
        one_chip, as_if_on_tpu, s, scenario_chunks):
    """The log never changes within a sweep: its layout sits before the
    round loop and before the scenario-chunk scan, not in their bodies."""
    compiled = _compile(
        lambda v, b, r: sweep_parallel(
            v, b, r, scenario_chunks=scenario_chunks).final_spend,
        _sds((N, C), one_chip), _sds((s, C), one_chip),
        _rules(s, one_chip))
    inside, outside = _relayout_in_and_out_of_loops(compiled)
    assert not inside, inside[:3]
    assert any(" gather(" in line for line in outside), outside[:3]


def test_sharded_sweep_program_compiles_for_four_chips(topo, as_if_on_tpu):
    """driver="sharded" on the 2x2 host: events over 4 chips, 250,000 per
    shard, the fused partials kernel on each, one psum per reduction; each
    shard's rows are laid out once, outside the round loop."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    spec = SweepMeshSpec(mesh, event_axes=("data",))
    rep = NamedSharding(mesh, P())
    compiled = _compile(
        lambda v, b, r: sweep_parallel(v, b, r, driver="sharded",
                                       mesh=spec).final_spend,
        _sds((N, C), NamedSharding(mesh, P("data", None))),
        _sds((32, C), rep), _rules(32, rep))
    assert "all-reduce" in compiled.as_text()
    inside, outside = _relayout_in_and_out_of_loops(compiled)
    assert not inside, inside[:3]
    assert any(" gather(" in line for line in outside), outside[:3]


def test_bigday_sharded_sweep_fits_four_chips(topo, as_if_on_tpu):
    """The four-chip benchmark cell's program (bigday-x4.grid8): the §7.1
    market at N = 3.2e7, 8e6 rows a chip, S = 8. Arguments, temp and
    output fit 14 GiB a chip; the round's only collectives are its two
    all-reduces of (S, 32, C) partials, scoped ``exchange``: no step
    gathers the log."""
    n, s = 32_000_000, 8
    mesh = Mesh(np.array(topo.devices), ("data",))
    spec = SweepMeshSpec(mesh, event_axes=("data",))
    rep = NamedSharding(mesh, P())
    compiled = _compile(
        lambda v, b, r: sweep_parallel(v, b, r, driver="sharded",
                                       mesh=spec).final_spend,
        _sds((n, C), NamedSharding(mesh, P("data", None))),
        _sds((s, C), rep), _rules(s, rep))
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert ma.argument_size_in_bytes >= n // 4 * C * 4    # one shard
    assert used <= 14 * 2 ** 30, used
    collectives = [line.strip() for line in compiled.as_text().splitlines()
                   if re.search(r" (all-reduce|all-gather|all-to-all|"
                                r"collective-permute|reduce-scatter)"
                                r"(-start)?\(", line)]
    assert len(collectives) == 2, collectives
    for line in collectives:
        assert re.search(rf"= f32\[{s},32,{C}\]\S* all-reduce\(", line), line
        assert re.search(r'op_name="[^"]*/exchange/', line), line
