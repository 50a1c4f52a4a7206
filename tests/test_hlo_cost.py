"""The structural HLO cost analyzer vs XLA's cost_analysis.

XLA counts while-loop bodies once (demonstrated here); our analyzer scales by
trip counts and must agree with XLA on loop-free programs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import compiled_cost_analysis
from repro.launch import hlo_cost


def _mm(x, w):
    return jnp.tanh(x @ w)


def test_matches_xla_on_straightline():
    x = jnp.ones((256, 256))
    w = jnp.ones((256, 256))
    c = jax.jit(lambda x, w: _mm(_mm(x, w), w)).lower(x, w).compile()
    mine = hlo_cost.analyze(c.as_text())
    xla = compiled_cost_analysis(c)
    assert abs(mine.flops - xla["flops"]) / xla["flops"] < 0.02
    assert abs(mine.bytes - xla["bytes accessed"]) / xla["bytes accessed"] < 0.3


def test_scan_trip_count_scaling():
    x = jnp.ones((512, 512))
    w = jnp.ones((512, 512))

    def scanned(x, w):
        def body(c, _):
            return _mm(c, w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    c = jax.jit(scanned).lower(x, w).compile()
    xla = compiled_cost_analysis(c)["flops"]
    mine = hlo_cost.analyze(c.as_text()).flops
    true = 10 * 2 * 512 ** 3
    # XLA undercounts ~10x; ours within 2% of the truth
    assert xla < true / 5
    assert abs(mine - true) / true < 0.02


def test_nested_scan():
    x = jnp.ones((256, 256))
    w = jnp.ones((256, 256))

    def nested(x, w):
        def outer(c, _):
            def inner(d, _):
                return _mm(d, w), None
            d, _ = jax.lax.scan(inner, c, None, length=5)
            return d, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y

    c = jax.jit(nested).lower(x, w).compile()
    mine = hlo_cost.analyze(c.as_text()).flops
    true = 20 * 2 * 256 ** 3
    assert abs(mine - true) / true < 0.03


def test_collective_parsing():
    from jax.sharding import PartitionSpec as P
    import functools
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (run in the dryrun subprocess tests)")


def test_dtype_bytes_table():
    assert hlo_cost._type_bytes("f32[128,256]") == 128 * 256 * 4
    assert hlo_cost._type_bytes("bf16[8]{0}") == 16
    assert hlo_cost._type_bytes("(f32[2,2], s32[4])") == 16 + 16
    assert hlo_cost._type_bytes("pred[]") == 1


def test_hardware_spec_keyed_on_device_kind():
    """Rates come from the JAX device_kind; a kind the table does not know
    is an error, never another chip's peaks."""
    from repro.launch.roofline import HardwareSpec
    v5e = HardwareSpec.for_device_kind("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    assert HardwareSpec.for_device_kind(jax.devices()[0].device_kind)
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        HardwareSpec.for_device_kind("TPU v9 imaginary")
