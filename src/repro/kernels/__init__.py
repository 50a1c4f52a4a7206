"""Pallas TPU kernels. Each subpackage: <name>.py (pl.pallas_call +
BlockSpec), ops.py (jit wrapper; interpret mode off-TPU), ref.py (jnp oracle).
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU.

    Asked when a kernel wrapper or the sweep executor is traced, never at
    import: asking initialises the backend, and on a TPU host that claims
    the chip for this process."""
    return jax.default_backend() == "tpu"


def use_interpret(interpret: Optional[bool]) -> bool:
    """A kernel wrapper's ``interpret=`` argument, resolved: ``None`` means
    compiled on a TPU and the Pallas interpreter anywhere else."""
    return not on_tpu() if interpret is None else interpret
