"""jit'd wrapper: (B, S, H, dh) GQA-aware entry for the flash kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import use_interpret
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(
    q: jax.Array,               # (B, S, H, dh)
    k: jax.Array,               # (B, S, KV, dh)
    v: jax.Array,               # (B, S, KV, dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    b, s, h, dh = q.shape
    kv = k.shape[2]
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    bq = min(block_q, s)
    bk = min(block_k, s)
    while s % bq:
        bq //= 2
    while s % bk:
        bk //= 2
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    o = flash_attention_pallas(qf, kf, vf, causal=causal, window=window,
                               block_q=max(bq, 1), block_k=max(bk, 1),
                               interpret=use_interpret(interpret))
    return o.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
