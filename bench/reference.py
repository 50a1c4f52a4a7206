"""The benchmark's plain reference: a sequential replay of the paper's
Eqs. 1-3 (arXiv:2509.04038 §3), first price with per-campaign bid
multipliers and a reserve, in straightforward ``jax.numpy``.

It imports nothing of the system under test and takes only the generated
inputs: the (N, C) valuation log, (S, C) budgets, (S, C) multipliers and
(S,) reserves. Semantics, per lane and per event ``n`` in log order:

* campaign ``c`` is active iff its spend so far is below its budget
  (``a_n^c = 1{s_n^c < b^c}``, evaluated before the auction);
* its bid is ``multiplier[c] * value[n, c]``; it is eligible iff active and
  its bid is strictly above the reserve;
* the highest eligible bid wins (ties to the lowest campaign index) and
  pays its own bid; no eligible bid, no sale;
* the winner's spend grows by the full price, even past its budget;
* a campaign's cap time is the 1-based index of the event after which its
  spend first reached its budget; ``N + 1`` where it never did.

``dtype`` is the precision of the data path (log and bids). ``float32`` is
the reference; ``bfloat16`` is the control that computes the same replay
on a log and bids rounded to bfloat16 (spends still add in float32), the
precision step a later change would be tempted by to halve the log's
bytes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _replay_segment(carry, values, n0, budgets, multipliers, reserve, dtype,
                    spend_dtype):
    """Replay ``values`` (T, C), whose first row is event ``n0`` (0-based),
    from carried ``(spend, cap)``; returns the new carry."""
    mult = multipliers.astype(dtype)
    res = reserve.astype(dtype)[:, None]
    neg_inf = jnp.array(-jnp.inf, dtype)
    n_campaigns = values.shape[1]
    idx = jnp.arange(n_campaigns, dtype=jnp.int32)

    def step(state, row_n):
        spend, cap = state
        row, n = row_n
        active = spend < budgets
        bid = row.astype(dtype)[None, :] * mult
        masked = jnp.where(active & (bid > res), bid, neg_inf)
        winner = jnp.argmax(masked, axis=1)
        top = jnp.max(masked, axis=1)
        sale = top > neg_inf
        price = jnp.where(sale, top, 0.0).astype(spend_dtype)
        won = (idx[None, :] == winner[:, None]) & sale[:, None]
        spend = spend + jnp.where(won, price[:, None],
                                  jnp.zeros((), spend_dtype))
        cap = jnp.where((spend >= budgets) & (cap < 0), n + 1, cap)
        return (spend, cap), None

    n_idx = n0 + jnp.arange(values.shape[0], dtype=jnp.int32)
    carry, _ = jax.lax.scan(step, carry, (values, n_idx), unroll=8)
    return carry


@functools.partial(jax.jit, static_argnames=("segment", "dtype",
                                             "spend_dtype"))
def _replay(values, budgets, multipliers, reserve, *, segment, dtype,
            spend_dtype):
    n_events, n_campaigns = values.shape
    n_lanes = budgets.shape[0]
    n_seg = n_events // segment
    budgets = budgets.astype(spend_dtype)
    init = (jnp.zeros((n_lanes, n_campaigns), spend_dtype),
            jnp.full((n_lanes, n_campaigns), -1, jnp.int32))

    def seg_step(carry, k):
        block = jax.lax.dynamic_slice_in_dim(values, k * segment, segment)
        carry = _replay_segment(carry, block, k * segment, budgets,
                                multipliers, reserve, dtype, spend_dtype)
        return carry, carry

    _, (spends, caps) = jax.lax.scan(seg_step, init,
                                     jnp.arange(n_seg, dtype=jnp.int32))
    return spends, caps


def replay(values, budgets, multipliers, reserve, *, segment=None,
           dtype="float32", spend_dtype="float32"):
    """Sequential replay of S lanes over the log.

    Returns ``(spend, cap)`` as host arrays of shape (K, S, C): the state
    after each of the ``K = N / segment`` prefixes of ``segment`` events
    (``segment=None``: one prefix, the whole log). ``cap`` holds 1-based
    cap times, -1 where the campaign had not capped by that prefix."""
    n_events = values.shape[0]
    segment = n_events if segment is None else int(segment)
    if n_events % segment:
        raise ValueError(f"segment {segment} does not divide N={n_events}")
    with jax.default_matmul_precision("highest"):
        spends, caps = _replay(
            values, jnp.asarray(budgets, jnp.float32),
            jnp.asarray(multipliers, jnp.float32),
            jnp.asarray(reserve, jnp.float32), segment=segment,
            dtype=jnp.dtype(dtype).name,
            spend_dtype=jnp.dtype(spend_dtype).name)
    return np.asarray(spends, np.float32), np.asarray(caps)


def spend_weighted_error(s_hat, s_ref) -> np.ndarray:
    """The paper's Fig. 6 metric per lane: per-campaign relative spend
    errors weighted by the reference's spend share. ``(..., C)`` arrays in,
    ``(...)`` out, computed in float64 on the host."""
    s_hat = np.asarray(s_hat, np.float64)
    s_ref = np.asarray(s_ref, np.float64)
    rel = np.abs(s_hat - s_ref) / np.maximum(np.abs(s_ref), 1e-12)
    w = s_ref / np.maximum(s_ref.sum(-1, keepdims=True), 1e-12)
    return (rel * w).sum(-1)


def uncapped_error(s_hat, s_ref, budgets) -> np.ndarray:
    """The same, over the campaigns the reference leaves below budget: the
    part of the error Algorithm 2 makes only through its competitors' cap
    times (its block spends are exact), where a rounded log errs on every
    sale."""
    s_hat = np.asarray(s_hat, np.float64)
    s_ref = np.asarray(s_ref, np.float64)
    below = s_ref < np.asarray(budgets, np.float64)
    return (np.abs(s_hat - s_ref) * below).sum(-1) / np.maximum(
        s_ref.sum(-1), 1e-12)


def median_error(s_hat, s_ref) -> np.ndarray:
    """The median campaign's relative spend error, per lane."""
    s_hat = np.asarray(s_hat, np.float64)
    s_ref = np.asarray(s_ref, np.float64)
    rel = np.abs(s_hat - s_ref) / np.maximum(np.abs(s_ref), 1e-12)
    return np.median(rel, -1)


def error_numbers(s_hat, s_ref, budgets) -> dict:
    """The numbers a correctness check may compare, each the largest over
    the answers ``(..., C)``: ``max_spend_err``, ``max_uncapped_err`` and
    ``max_median_err``."""
    return {
        "max_spend_err": float(spend_weighted_error(s_hat, s_ref).max()),
        "max_uncapped_err": float(uncapped_error(s_hat, s_ref,
                                                 budgets).max()),
        "max_median_err": float(median_error(s_hat, s_ref).max()),
    }


def compared(numbers: dict, limits: dict) -> list:
    """``(name, value, limit)`` for each number the cell's limits name."""
    return [(name, numbers[name], float(limit))
            for name, limit in limits.items() if name in numbers]
