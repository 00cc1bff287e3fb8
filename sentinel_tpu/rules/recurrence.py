"""Shared driver for per-segment recurrences over sorted item arrays.

Both serializing rule families — shaping pacers (rules/shaping.py) and
hot-param buckets (rules/param_table.py) — reduce to the same shape:
items sorted by (key, ts, arrival), per-key state threaded through the
key's items in order, a per-item ``transition`` producing (ok, wait)
and the successor state. This module owns the two exact execution
schedules so they cannot drift apart:

* ``rounds > 0`` — vectorized: within a segment each item's input
  state is its immediate predecessor's output (adjacent in the sorted
  order), so round *r* resolves every segment's *r*-th item in
  parallel. ``rounds`` is the host-known max items-per-key (static).
* ``rounds == 0`` — one ``lax.scan``: the carry is the running state;
  a segment start reloads from the pre-gathered segment-start state.

Invalid items must sort to the tail (callers key them past every real
key) and their transition must be identity on state with ok=True.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

# Rounds counts at or below this unroll at trace time (XLA fuses the
# whole chain); above it a fori_loop compiles the round body once.
# tests/test_scan_rounds.py derives its cross-path parity case from
# this constant.
UNROLL_MAX_ROUNDS = 4


def run_segmented(
    new_grp: jax.Array,  # bool [S] — segment starts in sorted order
    seg_states: Tuple[jax.Array, ...],  # per-item segment-START state
    items: Tuple[jax.Array, ...],  # per-item transition inputs [S]
    transition: Callable,  # (states, items) -> ((ok, wait), new_states)
    rounds: int,
) -> Tuple[jax.Array, jax.Array, Tuple[jax.Array, ...]]:
    """Returns (ok [S] bool, wait [S] int32, post-item states) in the
    sorted order of the inputs; a segment's final state sits at its
    last item's position (the caller's seg-end write-back)."""
    s = new_grp.shape[0]
    if rounds > 0:
        idx = jnp.arange(s, dtype=jnp.int32)
        seg_start = jax.lax.cummax(jnp.where(new_grp, idx, 0))
        seg_pos = idx - seg_start
        ok = jnp.ones((s,), dtype=bool)
        wait = jnp.zeros((s,), dtype=jnp.int32)

        def one_round(r, ok, wait, out_states):
            # Round r resolves every segment's r-th item: its input
            # state is seg-start state (r==0) or the adjacent
            # predecessor's output from the previous round.
            shifted = tuple(jnp.concatenate([o[:1], o[:-1]]) for o in out_states)
            ins = tuple(
                jnp.where(jnp.equal(r, 0), ss, sh)
                for ss, sh in zip(seg_states, shifted)
            )
            (ok_r, wait_r), new_states = transition(ins, items)
            sel = seg_pos == r
            ok = jnp.where(sel, ok_r, ok)
            wait = jnp.where(sel, wait_r, wait)
            out_states = tuple(
                jnp.where(sel, ns, os) for ns, os in zip(new_states, out_states)
            )
            return ok, wait, out_states

        if rounds <= UNROLL_MAX_ROUNDS:
            # Small counts: unroll at trace time so XLA fuses freely.
            out_states = seg_states
            for r in range(rounds):
                ok, wait, out_states = one_round(
                    jnp.int32(r), ok, wait, out_states
                )
            return ok, wait, out_states

        # Large counts: a fori_loop compiles the round body ONCE.
        # Unrolling 16+ copies of the transition into the HLO multiplied
        # compile time past the bench's stage timeout (round-4 hardware
        # session) for runtime that is identical.
        n_st = len(seg_states)

        def body(r, carry):
            ok, wait = carry[0], carry[1]
            out_states = tuple(carry[2 : 2 + n_st])
            ok, wait, out_states = one_round(r, ok, wait, out_states)
            return (ok, wait, *out_states)

        out = jax.lax.fori_loop(0, rounds, body, (ok, wait, *seg_states))
        return out[0], out[1], tuple(out[2 : 2 + n_st])

    n_st = len(seg_states)

    def step(carry, x):
        ng = x[0]
        item_vals = x[1 : 1 + len(items)]
        seg_vals = x[1 + len(items) :]
        states = tuple(
            jnp.where(ng, sv, cv) for sv, cv in zip(seg_vals, carry)
        )
        (ok_i, wait_i), new_states = transition(states, item_vals)
        return new_states, (ok_i, wait_i) + new_states

    init = tuple(a[0] for a in seg_states)
    xs = (new_grp,) + items + seg_states
    _, ys = jax.lax.scan(step, init, xs)
    return ys[0], ys[1], tuple(ys[2 : 2 + n_st])
