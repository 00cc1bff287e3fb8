"""Traffic-shaping controllers: RateLimiter, WarmUp, WarmUpRateLimiter.

These three behaviors carry *per-rule mutable state* across requests —
``latestPassedTime`` for the leaky-bucket pacer (reference: controller/
RateLimiterController.java:28-90), ``storedTokens``/``lastFilledTime``
for the Guava-style warm-up ramp (reference: controller/
WarmUpController.java:84-175, WarmUpRateLimiterController.java:25-90) —
which makes them a *recurrence* over each rule's request sequence, not a
stateless threshold like DefaultController.

Batched execution: shaping slots (a tiny minority of traffic in
practice) are gathered into their own compact array, sorted by
``(rule, ts, entry)``, and resolved per rule-segment. Two exact
implementations share one transition function:

* ``rounds > 0`` — the vectorized path: within a segment each item's
  state comes from its immediate predecessor in the sorted order, so
  round *r* resolves every segment's *r*-th item in parallel; ``rounds``
  is the host-known max items-per-rule in the batch (a static arg —
  each bucket compiles once). M full-vector passes instead of an
  s-step sequential scan: on TPU this is the difference between ~µs
  and ~ms, because a ``lax.scan`` iteration costs per-step loop
  overhead regardless of how little work the body does.
* ``rounds == 0`` — one ``lax.scan`` whose carry is the current rule's
  shaping state; the fallback when one rule dominates the batch
  (max-per-rule too large for unrolled rounds).

Both reproduce the reference's per-request logic step for step —
including the per-second token re-fill (syncToken) — so they are exact
even when a batch spans multiple seconds. A third, ``rounds == -1``,
is the closed form for batches whose items all share ONE timestamp and
ONE acquire ≥ 1 (the columnar-bulk shape), for all three behaviors: at
one ts, syncToken can fire only at a segment's first item, so the
warm-up state is fixed after it, each warm-up verdict is elementwise
and each pacer's cost is one constant per rule — rank math, no
recurrence. The scan and rounds paths serve mixed-timestamp batches.
The vectorized DEFAULT path never pays for any of this: when no shaping
rules are loaded the module is never entered.

Numerics: Java computes in float64; the math uses float32 for the
warm-up slope (divergence only possible exactly at a threshold
boundary for extreme rule counts) and host-precomputed exact int
``cost1_ms`` for the ubiquitous acquire==1 rate-limiter case. Java's
``latestPassedTime``/``lastFilledTime`` start effectively "infinitely
past" because wall-clock ms are huge; with the engine's relative clock
the same effect comes from the -1e9 initialisation in
FlowIndex.make_dyn_state.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sentinel_tpu.models import constants as C
from sentinel_tpu.rules.flow_table import FlowRuleDynState, FlowTableDevice


class ShapingBatch(NamedTuple):
    """Compact per-slot arrays for shaping-controlled rule slots.

    ``flat_pos`` indexes back into the [N*K] flattened slot matrix so
    verdicts/waits can be scattered into the main result.
    """

    valid: jax.Array  # bool [S]
    gid: jax.Array  # int32 [S] rule id
    row: jax.Array  # int32 [S] check-node row
    eidx: jax.Array  # int32 [S] entry index
    flat_pos: jax.Array  # int32 [S] position in the [N*K] slot matrix
    ts: jax.Array  # int32 [S]
    acquire: jax.Array  # int32 [S]


def _pacer_cost(acq_f, acq_i, cnt, c1):
    """RateLimiter pacing cost in ms: the host-precomputed exact cost1
    for the acquire==1 fast path, else round(acquire/count*1000).
    :func:`mirror_pacer_cost` is its host twin — one cost formula."""
    cost_generic = jnp.floor(acq_f / jnp.maximum(cnt, 1e-9) * 1000.0 + 0.5)
    return jnp.where(acq_i == 1, c1.astype(jnp.float32), cost_generic).astype(
        jnp.int32
    )


def _transition(latest, stored, lastfill, x):
    """One item's controller decision + state update, vector-friendly
    (works elementwise on arrays of items as well as on scan scalars).
    Invalid items are identity on state and ok=True.
    Returns (ok, wait_out, latest', stored', lastfill', cost): ``cost``
    is the pacer cost in ms the item was charged (the warm-up-adjusted
    one for WARM_UP_RATE_LIMITER), which the closed-form path reuses."""
    (valid, ts, acq_f, acq, passq, prevq, b, cnt, mq, c1, wn, mx, sl, rt) = x

    is_wu = (b == C.CONTROL_BEHAVIOR_WARM_UP) | (
        b == C.CONTROL_BEHAVIOR_WARM_UP_RATE_LIMITER
    )

    # --- syncToken (WarmUpController.syncToken/coolDownTokens) ---
    sec = ts - ts % 1000
    do_sync = is_wu & (sec > lastfill) & valid
    elapsed = (sec - lastfill).astype(jnp.float32)
    refill_ok = (stored < wn) | ((stored > wn) & (prevq < rt))
    refilled = jnp.minimum(jnp.floor(stored + elapsed * cnt / 1000.0), mx)
    stored1 = jnp.where(do_sync & refill_ok, refilled, stored)
    stored2 = jnp.where(do_sync, jnp.maximum(stored1 - prevq, 0.0), stored1)
    lastfill2 = jnp.where(do_sync, sec, lastfill)

    # --- warm-up admitted-QPS (above the warning line) ---
    above = jnp.maximum(stored2 - wn, 0.0)
    inv = above * sl + 1.0 / jnp.maximum(cnt, 1e-9)
    # Math.nextUp on the Java double; nextafter on f32 here.
    warning_qps = jnp.nextafter(1.0 / inv, jnp.float32(jnp.inf))
    cold = stored2 >= wn

    wu_ok = jnp.where(cold, passq + acq_f <= warning_qps, passq + acq_f <= cnt)

    # --- pacer cost (RateLimiter / WarmUpRateLimiter) ---
    cost_rl = _pacer_cost(acq_f, acq, cnt, c1).astype(jnp.float32)
    cost_wurl_cold = jnp.floor(acq_f / warning_qps * 1000.0 + 0.5)
    cost_wurl = jnp.where(cold, cost_wurl_cold, cost_rl)
    cost = jnp.where(
        b == C.CONTROL_BEHAVIOR_WARM_UP_RATE_LIMITER, cost_wurl, cost_rl
    ).astype(jnp.int32)

    expected = latest + cost
    imm = expected <= ts
    wait = expected - ts
    queued = (~imm) & (wait <= mq)
    pacer_ok = (imm | queued) & (cnt > 0)
    pacer_ok = pacer_ok | (acq <= 0)  # acquire<=0 always passes
    latest2 = jnp.where(
        valid & pacer_ok & (acq > 0), jnp.where(imm, ts, latest + cost), latest
    )
    wait_out = jnp.where(queued & pacer_ok, wait, 0)

    is_pacer = (b == C.CONTROL_BEHAVIOR_RATE_LIMITER) | (
        b == C.CONTROL_BEHAVIOR_WARM_UP_RATE_LIMITER
    )
    ok = jnp.where(
        b == C.CONTROL_BEHAVIOR_WARM_UP,
        wu_ok,
        jnp.where(is_pacer, pacer_ok, True),
    )
    ok = ok | ~valid
    wait_out = jnp.where(valid & is_pacer, wait_out, 0)

    # Pacer state only advances for pacer behaviors; warm-up state
    # only via sync. Invalid items must not touch state.
    latest3 = jnp.where(valid & is_pacer, latest2, latest)
    stored3 = jnp.where(valid, stored2, stored)
    lastfill3 = jnp.where(valid, lastfill2, lastfill)
    return ok, wait_out, latest3, stored3, lastfill3, cost


def run_shaping(
    flow_dev: FlowTableDevice,
    flow_dyn: FlowRuleDynState,
    shaping: ShapingBatch,
    pass_consumed: jax.Array,  # int32 [S] — windowed pass sum + intra-batch charge
    prev_pass: jax.Array,  # int32 [S] — previous 1s-bucket pass count (minute array)
    interval_sec: float,
    rounds: int = 0,
) -> Tuple[FlowRuleDynState, jax.Array, jax.Array]:
    """Evaluate shaping slots; returns (new_dyn, ok [S], wait_ms [S])
    in the caller's slot order.

    ``rounds`` (static): host-known upper bound on items-per-rule in
    this batch — picks the vectorized rounds path; 0 falls back to the
    sequential ``lax.scan`` (see module docstring); −1 selects the
    closed form, which is ONLY valid when the host verified that every
    item shares one ts and one acquire ≥ 1, whatever the behaviors
    (Engine._shaping_rounds_for owns that predicate — run_shaping does
    not re-validate).

    The three behaviors (reference files in module docstring):

    * RATE_LIMITER — pace requests ``cost = round(acquire/count*1000)``
      ms apart; queue up to ``max_queueing_time_ms``, else block.
    * WARM_UP — token bucket from cold: above the warning line the
      admitted QPS is ``1/(aboveToken*slope + 1/count)``; refill happens
      once per second, consuming the previous second's pass count.
    * WARM_UP_RATE_LIMITER — the pacer with the warm-up-adjusted cost.
    """
    s = shaping.valid.shape[0]
    nr = flow_dev.n_rules

    # Sort by (gid, ts, arrival); invalid slots sort last (gid = nr).
    # Compacted batches are built in entry order (eidx nondecreasing in
    # item position), so pos as the last key reproduces the
    # (gid, ts, eidx) order with one less sort operand.
    gid_key = jnp.where(shaping.valid, shaping.gid, jnp.int32(nr))
    pos = jnp.arange(s, dtype=jnp.int32)
    gid_s, ts_s, p_s = jax.lax.sort((gid_key, shaping.ts, pos), num_keys=3)
    gid_c = jnp.clip(gid_s, 0, nr - 1)
    valid_s = shaping.valid[p_s]
    acq_s = shaping.acquire[p_s].astype(jnp.float32)
    acq_i = shaping.acquire[p_s]
    passq_s = jnp.floor(pass_consumed[p_s].astype(jnp.float32) / interval_sec)
    prevq_s = prev_pass[p_s].astype(jnp.float32)

    beh = flow_dev.behavior[gid_c]
    count = flow_dev.count[gid_c]
    maxq = flow_dev.max_queueing_time_ms[gid_c]
    cost1 = flow_dev.cost1_ms[gid_c]
    warn = flow_dev.warmup_warning_token[gid_c].astype(jnp.float32)
    maxtok = flow_dev.warmup_max_token[gid_c].astype(jnp.float32)
    slope = flow_dev.warmup_slope[gid_c]
    refill_thr = flow_dev.warmup_refill_threshold[gid_c].astype(jnp.float32)

    # Segment-start state is pre-gathered OUTSIDE the recurrence (one
    # vectorized gather instead of per-step dynamic gathers).
    seg_latest = flow_dyn.latest_passed_time[gid_c]
    seg_stored = flow_dyn.stored_tokens[gid_c]
    seg_lastfill = flow_dyn.last_filled_time[gid_c]

    items = (
        valid_s, ts_s, acq_s, acq_i, passq_s, prevq_s,
        beh, count, maxq, cost1, warn, maxtok, slope, refill_thr,
    )
    ones = jnp.ones((1,), dtype=bool)
    new_grp = jnp.concatenate([ones, gid_s[1:] != gid_s[:-1]])

    if rounds == -1:
        # Closed-form path (host-selected when EVERY item shares ONE
        # timestamp and ONE acquire ≥ 1 — the columnar-bulk shape).
        # Warm-up state changes only in syncToken, which fires only
        # when sec > lastfill: within a segment that is at most its
        # first item, after which lastfill == sec. So every item can
        # see the segment-start state and the FIRST item's prevq (a
        # gather, in case one rule's items carry different check
        # rows): one elementwise _transition then gives each item the
        # post-sync stored/lastfill, its WARM_UP verdict (its own
        # passq) and its pacer cost, constant over the segment.
        idx = jnp.arange(s, dtype=jnp.int32)
        seg_start = jax.lax.cummax(jnp.where(new_grp, idx, 0))
        r1 = idx - seg_start + 1  # rank within segment, 1-indexed
        items_first = items[:5] + (prevq_s[seg_start],) + items[6:]
        ok_t, _, _, stored_t, lastfill_t, cost = _transition(
            seg_latest, seg_stored, seg_lastfill, items_first
        )
        is_pacer = (beh == C.CONTROL_BEHAVIOR_RATE_LIMITER) | (
            beh == C.CONTROL_BEHAVIOR_WARM_UP_RATE_LIMITER
        )

        # Pacer behaviors: with a single ts per rule, at most the FIRST
        # grant can be immediate (it pins latest to ts), and each
        # further grant queues exactly one more ``cost`` out, so the
        # r-th grant's wait is a closed form of the segment-start state
        # and admission is prefix-monotone rank math — any per-rule
        # multiplicity in O(sort), no unroll, no scan.
        latest0 = seg_latest
        imm0 = latest0 + cost <= ts_s
        gate = count > 0

        # Segment grant cap G, division math only — ``r1 <= cap`` is
        # the admission test precisely BECAUSE rank×cost products can
        # overflow int32 for large segments × large costs (a wait-based
        # test wraps negative and wrongly admits); the cap form never
        # multiplies. (cost==0 → unbounded: every grant is immediate /
        # same constant wait, latest never advances.)
        big = jnp.int32(1 << 30)
        safe_cost = jnp.maximum(cost, 1)
        g_imm = jnp.where(cost > 0, 1 + maxq // safe_cost, big)
        g_queue = jnp.where(
            cost > 0,
            jnp.maximum((ts_s + maxq - latest0) // safe_cost, 0),
            jnp.where(latest0 - ts_s <= maxq, big, 0),
        )
        cap = jnp.where(gate, jnp.where(imm0, g_imm, g_queue), 0)
        # WARM_UP items keep _transition's elementwise verdict, no wait
        # and no pacer state.
        ok_s = jnp.where(is_pacer, (r1 <= cap) | ~valid_s, ok_t)
        # Waits only for admitted items, whose rank×cost is bounded by
        # maxq (+ts−latest0) and cannot overflow; blocked lanes may
        # wrap but are masked to 0.
        wait_r = jnp.where(imm0, (r1 - 1) * cost, latest0 + r1 * cost - ts_s)
        wait_out_s = jnp.where(
            valid_s & is_pacer & ok_s & (wait_r > 0), wait_r, 0
        )
        granted_here = jnp.minimum(r1, cap)
        latest_s = jnp.where(
            is_pacer & (granted_here > 0),
            jnp.where(
                imm0, ts_s + (granted_here - 1) * cost,
                latest0 + granted_here * cost,
            ),
            latest0,
        )
        stored_s, lastfill_s = stored_t, lastfill_t
    else:
        def transition(states, item_vals):
            latest, stored, lastfill = states
            ok, wait_out, l2, s2, f2, _cost = _transition(
                latest, stored, lastfill, item_vals
            )
            return (ok, wait_out), (l2, s2, f2)

        from sentinel_tpu.rules.recurrence import run_segmented

        ok_s, wait_out_s, (latest_s, stored_s, lastfill_s) = run_segmented(
            new_grp, (seg_latest, seg_stored, seg_lastfill), items, transition,
            rounds,
        )

    # Write final per-rule state back at segment ends (last write wins).
    seg_end = jnp.concatenate(
        [gid_s[1:] != gid_s[:-1], jnp.ones((1,), dtype=bool)]
    ) & valid_s
    scatter_gid = jnp.where(seg_end, gid_c, jnp.int32(nr))  # nr -> dropped
    new_dyn = FlowRuleDynState(
        latest_passed_time=flow_dyn.latest_passed_time.at[scatter_gid].set(
            latest_s, mode="drop"
        ),
        stored_tokens=flow_dyn.stored_tokens.at[scatter_gid].set(stored_s, mode="drop"),
        last_filled_time=flow_dyn.last_filled_time.at[scatter_gid].set(
            lastfill_s, mode="drop"
        ),
    )

    # Un-sort results back to the caller's slot order.
    ok_out = jnp.ones((s,), dtype=bool).at[p_s].set(ok_s)
    wait_out = jnp.zeros((s,), dtype=jnp.int32).at[p_s].set(wait_out_s)
    return new_dyn, ok_out, wait_out


# ----------------------------------------------------------------------
# Host mirror of the shaping controllers (speculative fast tier)
# ----------------------------------------------------------------------
# The speculative tier (runtime/speculative.py) serves shaped resources
# from a persistent host mirror instead of declining them to the sync
# device path; the mirror's per-op decision lives HERE, next to the
# kernel recurrence it mirrors, so the two transition functions can
# only drift in one reviewed place. State (one mutable record per rule)
# lives on failover.HostFallbackAdmitter; these are pure-ish functions
# over that record. The device settles the very same ops and the mirror
# re-anchors to the settled ``latestPassedTime`` at every drain.


def mirror_pacer_cost(acquire: int, count: float, cost1_ms: int) -> int:
    """Host twin of :func:`_pacer_cost` — ONE cost formula. The
    ubiquitous acquire==1 case returns the host-precomputed exact int
    ``cost1_ms`` (bit-exact with the kernel, which reads the same
    column); generic acquire replicates the kernel's float32 math so a
    boundary-rounding divergence cannot admit on one plane and block on
    the other."""
    if acquire == 1:
        return int(cost1_ms)
    acq = np.float32(acquire)
    cnt = np.float32(max(float(count), 1e-9))
    return int(np.floor(np.float32(np.float32(acq / cnt) * np.float32(1000.0))
                        + np.float32(0.5)))


def mirror_shaping_decide(st, info, ts: int, acquire: int) -> Tuple[bool, int]:
    """One host decision + state update for a shaping-governed slot,
    mirroring :func:`_transition` step for step (syncToken refill,
    warm-up warning line, pacer cost/queueing). ``st`` is the mutable
    per-rule mirror record (failover._HostShaping: ``latest`` /
    ``stored`` / ``lastfill`` plus its pass counters); ``info`` is
    FlowIndex.mirror_shaping_info's static tuple. Returns
    ``(ok, wait_ms)``; state advances exactly when the kernel's would
    (a pacer grant advances ``latest`` even if a sibling slot later
    vetoes the entry — the caller sequences the stages to match)."""
    (_rule, behavior, count, maxq_ms, cost1_ms,
     warn, maxtok, slope, refill_thr) = info

    is_wu = behavior in (
        C.CONTROL_BEHAVIOR_WARM_UP, C.CONTROL_BEHAVIOR_WARM_UP_RATE_LIMITER
    )
    if is_wu:
        # --- syncToken (once per second; consumes prev-second pass),
        # in float32 elementwise like the kernel — a float64 floor can
        # land one token lower than the f32 one at product boundaries,
        # flipping the cold/warm classification between planes ---
        sec = ts - ts % 1000
        if sec > st.lastfill:
            prevq = float(st.pass_prev)
            refill_ok = st.stored < warn or (
                st.stored > warn and prevq < refill_thr
            )
            if refill_ok:
                elapsed = np.float32(sec - st.lastfill)
                refilled = np.floor(np.float32(
                    np.float32(st.stored)
                    + np.float32(np.float32(elapsed * np.float32(count))
                                 / np.float32(1000.0))
                ))
                st.stored = float(min(refilled, np.float32(maxtok)))
            st.stored = float(np.maximum(
                np.float32(st.stored) - np.float32(prevq), np.float32(0.0)
            ))
            st.lastfill = sec

    # --- warm-up admitted-QPS above the warning line (float32, like
    # the kernel — a float64 warning line could round the boundary
    # differently) ---
    above = np.float32(max(st.stored - warn, 0.0))
    inv = np.float32(
        above * np.float32(slope)
        + np.float32(1.0) / np.float32(max(float(count), 1e-9))
    )
    warning_qps = float(np.nextafter(np.float32(np.float32(1.0) / inv),
                                     np.float32(np.inf)))
    cold = st.stored >= warn

    if behavior == C.CONTROL_BEHAVIOR_WARM_UP:
        # passQps = floor(windowed pass / interval_sec), same rolling
        # LeapArray validity as the kernel's window_sums input.
        from sentinel_tpu.metrics import nodes as _ncfg

        interval_sec = _ncfg.SECOND_CFG.interval_ms / 1000.0
        passq = float(math.floor(st.passq(ts) / interval_sec))
        limit = warning_qps if cold else float(count)
        return passq + acquire <= limit, 0

    # --- pacer behaviors (RATE_LIMITER / WARM_UP_RATE_LIMITER) ---
    if acquire <= 0:
        return True, 0  # acquire<=0 always passes, no state change
    if count <= 0:
        return False, 0  # pacer_ok requires count > 0
    cost = mirror_pacer_cost(acquire, count, cost1_ms)
    if behavior == C.CONTROL_BEHAVIOR_WARM_UP_RATE_LIMITER and cold:
        cost = int(np.floor(
            np.float32(np.float32(np.float32(acquire)
                                  / np.float32(warning_qps))
                       * np.float32(1000.0))
            + np.float32(0.5)
        ))
    expected = st.latest + cost
    if expected <= ts:
        st.latest = ts  # immediate grant pins latest to NOW, not +=cost
        return True, 0
    wait = expected - ts
    if wait <= maxq_ms:
        st.latest += cost
        return True, int(wait)
    return False, 0


def mirror_pacer_bulk(
    latest0: int, count: float, maxq_ms: int, cost: int, ts: int,
    ranks: "np.ndarray",
) -> Tuple["np.ndarray", "np.ndarray", int]:
    """Closed-form host pacer for one bulk group's RATE_LIMITER slot —
    the host twin of the kernel's ``rounds == -1`` rank path for the
    plain pacer (ONE timestamp, ONE acquire >= 1 per row, plain
    RATE_LIMITER only — narrower than the kernel's, which also takes
    the warm-up behaviors; the speculative tier's predicate owns that
    check).
    ``ranks`` is the 1-indexed grant rank of each still-live row.
    Returns ``(ok, wait_ms, latest')``."""
    n = ranks.shape[0]
    if count <= 0:
        return (np.zeros(n, dtype=bool), np.zeros(n, dtype=np.int64),
                latest0)
    big = 1 << 30
    imm0 = latest0 + cost <= ts
    if cost > 0:
        g_imm = 1 + maxq_ms // cost
        g_queue = max((ts + maxq_ms - latest0) // cost, 0)
    else:
        g_imm = big
        g_queue = big if latest0 - ts <= maxq_ms else 0
    cap = g_imm if imm0 else g_queue
    ok = ranks <= cap
    if imm0:
        wait = (ranks.astype(np.int64) - 1) * cost
    else:
        wait = latest0 + ranks.astype(np.int64) * cost - ts
    wait = np.where(ok & (wait > 0), wait, 0)
    granted = int(min(int(ranks.max(initial=0)), cap))
    if granted > 0:
        latest = ts + (granted - 1) * cost if imm0 else latest0 + granted * cost
    else:
        latest = latest0
    return ok, wait, int(latest)
