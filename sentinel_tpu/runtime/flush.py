"""The flush kernel: one jitted step that applies a batch of ops.

Replaces the per-request slot-chain traversal (reference:
sentinel-core/.../slotchain/DefaultProcessorSlotChain.java +
slots/statistic/StatisticSlot.java:51-148 + slots/block/flow/
FlowSlot.java:141-172) with three vectorized phases:

1. **exits/traces** — scatter RT / success / exception / thread-release
   into the window tensors (StatisticSlot.exit semantics);
2. **admission** — evaluate every applicable flow rule for every entry
   against the *post-exit* statistics, with intra-batch sequencing
   resolved by per-node rank math (see below);
3. **entry accounting** — scatter pass / block / thread-acquire for
   admitted and rejected entries (StatisticSlot.entry semantics).

Intra-batch sequencing
----------------------
The reference processes requests one at a time: each admitted request
bumps the node's pass counter and is visible to the next request's
check (DefaultController.canPass, reference: controller/
DefaultController.java:49-75: pass iff ``curCount + acquire <= count``
with ``curCount = (int) passQps()`` or ``curThreadNum``). Batched, that
recurrence is resolved per *check node*: entries touching a node are
ordered by ``(ts, arrival index)`` and entry *i*'s check charges the sum
of earlier entries' acquire counts on that node — gated to slots whose
row the entry actually ACCOUNTS on (its own node rows), because a
RELATE slot reads the ref resource's node without the reference ever
bumping it from the guarded side. For a node whose entries share one
rule set and one acquire count — the overwhelmingly common case, and
everything the reference's own tests exercise — the admitted set is a
prefix and this is *exactly* the sequential outcome. A RELATE check
whose ref resource carries no rule reads the ref node's pre-flush
windows (no slots → no charge stream): the legal interleaving where
the guarded entries race ahead of co-flush ref traffic.

Within one flush, exits are applied before entry checks (a flush spans
a few ms at most; the reference's interleaving at sub-flush granularity
is not observable through 500 ms buckets).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.core import errors as E
from sentinel_tpu.metrics.events import MetricEvent
from sentinel_tpu.metrics import metric_array as ma
from sentinel_tpu.metrics import nodes as _ncfg
from sentinel_tpu.metrics.nodes import (
    MINUTE_CFG,
    StatsState,
    apply_run_updates,
    waiting_tokens,
)
from sentinel_tpu.models import constants as C
from sentinel_tpu.rules.degrade_table import (
    DegradeDynState,
    DegradeTableDevice,
    apply_probe_transitions,
    breaker_on_exits,
    breaker_try_pass,
)
from sentinel_tpu.rules.flow_table import FlowRuleDynState, FlowTableDevice
from sentinel_tpu.rules.param_table import ParamBatch, ParamDynState, run_param
from sentinel_tpu.rules.shaping import ShapingBatch, run_shaping
from sentinel_tpu.runtime.sketch import SketchBatch, SketchState, sketch_fold

# Plain int, not jnp.int32: creating a device array at import time would
# commit the JAX backend before callers can pick a platform (see
# utils/backend.py) — importing this library must never touch a device.
_I32_MAX = 2**31 - 1


class FlushBatch(NamedTuple):
    """One encoded batch of ops (padded; *_valid masks padding)."""

    now: jax.Array  # int32 scalar — flush time (ms rel epoch)
    # --- entries ---
    e_valid: jax.Array  # bool [N]
    e_ts: jax.Array  # int32 [N]
    e_acquire: jax.Array  # int32 [N]
    e_rows: jax.Array  # int32 [N, 4]: default, cluster, origin|-1, entry|-1
    e_rule_gid: jax.Array  # int32 [N, K], -1 = empty slot
    e_check_row: jax.Array  # int32 [N, K], -1 = rule passes trivially
    e_prio: jax.Array  # bool [N] — prioritized entries may borrow from
    # future windows when over threshold (entryWithPriority occupy path)
    e_auth_ok: jax.Array  # bool [N] — AuthoritySlot verdict (host-resolved
    # origin set membership, AuthorityRuleChecker.java:31-60)
    e_cluster_ok: jax.Array  # bool [N] — token-server verdict for
    # cluster-mode flow rules (BLOCKED → False; FlowRuleChecker.java:207)
    e_dgid: jax.Array  # int32 [N, KD] degrade-rule ids of the resource
    # --- exits and traces ---
    x_valid: jax.Array  # bool [M]
    x_ts: jax.Array  # int32 [M]
    x_count: jax.Array  # int32 [M] success delta (0 for trace ops)
    x_rows: jax.Array  # int32 [M, 4]
    x_rt: jax.Array  # int32 [M] RT delta (0 for trace ops)
    x_err: jax.Array  # int32 [M] exception delta
    x_thr: jax.Array  # int32 [M] thread delta (-1 exit, 0 trace)
    x_dgid: jax.Array  # int32 [M, KD] degrade-rule ids (breaker completion)
    # --- run tables (statistics accounting, nodes.apply_run_updates) ---
    # Index of the last entry (exit) of each run of entries that share
    # all four node rows and one ts, ascending, then -1 padding; under a
    # mesh, per shard in shard-local indices. None: one run per entry.
    e_run_end: Optional[jax.Array] = None  # int32 [G]
    x_run_end: Optional[jax.Array] = None  # int32 [GX]


class SystemDevice(NamedTuple):
    """Effective system-protection config + current host samples.

    Thresholds are +inf when disabled (a disabled dimension never
    blocks); load/cpu follow the reference's ">= 0 means set" flags
    (SystemRuleManager.java:298-353).
    """

    qps: jax.Array  # f32 scalar
    max_thread: jax.Array  # f32 scalar
    max_rt: jax.Array  # f32 scalar
    load_threshold: jax.Array  # f32 scalar (-1 disabled)
    cpu_threshold: jax.Array  # f32 scalar (-1 disabled)
    cur_load: jax.Array  # f32 scalar
    cur_cpu: jax.Array  # f32 scalar


class FlushResult(NamedTuple):
    admitted: jax.Array  # bool [N]
    reason: jax.Array  # int32 [N] — errors.PASS / BLOCK_*
    slot_ok: jax.Array  # bool [N, K] per-rule verdicts (block attribution)
    wait_ms: jax.Array  # int32 [N] shaping wait (rate-limiter; 0 for now)
    sys_type: jax.Array  # int32 [N] — system block dimension (see SYS_*)
    dslot_ok: jax.Array  # bool [N, KD] per-breaker verdicts
    flow_live: jax.Array  # bool [N] — passed every stage up to (excl.)
    # the breaker; the sharded path budgets on this (reference: FlowSlot
    # order −2000 grants tokens before DegradeSlot −1000 runs)
    occupied: jax.Array  # bool [N] — admitted by borrowing future-window
    # tokens (prioritized entries; PriorityWaitException semantics)
    occ_slot: jax.Array  # bool [N, K] — the specific slots that borrowed
    # (admission-gated); the sharded borrow budget charges these, not
    # the entry's other slots whose plain check passed
    # Telemetry blocked-weight top-K fold (static blk_topk > 0 only,
    # else None — NOT the statistics sketch tier, which lives in
    # runtime/sketch.py): the batch's top-K node rows by blocked
    # acquire weight — computed where the verdicts are so "what is
    # throttled right now" rides the existing coalesced device_get
    # instead of a second round-trip (the data-plane heavy-hitter
    # stance, arXiv:1611.04825).
    blk_rows: Optional[jax.Array] = None  # int32 [blk_topk] cluster rows
    blk_weight: Optional[jax.Array] = None  # int32 [blk_topk] blocked acquire sums


# System block dimension codes (limit types in SystemBlockException).
SYS_NONE = 0
SYS_QPS = 1
SYS_THREAD = 2
SYS_RT = 3
SYS_LOAD = 4
SYS_CPU = 5
SYS_TYPE_NAMES = {
    SYS_QPS: "qps",
    SYS_THREAD: "thread",
    SYS_RT: "rt",
    SYS_LOAD: "load",
    SYS_CPU: "cpu",
}


def make_probe_batch(now: int, n: int = 8, m: int = 8, k: int = 1,
                     kd: int = 1) -> FlushBatch:
    """An all-invalid batch for failover probe flushes (RECOVERING →
    HEALTHY re-entry, runtime/failover.py): every entry/exit slot is
    masked out, so the kernel exercises the full dispatch → execute →
    fetch round-trip — the thing a probe must prove works again —
    while admission state passes through untouched (only the
    time-based matured-borrow sweep runs, exactly as any flush at this
    ``now`` would). Shapes default to the smallest pow2-padded chunk
    so repeated probes share one jit cache entry."""
    return FlushBatch(
        now=jnp.int32(now),
        e_valid=jnp.zeros((n,), dtype=bool),
        e_ts=jnp.zeros((n,), dtype=jnp.int32),
        e_acquire=jnp.ones((n,), dtype=jnp.int32),
        e_rows=jnp.full((n, 4), -1, dtype=jnp.int32),
        e_rule_gid=jnp.full((n, k), -1, dtype=jnp.int32),
        e_check_row=jnp.full((n, k), -1, dtype=jnp.int32),
        e_prio=jnp.zeros((n,), dtype=bool),
        e_auth_ok=jnp.ones((n,), dtype=bool),
        e_cluster_ok=jnp.ones((n,), dtype=bool),
        e_dgid=jnp.full((n, kd), -1, dtype=jnp.int32),
        x_valid=jnp.zeros((m,), dtype=bool),
        x_ts=jnp.zeros((m,), dtype=jnp.int32),
        x_count=jnp.zeros((m,), dtype=jnp.int32),
        x_rows=jnp.full((m, 4), -1, dtype=jnp.int32),
        x_rt=jnp.zeros((m,), dtype=jnp.int32),
        x_err=jnp.zeros((m,), dtype=jnp.int32),
        x_thr=jnp.zeros((m,), dtype=jnp.int32),
        x_dgid=jnp.full((m, kd), -1, dtype=jnp.int32),
    )


def _exclusive_cumsum(x: jax.Array) -> jax.Array:
    return jnp.cumsum(x) - x


def segment_excl_cumsum(new_grp: jax.Array, contrib: jax.Array) -> jax.Array:
    """Exclusive running sum of ``contrib`` restarting at every group
    start (``new_grp`` marks segment boundaries in an already-sorted
    array). Requires ``contrib >= 0``: the cumsum is nondecreasing, so a
    running max over group-start snapshots recovers each segment's base.
    Shared by flow_admission's rank math and the sharded budget demotion
    (parallel/ici._demote_over_grant)."""
    excl = _exclusive_cumsum(contrib)
    grp_base = jax.lax.cummax(jnp.where(new_grp, excl, 0))
    return excl - grp_base


def _segment_consumed(new_grp: jax.Array, last_of_ent: jax.Array, contrib: jax.Array) -> jax.Array:
    """Per-position sum of *prior entries'* contributions within its group.

    An entry's slots are contiguous in the (node, ts, entry) sort order;
    placing each entry's contribution at its LAST slot makes the
    exclusive cumsum exclude the entry's own contribution at every one
    of its slots (a rule must not charge the entry's own acquire to
    itself) while later entries still see it.
    """
    return segment_excl_cumsum(new_grp, jnp.where(last_of_ent, contrib, 0))


def flow_admission(
    stats: StatsState,
    flow_dev: FlowTableDevice,
    batch: FlushBatch,
    live: Optional[jax.Array] = None,
    occupy_timeout_ms: int = 500,
    with_occupy: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Vectorized FlowRuleChecker + DefaultController (incl. occupy).

    Returns (slot_ok [N,K] bool, flow_pass [N] bool,
    pass_plus_consumed [N*K] int32 — the windowed pass sum plus the
    intra-batch charge per slot, which the shaping scan reuses as its
    ``passQps`` input, occupied [N] bool, occupy_wait_ms [N] int32,
    occ_slot [N,K] bool — which slots borrowed, occ_target [N,K] int32
    — each borrow's target window start). Borrows are NOT committed
    here: the caller gates :func:`commit_borrow_slab` on the entry's
    final admission, because a borrow by an entry vetoed by another
    slot must not leak into the slab. Slots whose behavior is not
    CONTROL_BEHAVIOR_DEFAULT are reported as ok here; their verdict is
    decided by the shaping scan (rules/shaping.py).

    The occupy branch (DefaultController.java:49-75 → StatisticNode.
    tryOccupyNext, node/StatisticNode.java:302-340): a prioritized
    QPS-grade entry that fails the plain check may borrow tokens from a
    future window if, after the windows between now and then expire,
    the borrowed total stays under the threshold and the wait is below
    ``occupy_timeout_ms`` (OccupyTimeoutProperty). Granted entries pass
    with ``wait_ms`` and their tokens land in the future slab; the
    intra-batch borrow charge among prioritized entries of one row is
    conservative (every earlier candidate charges, granted or not —
    same stance as the main rank math).
    """
    n, k = batch.e_rule_gid.shape
    r_rows = stats.n_rows
    nr = flow_dev.n_rules
    interval = _ncfg.SECOND_CFG.interval_ms
    wlen = _ncfg.SECOND_CFG.window_len_ms
    nb = _ncfg.SECOND_CFG.sample_count
    interval_sec = interval / 1000.0

    # Matured borrowed tokens are already in the buckets:
    # materialize_matured runs before admission in every flush path
    # (flush_step and the sharded two-pass), which the expiring-window
    # math in the occupy loop below also relies on.
    pass_sums = ma.window_sums(_ncfg.SECOND_CFG, stats.second, batch.now)[:, MetricEvent.PASS]

    gid_f = batch.e_rule_gid.reshape(-1)
    row_f = batch.e_check_row.reshape(-1)
    eidx_f = jnp.arange(n * k, dtype=jnp.int32) // k
    active = (gid_f >= 0) & (row_f >= 0) & batch.e_valid[eidx_f]

    # Sort slots by (node, ts, entry) so intra-batch charging is
    # ordered. ``pos`` subsumes the entry index as a tie-break key
    # (eidx == pos // k is nondecreasing in pos), so a 3-operand sort
    # with pos as the last KEY gives the identical — and now fully
    # deterministic — order with one less sort operand (TPU variadic
    # sorts cost per operand).
    row_key = jnp.where(active, row_f, jnp.int32(r_rows))
    ts_f = batch.e_ts[eidx_f]
    pos = jnp.arange(n * k, dtype=jnp.int32)
    rk_s, ts_s, pos_s = jax.lax.sort((row_key, ts_f, pos), num_keys=3)
    ei_s = pos_s // k

    active_s = active[pos_s]
    gid_s = jnp.clip(gid_f[pos_s], 0, nr - 1)
    acq_s = batch.e_acquire[ei_s]
    grade_s = flow_dev.grade[gid_s]
    count_s = flow_dev.count[gid_s]
    behavior_s = flow_dev.behavior[gid_s]

    ones = jnp.ones((1,), dtype=bool)
    new_grp = jnp.concatenate([ones, rk_s[1:] != rk_s[:-1]])
    last_of_ent = jnp.concatenate([rk_s[1:] != rk_s[:-1], ones]) | jnp.concatenate(
        [ei_s[1:] != ei_s[:-1], ones]
    )

    # A slot charges its row's intra-batch stream only when that row is
    # one the entry ACCOUNTS on (its own node rows, batch.e_rows).
    # RELATE/other-node slots read the ref resource's row but the
    # reference never bumps it from the guarded resource's entries
    # (FlowRuleChecker.java:96-165 — accounting stays on the entry's
    # node), so an ungated charge would over-block same-flush RELATE
    # streams (the round-3 documented deviation; measured ~8-10%
    # over-block on the RELATE pair in tests/test_conservatism.py).
    own_f = jnp.zeros((n * k,), dtype=bool)
    for j in range(4):
        own_f = own_f | (row_f == batch.e_rows[:, j][eidx_f])
    own_s = own_f[pos_s]

    consumed_acq = _segment_consumed(
        new_grp, last_of_ent, jnp.where(own_s, acq_s, 0)
    )
    consumed_cnt = _segment_consumed(
        new_grp, last_of_ent, jnp.where(own_s, 1, 0)
    )

    rk_c = jnp.clip(rk_s, 0, r_rows - 1)
    base_pass = pass_sums[rk_c]
    base_thread = stats.threads[rk_c]

    # DefaultController.avgUsedTokens: (int) passQps() for QPS grade,
    # curThreadNum for THREAD grade (DefaultController.java:73-78).
    qps_cur = jnp.floor((base_pass + consumed_acq).astype(jnp.float32) / interval_sec)
    thread_cur = (base_thread + consumed_cnt).astype(jnp.float32)
    cur = jnp.where(grade_s == C.FLOW_GRADE_QPS, qps_cur, thread_cur)

    # canPass: block iff curCount + acquireCount > count.
    ok = (cur + acq_s.astype(jnp.float32)) <= count_s
    is_default = behavior_s == C.CONTROL_BEHAVIOR_DEFAULT

    # ---- occupy branch (prioritized entries borrowing the future) ----
    # An entry the token server already BLOCKED never reaches the local
    # controller, so it must not borrow either (FlowRuleChecker.java:
    # 207-230: BLOCKED returns before passLocalCheck).
    # ``with_occupy=False`` (host knows the batch has no prioritized
    # entries) compiles all of this away — ``eligible`` would be all-
    # False anyway, so the specialization is exact.
    occ_slot = jnp.zeros((n * k,), dtype=bool)
    occ_wait = jnp.zeros((n * k,), dtype=jnp.int32)
    occ_target = jnp.zeros((n * k,), dtype=jnp.int32)
    if with_occupy:
        live_s = jnp.ones((n * k,), dtype=bool) if live is None else live[ei_s]
        eligible = (
            active_s
            & ~ok
            & is_default
            & live_s
            & batch.e_prio[ei_s]
            & batch.e_cluster_ok[ei_s]
            & (grade_s == C.FLOW_GRADE_QPS)
        )
        max_count = count_s * interval_sec
        waiting = waiting_tokens(stats, batch.now)[rk_c]
        # Conservative intra-batch borrow charge among this row's earlier
        # prioritized candidates (granted or not).
        borrow_charge = _segment_consumed(
            new_grp, last_of_ent, jnp.where(eligible, acq_s, 0)
        )
        cur_borrow = (waiting + borrow_charge).astype(jnp.float32)
        cur_pass = (base_pass + consumed_acq).astype(jnp.float32)
        acq_fs = acq_s.astype(jnp.float32)

        now_mod = batch.now % wlen
        # Static unroll over the (small) bucket count — tryOccupyNext's
        # while-loop over candidate future windows (StatisticNode.java:
        # 302-333). ``cur_pass`` is decremented by each expiring window's
        # pass as the unroll advances — the loop's cumulative
        # ``currentPass -= windowPass`` — so step *i*'s check sees the pass
        # count that will remain once windows 0..i have all expired.
        for i in range(nb):
            wait_i = i * wlen + wlen - now_mod  # tryOccupyNext waitInMs
            expiring_ws = batch.now - now_mod + wlen - interval + i * wlen
            bidx = (expiring_ws // wlen) % nb
            # Matured borrows are already IN the bucket: materialize_matured
            # runs before admission in every flush path, so the slab holds
            # only strictly-future windows and never overlaps expiring_ws.
            in_bucket = stats.second.window_start[rk_c, bidx] == expiring_ws
            win_pass = jnp.where(
                in_bucket, stats.second.counts[rk_c, bidx, MetricEvent.PASS], 0
            )
            cond = (
                eligible
                & (expiring_ws < batch.now)  # while (earliestTime < currentTime)
                & (wait_i < occupy_timeout_ms)
                & (cur_pass + cur_borrow + acq_fs - win_pass.astype(jnp.float32) <= max_count)
            )
            fresh = cond & ~occ_slot
            occ_wait = jnp.where(fresh, wait_i, occ_wait)
            occ_target = jnp.where(fresh, batch.now - now_mod + (i + 1) * wlen, occ_target)
            occ_slot = occ_slot | cond
            cur_pass = cur_pass - win_pass.astype(jnp.float32)

    ok = ok | occ_slot
    # Non-DEFAULT behaviors are decided by the shaping scan, not here.
    ok = ok | ~active_s | ~is_default

    # Per-entry occupy view: an entry is "occupied" if at least one of
    # its slots borrowed; its wait is the max over borrowing slots.
    drop_e = jnp.int32(n)
    e_scatter = jnp.where(occ_slot, ei_s, drop_e)
    occupied = (
        jnp.zeros((n,), dtype=bool).at[e_scatter].set(True, mode="drop")
    )
    occupy_wait = (
        jnp.zeros((n,), dtype=jnp.int32).at[e_scatter].max(occ_wait, mode="drop")
    )

    slot_ok = jnp.ones((n * k,), dtype=bool).at[pos_s].set(ok).reshape(n, k)
    flow_pass = slot_ok.all(axis=1)
    pass_plus_consumed = (
        jnp.zeros((n * k,), dtype=jnp.int32)
        .at[pos_s]
        .set((base_pass + consumed_acq).astype(jnp.int32))
    )
    occ_slot_nk = (
        jnp.zeros((n * k,), dtype=bool).at[pos_s].set(occ_slot).reshape(n, k)
    )
    occ_target_nk = (
        jnp.zeros((n * k,), dtype=jnp.int32).at[pos_s].set(occ_target).reshape(n, k)
    )
    return (
        slot_ok, flow_pass, pass_plus_consumed, occupied, occupy_wait,
        occ_slot_nk, occ_target_nk,
    )


def commit_borrow_slab(
    stats: StatsState,
    occ_slot: jax.Array,  # bool [N, K] — admission-gated borrow slots
    occ_target: jax.Array,  # int32 [N, K] — target window starts
    acquire: jax.Array,  # int32 [N]
    check_row: jax.Array,  # int32 [N, K]
) -> StatsState:
    """Write granted borrows into the future slab — addWaitingRequest ≙
    FutureBucketLeapArray currentWindow().addPass (StatisticNode.java:
    342-345), set-if-newer per bucket like the borrow array's
    reset-then-add roll.

    ``occ_slot`` must be gated on the entry's FINAL admission: the
    reference can never both block and borrow (PriorityWaitException
    aborts the chain with a pass), so a borrow by an entry vetoed by
    another slot (THREAD rule, shaping pacer) must not leak tokens into
    waiting()/future pass.
    """
    n, k = occ_slot.shape
    r_rows = stats.n_rows
    nb = _ncfg.SECOND_CFG.sample_count
    wlen = _ncfg.SECOND_CFG.window_len_ms

    occ_f = occ_slot.reshape(-1)
    tgt_f = occ_target.reshape(-1)
    eidx = jnp.arange(n * k, dtype=jnp.int32) // k
    acq_f = acquire[eidx]
    row_c = jnp.clip(check_row.reshape(-1), 0, r_rows - 1)

    tb = (tgt_f // wlen) % nb
    slab_key = jnp.where(occ_f, row_c * nb + tb.astype(jnp.int32), jnp.int32(r_rows * nb))
    sk_s, sp_s = jax.lax.sort((slab_key, jnp.arange(n * k, dtype=jnp.int32)), num_keys=1)
    ones = jnp.ones((1,), dtype=bool)
    s_new = jnp.concatenate([ones, sk_s[1:] != sk_s[:-1]])
    s_sid = jnp.cumsum(s_new.astype(jnp.int32)) - 1
    s_valid = occ_f[sp_s]
    s_ws = jnp.where(s_valid, tgt_f[sp_s], jnp.int32(_ncfg.SECOND_CFG.empty_ws))
    s_acq = jnp.where(s_valid, acq_f[sp_s], 0)
    seg_ws = jax.ops.segment_max(s_ws, s_sid, num_segments=n * k)
    contrib = s_valid & (s_ws == seg_ws[s_sid])
    seg_sum = jax.ops.segment_sum(jnp.where(contrib, s_acq, 0), s_sid, num_segments=n * k)
    u_valid = s_new & s_valid
    u_key = jnp.where(u_valid, sk_s, jnp.int32(r_rows * nb))
    u_row = jnp.minimum(u_key // nb, r_rows)
    u_b = u_key % nb
    u_ws = seg_ws[s_sid]
    u_sum = seg_sum[s_sid]
    old_ws = stats.future_ws[jnp.clip(u_row, 0, r_rows - 1), u_b]
    same = u_valid & (u_ws == old_ws)
    newer = u_valid & (u_ws > old_ws)
    drop_r = jnp.int32(r_rows)
    add_row = jnp.where(same, u_row, drop_r)
    set_row = jnp.where(newer, u_row, drop_r)
    fut_pass = stats.future_pass.at[add_row, u_b].add(u_sum, mode="drop", unique_indices=True)
    fut_pass = fut_pass.at[set_row, u_b].set(u_sum, mode="drop", unique_indices=True)
    fut_ws = stats.future_ws.at[set_row, u_b].set(u_ws, mode="drop", unique_indices=True)
    return stats._replace(future_pass=fut_pass, future_ws=fut_ws)


def system_check(
    stats: StatsState,
    sysdev: SystemDevice,
    batch: FlushBatch,
    live: jax.Array,  # bool [N]
) -> Tuple[jax.Array, jax.Array]:
    """Vectorized SystemRuleManager.checkSystem (SystemRuleManager.java:
    298-353) against the global inbound row (Constants.ENTRY_NODE, row 0).

    Only inbound (EntryType.IN) entries are checked. QPS/thread see the
    intra-batch charge of earlier inbound entries (same rank math and
    prefix-exactness caveats as flow_admission); RT / load / cpu use the
    flush-time snapshot, like the reference's once-a-second samples.

    Returns (ok [N], sys_type [N]).
    """
    n = batch.e_valid.shape[0]
    is_in = batch.e_rows[:, 3] >= 0
    checked = live & is_in

    sums = ma.window_sums(_ncfg.SECOND_CFG, stats.second, batch.now)[0]
    pass_sum = sums[MetricEvent.PASS].astype(jnp.float32)
    success = sums[MetricEvent.SUCCESS].astype(jnp.float32)
    rt_sum = sums[MetricEvent.RT].astype(jnp.float32)
    threads0 = stats.threads[0].astype(jnp.float32)
    interval_sec = _ncfg.SECOND_CFG.interval_ms / 1000.0

    # Intra-batch charge among inbound entries, in (ts, arrival) order.
    key = jnp.where(checked, 0, 1).astype(jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)
    key_s, ts_s, p_s = jax.lax.sort((key, batch.e_ts, pos), num_keys=3)
    acq_s = batch.e_acquire[p_s].astype(jnp.int32)
    in_grp = key_s == 0
    consumed_acq_s = jnp.where(in_grp, _exclusive_cumsum(jnp.where(in_grp, acq_s, 0)), 0)
    consumed_cnt_s = jnp.where(
        in_grp, _exclusive_cumsum(in_grp.astype(jnp.int32)), 0
    )
    consumed_acq = jnp.zeros((n,), dtype=jnp.int32).at[p_s].set(consumed_acq_s)
    consumed_cnt = jnp.zeros((n,), dtype=jnp.int32).at[p_s].set(consumed_cnt_s)

    acq = batch.e_acquire.astype(jnp.float32)
    cur_qps = (pass_sum + consumed_acq) / interval_sec
    qps_block = cur_qps + acq > sysdev.qps

    cur_thread = threads0 + consumed_cnt
    thread_block = cur_thread > sysdev.max_thread

    avg_rt = jnp.where(success > 0, rt_sum / jnp.maximum(success, 1.0), 0.0)
    rt_block = avg_rt > sysdev.max_rt

    # BBR (checkBbr): under high load, block unless
    # curThread <= maxSuccessQps * minRt / 1000 (or curThread <= 1).
    valid_b = (batch.now - stats.second.window_start[0]) <= _ncfg.SECOND_CFG.interval_ms
    succ_buckets = jnp.where(
        valid_b, stats.second.counts[0, :, MetricEvent.SUCCESS], 0
    )
    max_success_qps = (
        jnp.max(succ_buckets).astype(jnp.float32) * _ncfg.SECOND_CFG.sample_count
    )
    min_rt = jnp.min(
        jnp.where(valid_b, stats.second.min_rt[0], jnp.int32(_ncfg.SECOND_CFG.max_rt))
    ).astype(jnp.float32)
    load_on = (sysdev.load_threshold >= 0) & (sysdev.cur_load > sysdev.load_threshold)
    bbr_bad = (cur_thread > 1) & (cur_thread > max_success_qps * min_rt / 1000.0)
    load_block = load_on & bbr_bad

    cpu_block = (sysdev.cpu_threshold >= 0) & (sysdev.cur_cpu > sysdev.cpu_threshold)

    # First matching dimension wins, in the reference's check order.
    sys_type = jnp.full((n,), SYS_NONE, dtype=jnp.int32)
    for blocked, code in (
        (cpu_block, SYS_CPU),
        (load_block, SYS_LOAD),
        (rt_block, SYS_RT),
        (thread_block, SYS_THREAD),
        (qps_block, SYS_QPS),
    ):
        sys_type = jnp.where(checked & blocked, jnp.int32(code), sys_type)
    ok = sys_type == SYS_NONE
    return ok, sys_type


def _prev_second_pass(stats: StatsState, rows: jax.Array, ts: jax.Array) -> jax.Array:
    """Pass count of the previous 1s bucket of the minute window —
    ``node.previousPassQps()`` (reference: node/StatisticNode.java:185
    reads rollingCounterInMinute.previousWindowPass())."""
    wlen = MINUTE_CFG.window_len_ms  # 1000
    b = MINUTE_CFG.sample_count
    tprev = ts - wlen
    aligned = tprev - tprev % wlen
    idx = (tprev // wlen) % b
    rows_c = jnp.clip(rows, 0, stats.n_rows - 1)
    ws = stats.minute.window_start[rows_c, idx]
    val = stats.minute.counts[rows_c, idx, MetricEvent.PASS]
    return jnp.where(ws == aligned, val, 0)


def apply_exit_phase(
    stats: StatsState,
    ddev: DegradeTableDevice,
    ddyn: DegradeDynState,
    batch: FlushBatch,
    with_exits: bool = True,
    with_degrade: bool = True,
) -> Tuple[StatsState, DegradeDynState]:
    """Phases 1 + 1b: exits, traces and breaker completions.

    Split out of :func:`flush_step` so the sharded two-pass path can
    apply exits once and run admission twice against the post-exit
    statistics (parallel/ici.make_sharded_flush).

    ``with_exits=False`` (host knows the exit buffer is empty) /
    ``with_degrade=False`` (no degrade rules loaded) compile the
    corresponding scatters away — all masks would be all-False anyway,
    so the specialization is exact.
    """
    if not with_exits:
        return stats, ddyn

    # ---- phase 1: exits + traces (StatisticSlot.exit:148+) ----
    # min-RT tracked only for true exits (thread delta < 0) that carry
    # completions — not traces, and not the speculative tier's
    # thread-gauge compensation ops (count=0, thr=±n), whose rt=0 must
    # not write a bogus sample into the window minimum
    # (runtime/speculative.py reconciliation).
    x_rt_sample = jnp.where(
        (batch.x_thr < 0) & (batch.x_count > 0), batch.x_rt, _I32_MAX
    )
    stats = apply_run_updates(
        stats, batch.x_rows, batch.x_ts, batch.x_valid, batch.x_run_end,
        {MetricEvent.SUCCESS: batch.x_count, MetricEvent.RT: batch.x_rt,
         MetricEvent.EXCEPTION: batch.x_err},
        batch.x_thr, rt_sample=x_rt_sample,
    )

    # ---- phase 1b: breaker completions (DegradeSlot.exit:67-90) ----
    if with_degrade:
        ddyn = breaker_on_exits(
            ddev, ddyn, batch.x_dgid, batch.x_ts, batch.x_rt, batch.x_err, batch.x_valid
        )
    return stats, ddyn


def flush_entries(
    stats: StatsState,
    flow_dev: FlowTableDevice,
    flow_dyn: FlowRuleDynState,
    ddev: DegradeTableDevice,
    ddyn: DegradeDynState,
    pdyn: ParamDynState,
    sysdev: SystemDevice,
    batch: FlushBatch,
    shaping: Optional[ShapingBatch] = None,
    param: Optional[ParamBatch] = None,
    commit: bool = True,
    occupy_timeout_ms: int = 500,
    probe_allowed: Optional[jax.Array] = None,
    param_pre: Optional[Tuple[jax.Array, jax.Array]] = None,
    shaping_pre: Optional[Tuple[jax.Array, ...]] = None,
    with_occupy: bool = True,
    with_system: bool = True,
    with_degrade: bool = True,
    shaping_rounds: int = 0,
    param_rounds: int = 0,
    blk_topk: int = 0,
) -> Tuple[StatsState, FlowRuleDynState, DegradeDynState, ParamDynState, FlushResult]:
    """Phases 2-3: admission checks and (when ``commit``) accounting.

    ``blk_topk`` (static, 0 = off) folds a per-batch top-K
    blocked-resource summary into the result: blocked acquire weight is
    scatter-added per cluster-node row and the K heaviest rows ride the
    verdict fetch (``FlushResult.blk_rows``/``blk_weight``) — exact
    within the batch; the host merges batches into a space-saving
    summary (metrics/telemetry.py). Distinct from the statistics
    sketch tier's count-min fold, which ``flush_step`` threads
    separately (runtime/sketch.py).

    ``shaping_rounds`` / ``param_rounds`` (static) are the host-known
    execution modes (negative = closed-form rank paths with
    host-verified preconditions — for params, −S runs the segmented
    rank math with up to S timestamp sub-segments per value row;
    >0 = unrolled rounds, 0 = scan) — the host-known
    max-items-per-rule bounds selecting the vectorized rounds path of
    the serializing scans (rules/shaping.py, rules/param_table.py);
    0 = sequential lax.scan fallback.

    The ``with_*`` flags are host-known specializations — "no
    prioritized entries in this batch" / "no system rules configured" /
    "no degrade rules loaded" — that compile the corresponding stages
    away; each stage's masks would be all-pass anyway, so the flags
    never change a verdict, only the op count.

    ``commit=False`` evaluates the checks but skips every state write
    (pass/block scatters, breaker probe transitions, param thread
    gauges) — the demand-probe pass of the sharded path.
    ``probe_allowed`` (bool [ND]) restricts HALF_OPEN probe candidacy to
    elected breakers — the sharded path's cross-chip probe election.

    ``param_pre`` / ``shaping_pre`` carry verdicts precomputed OUTSIDE
    this call — the sharded path runs the serializing per-rule scans
    once on globally-replicated item batches (parallel/ici) and feeds
    each chip its local slice here; no pacer/param state is touched:
    * ``param_pre = (param_ok [N] bool, wait_param [N] int32)``
    * ``shaping_pre = (valid [S] bool, flat_pos [S], eidx [S],
      ok [S] bool, wait_ms [S] int32)`` with local positions.
    """
    n = batch.e_valid.shape[0]

    with jax.named_scope("sentinel/system"):
        # ---- phase 2a: authority (AuthoritySlot) ----
        live = batch.e_valid & batch.e_auth_ok

        # ---- phase 2b: system protection (SystemSlot) ----
        if with_system:
            sys_ok, sys_type = system_check(stats, sysdev, batch, live)
            live = live & sys_ok
        else:
            sys_ok = jnp.ones((n,), dtype=bool)
            sys_type = jnp.full((n,), SYS_NONE, dtype=jnp.int32)
    with jax.named_scope("sentinel/param"):
        # ---- phase 2b': hot-parameter rules (ParamFlowSlot, order -3000) ----
        wait_param = jnp.zeros((n,), dtype=jnp.int32)
        param_ok = jnp.ones((n,), dtype=bool)
        if param_pre is not None:
            param_ok, wait_param = param_pre
        elif param is not None:
            # Exits release per-value thread slots before this batch's checks
            # (ParamFlowStatisticExitCallback runs at completion).
            pr0 = pdyn.threads.shape[0]
            dec_rows = jnp.where(param.exit_rows >= 0, param.exit_rows, jnp.int32(pr0))
            pdyn = pdyn._replace(threads=pdyn.threads.at[dec_rows].add(-1, mode="drop"))
            param_live = param._replace(valid=param.valid & live[param.eidx])
            pdyn, p_ok_s, p_wait_s = run_param(pdyn, param_live, rounds=param_rounds)
            eidx_p = jnp.where(param_live.valid, param.eidx, jnp.int32(n))
            param_ok = param_ok.at[eidx_p].min(p_ok_s, mode="drop")
            wait_param = wait_param.at[eidx_p].max(p_wait_s, mode="drop")
        live = live & param_ok
    with jax.named_scope("sentinel/flow"):
        # ---- phase 2c: flow rules (FlowSlot / FlowRuleChecker) ----
        (
            slot_ok, flow_pass, pass_plus_consumed, occupied, occupy_wait,
            occ_slot_nk, occ_target_nk,
        ) = flow_admission(
            stats, flow_dev, batch, live, occupy_timeout_ms, with_occupy=with_occupy
        )
        occupied = occupied & live
        wait_ms = jnp.maximum(jnp.zeros((n,), dtype=jnp.int32), jnp.where(occupied, occupy_wait, 0))
    with jax.named_scope("sentinel/shaping"):
        if shaping is not None:
            # shaping controllers (rate-limiter / warm-up); entries already
            # blocked upstream must not advance pacer state.
            k = batch.e_rule_gid.shape[1]
            ppc_s = pass_plus_consumed[jnp.clip(shaping.flat_pos, 0, n * k - 1)]
            prev_s = _prev_second_pass(stats, shaping.row, shaping.ts)
            interval_sec = _ncfg.SECOND_CFG.interval_ms / 1000.0
            shaping_live = shaping._replace(valid=shaping.valid & live[shaping.eidx])
            flow_dyn, ok_s, wait_s = run_shaping(
                flow_dev, flow_dyn, shaping_live, ppc_s, prev_s, interval_sec,
                rounds=shaping_rounds,
            )
            flat_ok = slot_ok.reshape(-1)
            scatter_pos = jnp.where(
                shaping_live.valid, shaping.flat_pos, jnp.int32(flat_ok.shape[0])
            )
            # bool .min scatter == logical AND with existing verdicts.
            flat_ok = flat_ok.at[scatter_pos].min(ok_s, mode="drop")
            slot_ok = flat_ok.reshape(slot_ok.shape)
            flow_pass = slot_ok.all(axis=1)
            eidx_scatter = jnp.where(shaping_live.valid, shaping.eidx, jnp.int32(n))
            wait_ms = wait_ms.at[eidx_scatter].max(wait_s, mode="drop")
        if shaping_pre is not None:
            sp_valid, sp_flat, sp_eidx, sp_ok, sp_wait = shaping_pre
            flat_ok = slot_ok.reshape(-1)
            scatter_pos = jnp.where(sp_valid, sp_flat, jnp.int32(flat_ok.shape[0]))
            flat_ok = flat_ok.at[scatter_pos].min(sp_ok, mode="drop")
            slot_ok = flat_ok.reshape(slot_ok.shape)
            flow_pass = slot_ok.all(axis=1)
            eidx_scatter = jnp.where(sp_valid, sp_eidx, jnp.int32(n))
            wait_ms = wait_ms.at[eidx_scatter].max(sp_wait, mode="drop")
    with jax.named_scope("sentinel/flow"):
        flow_pass = flow_pass & batch.e_cluster_ok
        live2 = live & flow_pass
        wait_ms = jnp.where(live2, wait_ms, 0)
    with jax.named_scope("sentinel/degrade"):
        # ---- phase 2d: circuit breakers (DegradeSlot.entry) ----
        # Occupied entries bypass the breaker: the reference's
        # PriorityWaitException aborts the slot chain before DegradeSlot
        # (FlowSlot order −2000 < DegradeSlot −1000), and StatisticSlot
        # catches it to count only the thread acquire.
        occ_live = occupied & live2
        if with_degrade:
            dslot_ok, probe_slot = breaker_try_pass(
                ddev, ddyn, batch.e_dgid, batch.e_ts, live2 & ~occupied, probe_allowed
            )
            deg_pass = dslot_ok.all(axis=1) | occ_live
        else:
            dslot_ok = jnp.ones(batch.e_dgid.shape, dtype=bool)
            deg_pass = jnp.ones((n,), dtype=bool)

        admitted = live2 & deg_pass
        if commit and with_degrade:
            ddyn = apply_probe_transitions(
                ddyn, batch.e_dgid, probe_slot, admitted & ~occupied
            )
    with jax.named_scope("sentinel/account"):
        if commit and with_occupy:
            # Borrows persist only for entries that were finally admitted —
            # an entry vetoed by another slot never borrowed in the
            # reference (PriorityWaitException would have aborted the chain
            # with a pass before that slot could veto).
            stats = commit_borrow_slab(
                stats,
                occ_slot_nk & (admitted & occupied)[:, None],
                occ_target_nk,
                batch.e_acquire,
                batch.e_check_row,
            )
        wait_ms = jnp.maximum(wait_ms, jnp.where(admitted, wait_param, 0))

        # Per-value thread acquire (ParamFlowStatisticEntryCallback.onPass):
        # +1 per thread-grade param slot of an admitted entry.
        if param is not None and commit:
            pr = pdyn.threads.shape[0]
            inc_slot = (
                param.valid
                & (param.grade == C.FLOW_GRADE_THREAD)
                & admitted[param.eidx]
            )
            inc_rows = jnp.where(inc_slot, param.prow, jnp.int32(pr))
            pdyn = pdyn._replace(threads=pdyn.threads.at[inc_rows].add(1, mode="drop"))

        reason = jnp.full((n,), E.PASS, dtype=jnp.int32)
        reason = jnp.where(batch.e_valid & ~deg_pass, jnp.int32(E.BLOCK_DEGRADE), reason)
        reason = jnp.where(batch.e_valid & ~flow_pass, jnp.int32(E.BLOCK_FLOW), reason)
        reason = jnp.where(batch.e_valid & ~param_ok, jnp.int32(E.BLOCK_PARAM), reason)
        reason = jnp.where(batch.e_valid & ~sys_ok, jnp.int32(E.BLOCK_SYSTEM), reason)
        reason = jnp.where(
            batch.e_valid & ~batch.e_auth_ok, jnp.int32(E.BLOCK_AUTHORITY), reason
        )
        reason = jnp.where(admitted, jnp.int32(E.PASS), reason)

        # ---- phase 3: entry accounting (StatisticSlot.entry:64-120) ----
        if commit:
            # Occupied entries: thread acquire + OCCUPIED_PASS now; their
            # PASS materialises when the borrowed window becomes current
            # (StatisticSlot's PriorityWaitException branch + the
            # DefaultController addOccupiedPass call).
            occ = occupied & admitted
            acq = batch.e_acquire
            blocked = jnp.where(admitted, 0, acq)
            # Minute window: occupied entries count PASS + OCCUPIED_PASS
            # immediately (StatisticNode.addOccupiedPass writes both to
            # rollingCounterInMinute, node/StatisticNode.java:343-346); the
            # second window's pass arrives via the future slab instead.
            stats = apply_run_updates(
                stats, batch.e_rows, batch.e_ts, batch.e_valid, batch.e_run_end,
                {MetricEvent.PASS: jnp.where(admitted & ~occ, acq, 0),
                 MetricEvent.BLOCK: blocked},
                jnp.where(admitted, 1, 0),
                minute_deltas={
                    MetricEvent.PASS: jnp.where(admitted, acq, 0),
                    MetricEvent.BLOCK: blocked,
                    MetricEvent.OCCUPIED_PASS: jnp.where(occ, acq, 0),
                },
            )
    with jax.named_scope("sentinel/topk"):
        blk_rows = blk_weight = None
        if blk_topk > 0:
            # Blocked acquire weight per cluster-node row (e_rows[:, 1] is
            # the resource's ClusterNode — always >= 0 for valid entries).
            # Dense scatter-add into [n_rows + 1] with the last slot as the
            # dump row for non-blocked/padding entries, then one top_k:
            # O(n_rows) work against an already-O(n_rows)-sized state, and
            # exact within the batch.
            r_rows = stats.n_rows
            blocked_w = jnp.where(
                batch.e_valid & ~admitted, batch.e_acquire, 0
            ).astype(jnp.int32)
            crow = jnp.clip(batch.e_rows[:, 1], 0, r_rows - 1)
            scat = jnp.where(blocked_w > 0, crow, jnp.int32(r_rows))
            dense = jnp.zeros((r_rows + 1,), dtype=jnp.int32).at[scat].add(blocked_w)
            blk_weight, blk_rows = jax.lax.top_k(
                dense[:r_rows], min(blk_topk, r_rows)
            )
            blk_rows = blk_rows.astype(jnp.int32)
    result = FlushResult(
        admitted=admitted,
        reason=reason,
        slot_ok=slot_ok,
        wait_ms=wait_ms,
        sys_type=sys_type,
        dslot_ok=dslot_ok,
        flow_live=live2,
        occupied=occupied & admitted,
        occ_slot=occ_slot_nk & (admitted & occupied)[:, None],
        blk_rows=blk_rows,
        blk_weight=blk_weight,
    )
    return stats, flow_dyn, ddyn, pdyn, result


def flush_step(
    stats: StatsState,
    flow_dev: FlowTableDevice,
    flow_dyn: FlowRuleDynState,
    ddev: DegradeTableDevice,
    ddyn: DegradeDynState,
    pdyn: ParamDynState,
    sysdev: SystemDevice,
    batch: FlushBatch,
    shaping: Optional[ShapingBatch] = None,
    param: Optional[ParamBatch] = None,
    skstate: Optional[SketchState] = None,
    sk: Optional[SketchBatch] = None,
    occupy_timeout_ms: int = 500,
    with_occupy: bool = True,
    with_system: bool = True,
    with_degrade: bool = True,
    with_exits: bool = True,
    shaping_rounds: int = 0,
    param_rounds: int = 0,
    blk_topk: int = 0,
    sketch_decay: bool = False,
) -> Tuple[
    StatsState, FlowRuleDynState, DegradeDynState, ParamDynState,
    Optional[SketchState], FlushResult,
]:
    """Pure function: apply one batch.

    Check order matches the slot chain (DefaultSlotChainBuilder order:
    Authority −6000 → System −5000 → [ParamFlow −3000] → Flow −2000 →
    Degrade −1000); entries blocked by an earlier stage neither consume
    later stages' state (pacer time, breaker probes, param tokens) nor
    count toward their thresholds.

    The ``with_*`` flags are exact host-known specializations (see
    :func:`flush_entries`) — the engine passes "this batch has no
    prioritized entries / exits" and "no system/degrade rules are
    loaded" so plain DEFAULT-flow traffic compiles to a much leaner
    kernel. ``materialize_matured`` stays unconditional: the future
    slab may hold borrows committed by a *previous* (prioritized)
    flush.

    ``skstate``/``sk`` thread the statistics sketch tier through the
    kernel (runtime/sketch.py): count-min + candidate-table updates
    over the chunk's key-id stream, chained flush-to-flush with the
    same donated-state discipline as ``stats``. ``sketch_decay``
    (static) carries the once-per-window halving. With ``skstate``
    None the fold never traces — disabled is compile-identical to
    before the tier existed.
    """
    from sentinel_tpu.metrics.nodes import materialize_matured

    # Every op sits under one ``sentinel/<stage>`` scope (roll, exits,
    # system, param, flow, shaping, degrade, account, topk, sketch): the
    # op metadata a device trace attributes its time by. Scopes change
    # no computation, only the metadata and so the compile-cache key.
    with jax.named_scope("sentinel/roll"):
        stats = materialize_matured(stats, batch.now)
    with jax.named_scope("sentinel/exits"):
        stats, ddyn = apply_exit_phase(
            stats, ddev, ddyn, batch, with_exits=with_exits,
            with_degrade=with_degrade,
        )
    stats, flow_dyn, ddyn, pdyn, result = flush_entries(
        stats, flow_dev, flow_dyn, ddev, ddyn, pdyn, sysdev, batch, shaping, param,
        occupy_timeout_ms=occupy_timeout_ms,
        with_occupy=with_occupy, with_system=with_system, with_degrade=with_degrade,
        shaping_rounds=shaping_rounds, param_rounds=param_rounds,
        blk_topk=blk_topk,
    )
    if skstate is not None and sk is not None:
        with jax.named_scope("sentinel/sketch"):
            skstate = sketch_fold(skstate, sk, decay=sketch_decay)
    return stats, flow_dyn, ddyn, pdyn, skstate, result


# Four jit variants keyed by which optional batches are present; the
# engine picks per flush so DEFAULT-only traffic never pays for the
# shaping/param machinery. occupy_timeout_ms and the with_* stage
# flags are static (each used combination compiles once and is cached).
# ``win_key`` is the current second-window geometry (the engine passes
# ``_ncfg.SECOND_CFG``): the kernels read the module-global config at
# trace time, so a live window retune (SampleCountProperty /
# IntervalProperty parity) must key the jit cache on it — an
# interval-only change keeps every tensor shape and would otherwise
# silently hit the stale-constant cache entry. ``skstate``/``sk``
# (keyword-only, default None) thread the statistics sketch tier;
# ``skstate`` is donated by NAME so the count-min chain reuses its
# buffers flush-to-flush exactly like ``stats`` (a None skstate has no
# buffers — the donation is a no-op and the fold compiles away).
_STATIC_FLAGS = (
    "occupy_timeout_ms", "with_occupy", "with_system", "with_degrade", "with_exits",
    "shaping_rounds", "param_rounds", "blk_topk", "sketch_decay", "win_key",
)


@functools.partial(
    jax.jit, donate_argnums=(0, 4, 5), donate_argnames=("skstate",),
    static_argnames=_STATIC_FLAGS,
)
def flush_step_jit(
    stats, flow_dev, flow_dyn, ddev, ddyn, pdyn, sysdev, batch,
    skstate=None, sk=None, occupy_timeout_ms=500,
    with_occupy=True, with_system=True, with_degrade=True, with_exits=True,
    shaping_rounds=0, param_rounds=0, blk_topk=0, sketch_decay=False,
    win_key=None,
):
    return flush_step(
        stats, flow_dev, flow_dyn, ddev, ddyn, pdyn, sysdev, batch,
        skstate=skstate, sk=sk,
        occupy_timeout_ms=occupy_timeout_ms,
        with_occupy=with_occupy, with_system=with_system,
        with_degrade=with_degrade, with_exits=with_exits,
        shaping_rounds=shaping_rounds, param_rounds=param_rounds,
        blk_topk=blk_topk, sketch_decay=sketch_decay,
    )


@functools.partial(
    jax.jit, donate_argnums=(0, 2, 4, 5), donate_argnames=("skstate",),
    static_argnames=_STATIC_FLAGS,
)
def flush_step_shaping_jit(
    stats, flow_dev, flow_dyn, ddev, ddyn, pdyn, sysdev, batch, shaping,
    skstate=None, sk=None, occupy_timeout_ms=500,
    with_occupy=True, with_system=True, with_degrade=True, with_exits=True,
    shaping_rounds=0, param_rounds=0, blk_topk=0, sketch_decay=False,
    win_key=None,
):
    return flush_step(
        stats, flow_dev, flow_dyn, ddev, ddyn, pdyn, sysdev, batch, shaping,
        skstate=skstate, sk=sk,
        occupy_timeout_ms=occupy_timeout_ms,
        with_occupy=with_occupy, with_system=with_system,
        with_degrade=with_degrade, with_exits=with_exits,
        shaping_rounds=shaping_rounds, param_rounds=param_rounds,
        blk_topk=blk_topk, sketch_decay=sketch_decay,
    )


@functools.partial(
    jax.jit, donate_argnums=(0, 4, 5), donate_argnames=("skstate",),
    static_argnames=_STATIC_FLAGS,
)
def flush_step_param_jit(
    stats, flow_dev, flow_dyn, ddev, ddyn, pdyn, sysdev, batch, param,
    skstate=None, sk=None, occupy_timeout_ms=500,
    with_occupy=True, with_system=True, with_degrade=True, with_exits=True,
    shaping_rounds=0, param_rounds=0, blk_topk=0, sketch_decay=False,
    win_key=None,
):
    return flush_step(
        stats, flow_dev, flow_dyn, ddev, ddyn, pdyn, sysdev, batch, None, param,
        skstate=skstate, sk=sk,
        occupy_timeout_ms=occupy_timeout_ms,
        with_occupy=with_occupy, with_system=with_system,
        with_degrade=with_degrade, with_exits=with_exits,
        shaping_rounds=shaping_rounds, param_rounds=param_rounds,
        blk_topk=blk_topk, sketch_decay=sketch_decay,
    )


@functools.partial(
    jax.jit, donate_argnums=(0, 2, 4, 5), donate_argnames=("skstate",),
    static_argnames=_STATIC_FLAGS,
)
def flush_step_full_jit(
    stats, flow_dev, flow_dyn, ddev, ddyn, pdyn, sysdev, batch, shaping, param,
    skstate=None, sk=None, occupy_timeout_ms=500,
    with_occupy=True, with_system=True, with_degrade=True, with_exits=True,
    shaping_rounds=0, param_rounds=0, blk_topk=0, sketch_decay=False,
    win_key=None,
):
    return flush_step(
        stats, flow_dev, flow_dyn, ddev, ddyn, pdyn, sysdev, batch, shaping, param,
        skstate=skstate, sk=sk,
        occupy_timeout_ms=occupy_timeout_ms,
        with_occupy=with_occupy, with_system=with_system,
        with_degrade=with_degrade, with_exits=with_exits,
        shaping_rounds=shaping_rounds, param_rounds=param_rounds,
        blk_topk=blk_topk, sketch_decay=sketch_decay,
    )
