"""ICI-collective building blocks for multi-chip flow control.

This module is the TPU-native replacement for the reference's
token-server RPC (SURVEY.md §5 "Distributed communication backend"):
instead of every app instance RPCing a single Netty server that owns the
global ClusterMetric (reference: sentinel-cluster-server-default/.../
flow/ClusterFlowChecker.java:36-118), every chip holds replicated
counter tensors, processes its shard of the entry batch, and the merged
global state is reconstructed with ``psum``/``pmax``/``pmin`` inside the
jitted step — one ICI all-reduce instead of a network round-trip.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.metrics.metric_array import MetricArrayState
from sentinel_tpu.metrics.nodes import StatsState


def merge_window_across(old: MetricArrayState, new: MetricArrayState, axis: str) -> MetricArrayState:
    """Rollover-aware all-reduce of one window array.

    A device that rolled a bucket to a newer window zeroed the old
    counts, so a naive delta-psum would subtract the old counts once per
    rolling device. Instead: the merged window start is the max across
    devices; only devices whose final window matches it contribute
    (their counts minus the shared base, which is the old counts iff the
    old window already was the merged one).
    """
    g_ws = jax.lax.pmax(new.window_start, axis)
    old_cur = (old.window_start == g_ws)[:, :, None]
    new_cur = (new.window_start == g_ws)[:, :, None]
    base = jnp.where(old_cur, old.counts, 0)
    contrib = jnp.where(new_cur, new.counts - base, 0)
    counts = base + jax.lax.psum(contrib, axis)
    big = jnp.int32(2**31 - 1)
    min_rt = jnp.minimum(
        jnp.where(old.window_start == g_ws, old.min_rt, big),
        jax.lax.pmin(jnp.where(new.window_start == g_ws, new.min_rt, big), axis),
    )
    return MetricArrayState(counts=counts, min_rt=min_rt, window_start=g_ws)


def merge_stats_across(old: StatsState, new: StatsState, axis: str) -> StatsState:
    """All-reduce the full stats family (second + minute + thread gauge
    + occupy future slab)."""
    # Future slab: same rollover-aware merge as the window arrays (max
    # window start wins; only chips whose final ws matches contribute).
    g_ws = jax.lax.pmax(new.future_ws, axis)
    old_cur = old.future_ws == g_ws
    new_cur = new.future_ws == g_ws
    base = jnp.where(old_cur, old.future_pass, 0)
    contrib = jnp.where(new_cur, new.future_pass - base, 0)
    fut_pass = base + jax.lax.psum(contrib, axis)
    return StatsState(
        second=merge_window_across(old.second, new.second, axis),
        minute=merge_window_across(old.minute, new.minute, axis),
        threads=old.threads + jax.lax.psum(new.threads - old.threads, axis),
        future_pass=fut_pass,
        future_ws=g_ws,
    )


def cluster_allocate(
    axis: str, demand: jax.Array, capacity: jax.Array, *, with_before: bool = False
):
    """Greedy chip-indexed allocation of global capacity.

    Each chip has ``demand`` admission candidates for a cluster rule;
    the global remaining capacity is split by exclusive prefix over the
    mesh axis: chip i may admit ``min(demand_i, capacity -
    sum_{j<i} demand_j)``. Deterministic and conserving — the analog of
    the token server serializing client requests in arrival order
    (arrival order there is nondeterministic; chip index here is).
    Shapes: demand/capacity broadcastable; returns the per-chip grant,
    or ``(grant, before)`` when ``with_before`` (``before`` = the
    exclusive demand prefix, i.e. this chip's starting offset into the
    global budget).
    """
    idx = jax.lax.axis_index(axis)
    n = jax.lax.axis_size(axis)
    # Exclusive prefix sum over the axis via one-hot matmul-free trick:
    # gather all demands, mask those with lower index.
    all_d = jax.lax.all_gather(demand, axis)  # [n, ...]
    ranks = jnp.arange(n)
    shape = (n,) + (1,) * (all_d.ndim - 1)
    before = jnp.sum(jnp.where(ranks.reshape(shape) < idx, all_d, 0), axis=0)
    left = jnp.maximum(capacity - before, 0)
    grant = jnp.minimum(demand, left)
    return (grant, before) if with_before else grant


def batch_partition_specs(axis: str = "data"):
    """PartitionSpec pytree for a FlushBatch: entries/exits sharded over
    the mesh, scalars replicated. The run tables split into equal
    per-shard blocks of shard-local indices (Engine._encode_staging cuts
    runs at shard boundaries)."""
    from jax.sharding import PartitionSpec as P

    from sentinel_tpu.runtime.flush import FlushBatch

    return FlushBatch(
        now=P(),
        e_valid=P(axis),
        e_ts=P(axis),
        e_acquire=P(axis),
        e_rows=P(axis, None),
        e_rule_gid=P(axis, None),
        e_check_row=P(axis, None),
        e_prio=P(axis),
        e_auth_ok=P(axis),
        e_cluster_ok=P(axis),
        e_dgid=P(axis, None),
        x_valid=P(axis),
        x_ts=P(axis),
        x_count=P(axis),
        x_rows=P(axis, None),
        x_rt=P(axis),
        x_err=P(axis),
        x_thr=P(axis),
        x_dgid=P(axis, None),
        e_run_end=P(axis),
        x_run_end=P(axis),
    )


def _split_and_spend(
    axis: str, batch, r_rows: int, mask: jax.Array, unit_f: jax.Array,
    cap_slot: jax.Array
) -> jax.Array:
    """The shared mesh-budget recipe behind both demotion passes, keyed
    per CHECK ROW — the same key the single-chip rank math segments on
    (flow_admission sorts slots by ``(row, ts, arrival)`` and charges
    per row), so the sharded budget is exact wherever single-chip
    batching is:

    * per-chip demand = sum of participating slots' ``unit_f`` per row;
    * the cross-chip exclusive demand prefix (``before``) offsets each
      chip into the global per-row charge stream, the deterministic
      analog of the token server serializing grants (reference:
      ClusterFlowChecker.java:55-112);
    * within the chip the row's stream is spent in (ts, arrival) order
      with the per-slot admission check ``before + prefix + acquire ≤
      cap_slot`` — ``cap_slot`` stays per-SLOT, so two rules sharing a
      row each enforce their own count against the shared row charge,
      exactly like the single-chip ``(cur + acquire) <= count_s``.

    Earlier rounds keyed this per rule with a MIN cap over the rule's
    rows, which over-blocked origin-split topologies (a rule checked
    against several origin rows was capped at its most-loaded row);
    row keying removes that deviation. Returns the per-entry keep mask
    (an entry is kept iff every participating slot fits)."""
    from sentinel_tpu.runtime.flush import segment_excl_cumsum

    n, k = batch.e_rule_gid.shape
    row_f = batch.e_check_row.reshape(-1)
    eidx_f = jnp.arange(n * k, dtype=jnp.int32) // k
    acq_f = batch.e_acquire[eidx_f]
    row_c = jnp.clip(row_f, 0, r_rows - 1)

    demand = (
        jnp.zeros((r_rows,), dtype=jnp.int32)
        .at[jnp.where(mask, row_c, r_rows)]
        .add(jnp.where(mask, unit_f, 0), mode="drop")
    )
    # Exclusive cross-chip prefix of per-row demand: chip i's offset
    # into each row's global charge stream.
    idx = jax.lax.axis_index(axis)
    nax = jax.lax.axis_size(axis)
    all_d = jax.lax.all_gather(demand, axis)  # [nax, r_rows]
    before = jnp.sum(
        jnp.where(jnp.arange(nax).reshape(nax, 1) < idx, all_d, 0), axis=0
    )

    # Spend in (ts, arrival) order within each row segment. Per-slot
    # admission = the reference's sequential check run at this chip's
    # offset into the global stream. Since unit ≤ acquire, kept spend
    # per chip stays ≤ its grant, so the total across the mesh never
    # exceeds any slot's cap.
    pos = jnp.arange(n * k, dtype=jnp.int32)
    row_key = jnp.where(mask, row_c, jnp.int32(r_rows))
    ts_f = batch.e_ts[eidx_f]
    key_s, ts_s, pos_s = jax.lax.sort((row_key, ts_f, pos), num_keys=3)
    acq_s = acq_f[pos_s]
    m_s = mask[pos_s]
    ones = jnp.ones((1,), dtype=bool)
    new_grp = jnp.concatenate([ones, key_s[1:] != key_s[:-1]])
    prefix = segment_excl_cumsum(new_grp, jnp.where(m_s, unit_f[pos_s], 0))
    key_c = jnp.clip(key_s, 0, r_rows - 1)
    keep_s = ~m_s | ((before[key_c] + prefix + acq_s) <= cap_slot[pos_s])
    keep_slot = jnp.ones((n * k,), dtype=bool).at[pos_s].set(keep_s)
    return keep_slot.reshape(n, k).all(axis=1)


def _demote_over_grant(
    axis: str, stats_x, flow_dev, batch, flow_live: jax.Array
) -> jax.Array:
    """Cap each DEFAULT-behavior flow rule's admissions at the globally
    allocated grant; returns the per-entry keep mask.

    Budgeting happens at the FLOW level (``flow_live`` = passed every
    stage up to the breaker): the reference's FlowSlot (order −2000)
    grants tokens before DegradeSlot (−1000) runs, and budgeting on the
    post-breaker set would let a demoted HALF_OPEN probe shift to a
    different, un-budgeted entry in pass 2.

    Per rule: each chip's demand is the budget-unit sum of its
    flow-passing entries; ``cluster_allocate`` splits the global
    remaining capacity by chip-indexed exclusive prefix (the
    deterministic analog of the token server serializing grants,
    reference: ClusterFlowChecker.java:55-112); within a chip the grant
    is spent in (ts, arrival) order and the remainder demoted.

    Budget units follow DefaultController.canPass (reference:
    controller/DefaultController.java:49-78): QPS grade spends
    ``acquire`` per entry against ``count − floor(passQps)``; THREAD
    grade spends 1 per entry (the gauge rises by 1 regardless of
    acquire) against ``count − curThreadNum``, with the per-entry
    admission check ``prefix + acquire ≤ grant`` in both grades.

    ``stats_x`` is the GLOBAL post-exit view (the sharded step merges
    exit deltas across the mesh before any admission), so thread
    capacity reads directly from its gauge. Rows are per-slot in
    general (limitApp×strategy); budgets are conserved per CHECK ROW
    with per-slot caps (see _split_and_spend), matching the single-chip
    row-keyed rank math — exact for origin-split topologies too.
    """
    from sentinel_tpu.metrics import metric_array as ma
    from sentinel_tpu.metrics.events import MetricEvent
    from sentinel_tpu.metrics.nodes import SECOND_CFG
    from sentinel_tpu.models import constants as C

    n, k = batch.e_rule_gid.shape
    nr = flow_dev.n_rules
    r_rows = stats_x.n_rows
    interval_sec = SECOND_CFG.interval_ms / 1000.0

    gid_f = batch.e_rule_gid.reshape(-1)
    row_f = batch.e_check_row.reshape(-1)
    eidx_f = jnp.arange(n * k, dtype=jnp.int32) // k
    gid_c = jnp.clip(gid_f, 0, nr - 1)
    is_qps = flow_dev.grade[gid_c] == C.FLOW_GRADE_QPS
    # Only DEFAULT-behavior slots consume budget here; shaping slots are
    # governed by their pacer scan, not the windowed count.
    constrained = (
        (gid_f >= 0)
        & (row_f >= 0)
        & batch.e_valid[eidx_f]
        & flow_live[eidx_f]
        & (flow_dev.behavior[gid_c] == C.CONTROL_BEHAVIOR_DEFAULT)
    )
    acq_f = batch.e_acquire[eidx_f]
    unit_f = jnp.where(is_qps, acq_f, 1)

    # --- global remaining capacity per rule: the MIN over every row the
    # rule is checked against in this batch (pass counts are replicated;
    # thread gauges are reconstructed globally). A per-(rule,row) budget
    # would be exact; per-rule min is conservative for origin-split
    # topologies and exact for the dominant single-row case. ---
    pass_sums = ma.window_sums(SECOND_CFG, stats_x.second, batch.now)[:, MetricEvent.PASS]
    threads_global = stats_x.threads
    row_fc = jnp.clip(row_f, 0, r_rows - 1)
    base_qps_slot = jnp.floor(pass_sums[row_fc].astype(jnp.float32) / interval_sec)
    base_thr_slot = threads_global[row_fc].astype(jnp.float32)
    base_slot = jnp.where(is_qps, base_qps_slot, base_thr_slot)
    cap_slot = jnp.maximum(
        jnp.floor(flow_dev.count[gid_c]) - base_slot, 0.0
    ).astype(jnp.int32)
    return _split_and_spend(axis, batch, r_rows, constrained, unit_f, cap_slot)


def _demote_over_borrow(
    axis, stats_pre, flow_dev, batch, occ_slot: jax.Array
) -> jax.Array:
    """Cap occupy borrows at the global borrow budget; returns the
    per-entry keep mask over pass-1-borrowing entries.

    A chip-local occupy grant honors ``waiting + borrow ≤ maxCount``
    only against its own slab writes (StatisticNode.tryOccupyNext's
    ``currentBorrow`` bound, reference: node/StatisticNode.java:305-307)
    — n chips could each borrow up to the full budget. Same recipe as
    ``_demote_over_grant``: per rule, demand = the borrowing slots'
    acquire units (``occ_slot`` from pass 1 — only slots that actually
    borrowed charge the budget, not the entry's other slots whose plain
    check passed), capacity = maxCount − already-waiting tokens
    (replicated pre-flush state, so identical on every chip), split by
    chip-indexed exclusive prefix, spent in (ts, arrival) order within
    the chip.
    """
    from sentinel_tpu.metrics.nodes import SECOND_CFG, waiting_tokens

    n, k = batch.e_rule_gid.shape
    nr = flow_dev.n_rules
    r_rows = stats_pre.n_rows
    interval_sec = SECOND_CFG.interval_ms / 1000.0

    gid_f = batch.e_rule_gid.reshape(-1)
    row_f = batch.e_check_row.reshape(-1)
    eidx_f = jnp.arange(n * k, dtype=jnp.int32) // k
    gid_c = jnp.clip(gid_f, 0, nr - 1)
    borrower = occ_slot.reshape(-1)
    acq_f = batch.e_acquire[eidx_f]

    waiting = waiting_tokens(stats_pre, batch.now)
    row_fc = jnp.clip(row_f, 0, r_rows - 1)
    max_count = jnp.floor(flow_dev.count[gid_c] * interval_sec)
    cap_slot = jnp.maximum(
        max_count - waiting[row_fc].astype(jnp.float32), 0.0
    ).astype(jnp.int32)
    return _split_and_spend(axis, batch, r_rows, borrower, acq_f, cap_slot)


def _global_param_scan(axis, pdyn, param_g, live_up, n_local, rounds=0):
    """Run the hot-param scan once per chip on the GLOBALLY-replicated
    item batch — every chip computes the identical new param state (no
    merge needed), and the scan sees the global (value-row, ts)-ordered
    stream, so token-bucket/throttle/thread semantics are exactly the
    single-chip ones.

    Item liveness (auth/system verdicts of the item's entry) lives on
    the entry's owner chip only; one psum ORs the owner bits so every
    chip gates the scan identically. Returns (new_pdyn, per-local-entry
    (param_ok, wait_param), owner mask, local entry idx).
    """
    from sentinel_tpu.rules.param_table import run_param

    c = jax.lax.axis_index(axis)
    owner = (param_g.eidx // n_local) == c
    lidx = jnp.clip(param_g.eidx % n_local, 0, n_local - 1)
    # Exits release per-value thread slots first (replicated op —
    # identical on every chip).
    pr0 = pdyn.threads.shape[0]
    dec_rows = jnp.where(param_g.exit_rows >= 0, param_g.exit_rows, jnp.int32(pr0))
    pdyn = pdyn._replace(threads=pdyn.threads.at[dec_rows].add(-1, mode="drop"))
    live_bit = owner & live_up[lidx]
    item_live = jax.lax.psum(live_bit.astype(jnp.int32), axis) > 0
    pg_live = param_g._replace(valid=param_g.valid & item_live)
    new_pdyn, p_ok, p_wait = run_param(pdyn, pg_live, rounds=rounds)
    drop = jnp.int32(n_local)
    sc = jnp.where(pg_live.valid & owner, lidx, drop)
    param_ok_local = jnp.ones((n_local,), dtype=bool).at[sc].min(p_ok, mode="drop")
    wait_local = jnp.zeros((n_local,), dtype=jnp.int32).at[sc].max(p_wait, mode="drop")
    return new_pdyn, (param_ok_local, wait_local), owner, lidx


def _global_shaping_scan(
    axis, stats_x, flow_dev, flow_dyn, shaping_g, batch, live_up, n_local, k, rounds=0
):
    """Run the shaping pacer scan once per chip on the GLOBALLY-
    replicated item batch: replicated ``flow_dyn`` in, identical new
    ``flow_dyn`` out on every chip, and the ``lax.scan`` sees the global
    (rule, ts)-ordered request stream — exactly the single-chip pacer
    semantics (a chip-local scan would let every chip pace its own
    sub-stream and admit n× the configured rate).

    ``passQps`` for the warm-up math is rebuilt deterministically from
    the replicated post-exit windows plus the intra-batch charge among
    the global shaping items themselves — charged over ALL valid items
    regardless of upstream liveness, like flow_admission's
    liveness-unmasked ``consumed_acq`` on the single-chip path. (The
    single-chip charge is own-row-gated for RELATE slots; a RELATE +
    warm-up combination on the mesh keeps the ungated charge here —
    one-sided conservative in that corner.) Charges from
    co-row DEFAULT slots within this same flush are not visible to it
    (they land in the windows by the next flush) — a within-one-flush
    optimism that only matters when a warm-up rule shares its check row
    with a DEFAULT rule matching a *different* entry set.
    """
    from sentinel_tpu.metrics import metric_array as ma
    from sentinel_tpu.metrics.events import MetricEvent
    from sentinel_tpu.metrics.nodes import SECOND_CFG
    from sentinel_tpu.runtime.flush import (
        _prev_second_pass,
        _segment_consumed,
    )
    from sentinel_tpu.rules.shaping import run_shaping

    c = jax.lax.axis_index(axis)
    owner = (shaping_g.eidx // n_local) == c
    lidx = jnp.clip(shaping_g.eidx % n_local, 0, n_local - 1)
    live_bit = owner & live_up[lidx]
    item_live = jax.lax.psum(live_bit.astype(jnp.int32), axis) > 0
    sg_live = shaping_g._replace(valid=shaping_g.valid & item_live)

    s = sg_live.valid.shape[0]
    r_rows = stats_x.n_rows
    pass_sums = ma.window_sums(SECOND_CFG, stats_x.second, batch.now)[:, MetricEvent.PASS]
    # Charge population = every valid item, NOT gated by liveness: the
    # single-chip pass_plus_consumed charges upstream-blocked entries
    # too (flow_admission's consumed_acq is unmasked), and parity with
    # it is the contract. Only the scan's state advance is live-gated.
    rkey = jnp.where(shaping_g.valid, shaping_g.row, jnp.int32(r_rows))
    pos = jnp.arange(s, dtype=jnp.int32)
    # Global items concatenate per chip in eidx order, so pos as the
    # last key reproduces (row, ts, eidx) with one less sort operand.
    rk_s, _, p_s = jax.lax.sort((rkey, shaping_g.ts, pos), num_keys=3)
    ei_s = shaping_g.eidx[p_s]
    ones = jnp.ones((1,), dtype=bool)
    new_grp = jnp.concatenate([ones, rk_s[1:] != rk_s[:-1]])
    last_of_ent = jnp.concatenate([rk_s[1:] != rk_s[:-1], ones]) | jnp.concatenate(
        [ei_s[1:] != ei_s[:-1], ones]
    )
    valid_sorted = shaping_g.valid[p_s]
    consumed = _segment_consumed(
        new_grp, last_of_ent, jnp.where(valid_sorted, shaping_g.acquire[p_s], 0)
    )
    base = pass_sums[jnp.clip(rk_s, 0, r_rows - 1)]
    ppc = (
        jnp.zeros((s,), dtype=jnp.int32)
        .at[p_s]
        .set((base + consumed).astype(jnp.int32))
    )
    prev = _prev_second_pass(stats_x, shaping_g.row, shaping_g.ts)
    interval_sec = SECOND_CFG.interval_ms / 1000.0
    new_fdyn, ok_s, wait_s = run_shaping(
        flow_dev, flow_dyn, sg_live, ppc, prev, interval_sec, rounds=rounds
    )
    lflat = lidx * k + shaping_g.flat_pos % k
    shaping_pre = (sg_live.valid & owner, lflat, lidx, ok_s, wait_s)
    return new_fdyn, shaping_pre


def make_sharded_flush(
    mesh,
    axis: str = "data",
    occupy_timeout_ms: int = 500,
    with_shaping: bool = False,
    with_param: bool = False,
    shaping_rounds: int = 0,
    param_rounds: int = 0,
):
    """The full batched step over an n-device mesh.

    Entries and exits are data-parallel across chips; counter tensors
    and rule tables are replicated; after each local flush the window
    deltas and breaker state are all-reduced so every chip ends the step
    with the identical global state.

    Flow budgets are conserved across the mesh in two passes: pass 1
    computes each chip's locally-admitted demand, ``cluster_allocate``
    splits the global remaining capacity deterministically, over-grant
    admissions are demoted to BLOCK via the batch's ``e_cluster_ok``
    channel, and pass 2 re-runs the step so accounting, breaker probes
    and verdicts all see the demotions coherently. This replaces the
    reference's token-server RPC (one all-gather over ICI instead of a
    Netty round-trip per request).

    ``with_shaping`` / ``with_param`` extend the signature with a
    ShapingBatch / ParamBatch holding the GLOBAL item set (replicated on
    every chip, ``eidx``/``flat_pos`` in global coordinates): the
    serializing per-rule scans run once per chip on replicated state —
    identical results everywhere, global-stream ordering — and each chip
    scatters its own entries' verdicts (see the helpers above). The
    returned callable's signature then matches ``flush_step`` with the
    same optional batches appended.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from sentinel_tpu.runtime.flush import apply_exit_phase, flush_entries, system_check

    def sharded_step(
        stats, flow_dev, flow_dyn, ddev, ddyn, pdyn, sysdev, batch,
        shaping_g=None, param_g=None,
    ):
        from sentinel_tpu.metrics.nodes import materialize_matured
        from sentinel_tpu.rules.degrade_table import CLOSED as _CLOSED, OPEN as _OPEN

        from sentinel_tpu.rules.degrade_table import trip_condition

        def merge_breaker(base_ddyn, new_ddyn):
            """Merge per-chip breaker windows/state against a replicated
            base. State: transitions happen on the one chip whose shard
            carried the triggering op, so "any chip that changed wins" —
            a plain pmax would discard HALF_OPEN→CLOSED (0 < 2) and
            HALF_OPEN→OPEN (1 < 2), wedging the breaker forever; if
            several chips transitioned differently in one flush, the max
            changed state wins (OPEN over CLOSED — pessimistic, like the
            reference resolving concurrent probe outcomes through its
            CAS, AbstractCircuitBreaker.java:40-150). Windows merge
            rollover-aware like merge_window_across. Finally, a breaker
            whose MERGED window crosses the threshold may have tripped
            on no single chip (errors spread 1-per-chip): re-evaluate
            CLOSED→OPEN on the merged counts, retry deadline anchored
            at flush time (later than the crossing completion's ts by at
            most one flush interval)."""
            changed = new_ddyn.state != base_ddyn.state
            cand = jnp.where(changed, new_ddyn.state, jnp.int32(-1))
            best = jax.lax.pmax(cand, axis)
            merged_state = jnp.where(best >= 0, best, base_ddyn.state)
            g_dws = jax.lax.pmax(new_ddyn.ws, axis)
            d_old_cur = base_ddyn.ws == g_dws
            d_new_cur = new_ddyn.ws == g_dws
            base_bad = jnp.where(d_old_cur, base_ddyn.bad, 0)
            base_total = jnp.where(d_old_cur, base_ddyn.total, 0)
            out = type(base_ddyn)(
                state=merged_state,
                next_retry=jax.lax.pmax(new_ddyn.next_retry, axis),
                bad=base_bad
                + jax.lax.psum(
                    jnp.where(d_new_cur, new_ddyn.bad - base_bad, 0), axis
                ),
                total=base_total
                + jax.lax.psum(
                    jnp.where(d_new_cur, new_ddyn.total - base_total, 0), axis
                ),
                ws=g_dws,
            )
            trip = trip_condition(
                ddev.grade, ddev.threshold, ddev.slow_ratio,
                out.bad.astype(jnp.float32),
                out.total.astype(jnp.float32),
            )
            cross = (
                (out.state == _CLOSED)
                & (out.total >= ddev.min_request)
                & trip
            )
            return out._replace(
                state=jnp.where(cross, _OPEN, out.state),
                next_retry=jnp.where(
                    cross, batch.now + ddev.retry_ms, out.next_retry
                ),
            )

        # Matured borrows fold into the window FIRST — deterministic on
        # replicated state, so it must happen before per-shard writes
        # diverge and must be the merge base (otherwise every chip's
        # identical materialisation would be summed once per chip).
        stats = materialize_matured(stats, batch.now)
        # Exits once, then the post-exit view is made GLOBAL before any
        # admission: within one flush exits apply before entry checks
        # on the WHOLE mesh (flush.py "Intra-batch sequencing"), so a
        # thread release / breaker completion carried by one chip's
        # shard is visible to every chip's checks — without this an
        # entry landing on a different chip than its same-flush exit
        # was blocked against a stale gauge (caught by the batched mesh
        # differential, round 4). Window tensors are additive: local
        # apply + rollover-aware merge is exact.
        stats_x, _ = apply_exit_phase(stats, ddev, ddyn, batch)
        stats_x = merge_stats_across(stats, stats_x, axis)
        # Breaker completions are a serializing state machine (the trip
        # latches at the FIRST prefix crossing the threshold), so a
        # per-chip run + endpoint merge loses trips whose crossing
        # prefix spans chips (e.g. errors front-loaded in ts order but
        # sharded apart: the merged endpoint ratio can sit back under
        # the threshold). Same treatment as the shaping/param scans:
        # every chip runs the completion machine once on the GLOBALLY
        # gathered completion set — identical replicated result, exact
        # global (ts, chip, arrival) order, nothing to merge.
        from sentinel_tpu.rules.degrade_table import breaker_on_exits

        def gather_flat(x):
            g = jax.lax.all_gather(x, axis)  # [nch, M, ...]
            return g.reshape((-1,) + x.shape[1:])

        ddyn_x = breaker_on_exits(
            ddev,
            ddyn,
            gather_flat(batch.x_dgid),
            gather_flat(batch.x_ts),
            gather_flat(batch.x_rt),
            gather_flat(batch.x_err),
            gather_flat(batch.x_valid),
        )

        # ---- global serializing scans (shaping pacers, hot params) ----
        # Upstream liveness (auth + system) for this chip's entries —
        # the owner-chip bits gate the replicated global scans.
        n_local = batch.e_valid.shape[0]
        k = batch.e_rule_gid.shape[1]
        param_pre = None
        shaping_pre = None
        new_pdyn_scan = None
        new_fdyn_scan = None
        p_owner = p_lidx = None
        if shaping_g is not None or param_g is not None:
            live0 = batch.e_valid & batch.e_auth_ok
            sys_ok, _ = system_check(stats_x, sysdev, batch, live0)
            live_up = live0 & sys_ok
            if param_g is not None:
                new_pdyn_scan, param_pre, p_owner, p_lidx = _global_param_scan(
                    axis, pdyn, param_g, live_up, n_local, rounds=param_rounds
                )
                live_up = live_up & param_pre[0]
            if shaping_g is not None:
                new_fdyn_scan, shaping_pre = _global_shaping_scan(
                    axis, stats_x, flow_dev, flow_dyn, shaping_g, batch,
                    live_up, n_local, k, rounds=shaping_rounds,
                )

        # Pass 1 (no state writes): local flow-level admission demand.
        _, _, _, _, r1 = flush_entries(
            stats_x, flow_dev, flow_dyn, ddev, ddyn_x, pdyn, sysdev, batch,
            commit=False, occupy_timeout_ms=occupy_timeout_ms,
            param_pre=param_pre, shaping_pre=shaping_pre,
        )
        # Occupied entries borrow from future windows, not the current
        # budget — exclude them from the grant math (their slab commits
        # merge like window counters) and budget them separately against
        # the global borrow allowance.
        budgeted = r1.flow_live & ~r1.occupied
        keep = _demote_over_grant(axis, stats_x, flow_dev, batch, budgeted)
        keep_occ = _demote_over_borrow(axis, stats, flow_dev, batch, r1.occ_slot)
        # Pass 2 borrows only what pass 1 granted within the global
        # budget: demoted borrowers lose prio (they fall to plain BLOCK
        # — their plain check already failed, that's why they borrowed);
        # entries pass 1 never occupied must not start borrowing now
        # that demotions shrank the intra-chip charge.
        batch2 = batch._replace(
            e_cluster_ok=batch.e_cluster_ok & (keep | ~budgeted),
            e_prio=batch.e_prio & r1.occupied & keep_occ,
        )
        # Probe election: exactly ONE entry across the mesh may probe an
        # OPEN breaker (fromOpenToHalfOpen is a single CAS,
        # AbstractCircuitBreaker.java:91-110); without this every chip
        # admits its own local rank-0 candidate. Each chip offers its
        # best candidate ts; the global (ts, chip) minimum wins.
        nd = ddev.n_rules
        n, kd = batch.e_dgid.shape
        gid_f = batch.e_dgid.reshape(-1)
        eidx_d = jnp.arange(n * kd, dtype=jnp.int32) // kd
        gid_dc = jnp.clip(gid_f, 0, nd - 1)
        big = jnp.int32(2**31 - 1)
        cand = (
            (gid_f >= 0)
            & r1.flow_live[eidx_d]
            & (ddyn.state[gid_dc] == _OPEN)
            & (batch.e_ts[eidx_d] >= ddyn.next_retry[gid_dc])
        )
        best_ts = (
            jnp.full((nd,), big, dtype=jnp.int32)
            .at[jnp.where(cand, gid_f, nd)]
            .min(batch.e_ts[eidx_d], mode="drop")
        )
        g_ts = jax.lax.pmin(best_ts, axis)
        idx = jax.lax.axis_index(axis)
        nch = jax.lax.axis_size(axis)
        chip_rank = jnp.where(best_ts == g_ts, idx, jnp.int32(nch))
        g_chip = jax.lax.pmin(chip_rank, axis)
        probe_allowed = (g_ts < big) & (idx == g_chip)
        # Pass 2: the real step with over-grants demoted.
        new_stats, new_fdyn, new_ddyn, new_pdyn, result = flush_entries(
            stats_x, flow_dev, flow_dyn, ddev, ddyn_x, pdyn, sysdev, batch2,
            occupy_timeout_ms=occupy_timeout_ms, probe_allowed=probe_allowed,
            param_pre=param_pre, shaping_pre=shaping_pre,
        )
        # The serializing scans own their state families: the global
        # shaping scan's pacer columns and the global param scan's
        # buckets (plus thread-gauge increments for finally-admitted
        # entries, ORed across owner chips) replace the untouched
        # pass-through values.
        if new_fdyn_scan is not None:
            new_fdyn = new_fdyn_scan
        if new_pdyn_scan is not None:
            from sentinel_tpu.models import constants as _C

            adm_bit = p_owner & param_g.valid & result.admitted[p_lidx]
            adm_item = jax.lax.psum(adm_bit.astype(jnp.int32), axis) > 0
            inc = param_g.valid & (param_g.grade == _C.FLOW_GRADE_THREAD) & adm_item
            pr = new_pdyn_scan.threads.shape[0]
            inc_rows = jnp.where(inc, param_g.prow, jnp.int32(pr))
            new_pdyn = new_pdyn_scan._replace(
                threads=new_pdyn_scan.threads.at[inc_rows].add(1, mode="drop")
            )
        # Bases are the GLOBAL post-exit views (replicated identical on
        # every chip): the merges then sum exactly the per-chip entry
        # deltas, with the exit deltas counted once inside the base.
        merged = merge_stats_across(stats_x, new_stats, axis)
        merged_ddyn = merge_breaker(ddyn_x, new_ddyn)
        return merged, new_fdyn, merged_ddyn, new_pdyn, result

    # Shaping/param item batches are replicated (P() pytree prefix):
    # every chip holds the full global item set for the scans.
    in_specs = [P(), P(), P(), P(), P(), P(), P(), batch_partition_specs(axis)]
    if with_shaping:
        in_specs.append(P())
    if with_param:
        in_specs.append(P())
    names = [
        kw for kw, on in (("shaping_g", with_shaping), ("param_g", with_param)) if on
    ]
    body = lambda *a: sharded_step(*a[:8], **dict(zip(names, a[8:])))  # noqa: E731
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P(), P(), P(), P(axis)),
        check_vma=False,
    )
    return jax.jit(fn)
