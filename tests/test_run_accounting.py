"""Run-reduced statistics accounting against one record per entry.

``nodes.apply_run_updates`` sums each run's deltas (a span of entries
sharing node rows and ts) before the fan-out to the four node rows. The
reference here is the per-entry fan-out it replaced: one
``metric_array.update`` record per (entry, node row). Every stats tensor
must come out bit for bit the same.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.metrics import metric_array as ma
from sentinel_tpu.metrics import nodes
from sentinel_tpu.metrics.events import MetricEvent, NUM_EVENTS
from sentinel_tpu.runtime.engine import _run_table

I32_MAX = 2**31 - 1
ROWS = 24


def _per_entry(state, rows, ts, valid, deltas, thr, rt_sample=None, minute_deltas=None):
    n = ts.shape[0]
    rows_f = rows.reshape(-1)
    mask = (rows_f >= 0) & jnp.repeat(valid, 4)
    ts_f = jnp.repeat(ts, 4)

    def mat(cols):
        out = jnp.zeros((4 * n, NUM_EVENTS), dtype=jnp.int32)
        for ev, v in cols.items():
            out = out.at[:, ev].set(jnp.repeat(v, 4))
        return out

    rt_f = None if rt_sample is None else jnp.repeat(rt_sample, 4)
    second = ma.update(nodes.SECOND_CFG, state.second, rows_f, ts_f, mat(deltas), rt_f, mask)
    minute = ma.update(
        nodes.MINUTE_CFG, state.minute, rows_f, ts_f,
        mat(deltas if minute_deltas is None else minute_deltas), rt_f, mask,
    )
    add = jnp.where(mask, jnp.repeat(thr, 4), 0)
    threads = state.threads.at[jnp.where(mask, rows_f, 0)].add(add, mode="drop")
    return state._replace(second=second, minute=minute, threads=threads)


def _assert_same(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _seeded_state(rng, ts0):
    """Stats with live buckets around ``ts0``: some windows current, some
    one or two windows old, so records add, roll or drop."""
    st = nodes.make_stats(ROWS)
    for cfg, name in ((nodes.SECOND_CFG, "second"), (nodes.MINUTE_CFG, "minute")):
        w = cfg.window_len_ms
        cur = getattr(st, name)
        lag = rng.integers(-2, 1, cur.window_start.shape)
        aligned = (ts0 // w + lag) * w
        idx = (aligned // w) % cfg.sample_count
        b = cfg.sample_count
        ws = np.full((ROWS, b), cfg.empty_ws, np.int32)
        ws[np.arange(ROWS)[:, None], idx] = aligned
        st = st._replace(**{name: cur._replace(
            counts=jnp.asarray(rng.integers(0, 9, cur.counts.shape), jnp.int32),
            min_rt=jnp.asarray(rng.integers(1, 300, cur.min_rt.shape), jnp.int32),
            window_start=jnp.asarray(ws),
        )})
    return st._replace(threads=jnp.asarray(rng.integers(0, 5, ROWS), jnp.int32))


def _random_runs(rng, n, ts0, single=False):
    """``n`` entries made of runs with shared rows and ts; rows drawn from
    a few node sets so runs collide on rows, ts across 500 ms and 1 s
    bucket edges (and one older window)."""
    sets = [
        (rng.integers(0, ROWS), rng.integers(0, ROWS),
         rng.choice([-1, rng.integers(0, ROWS)]), rng.choice([-1, 0]))
        for _ in range(5)
    ]
    times = [ts0 - 1300, ts0 - 501, ts0 - 1, ts0, ts0 + 1, ts0 + 499, ts0 + 500]
    rows = np.empty((n, 4), np.int32)
    ts = np.empty(n, np.int32)
    i = 0
    while i < n:
        ln = 1 if single else int(rng.integers(1, 9))
        ln = min(ln, n - i)
        rows[i:i + ln] = sets[rng.integers(0, len(sets))]
        ts[i:i + ln] = times[rng.integers(0, len(times))]
        i += ln
    valid = rng.random(n) < 0.85
    return rows, ts, valid


def _entry_cols(rng, n):
    adm = rng.random(n) < 0.6
    occ = adm & (rng.random(n) < 0.2)
    acq = rng.integers(1, 4, n).astype(np.int32)
    blocked = np.where(adm, 0, acq)
    sec = {MetricEvent.PASS: np.where(adm & ~occ, acq, 0), MetricEvent.BLOCK: blocked}
    minute = {MetricEvent.PASS: np.where(adm, acq, 0), MetricEvent.BLOCK: blocked,
              MetricEvent.OCCUPIED_PASS: np.where(occ, acq, 0)}
    return sec, minute, np.where(adm, 1, 0).astype(np.int32)


def _exit_cols(rng, n):
    count = rng.integers(0, 3, n).astype(np.int32)
    thr = rng.choice(np.array([-1, 0, -1, 2], np.int32), n)  # exits, traces, compensation
    count = np.where(thr > 0, 0, count)
    rt = rng.integers(0, 200, n).astype(np.int32)
    err = (rng.random(n) < 0.3).astype(np.int32)
    rt_sample = np.where((thr < 0) & (count > 0), rt, I32_MAX).astype(np.int32)
    cols = {MetricEvent.SUCCESS: count, MetricEvent.RT: rt, MetricEvent.EXCEPTION: err}
    return cols, thr, rt_sample


def _j(d):
    return {k: jnp.asarray(v, jnp.int32) for k, v in d.items()}


def _check_entries(st, rows, ts, valid, table, sec, minute, thr):
    got = jax.jit(nodes.apply_run_updates)(
        st, jnp.asarray(rows), jnp.asarray(ts), jnp.asarray(valid),
        None if table is None else jnp.asarray(table), _j(sec), jnp.asarray(thr),
        minute_deltas=_j(minute),
    )
    want = _per_entry(st, jnp.asarray(rows), jnp.asarray(ts), jnp.asarray(valid),
                      _j(sec), jnp.asarray(thr), minute_deltas=_j(minute))
    _assert_same(got, want)
    return got


def _check_exits(st, rows, ts, valid, table, cols, thr, rt_sample):
    got = jax.jit(nodes.apply_run_updates)(
        st, jnp.asarray(rows), jnp.asarray(ts), jnp.asarray(valid),
        None if table is None else jnp.asarray(table), _j(cols), jnp.asarray(thr),
        rt_sample=jnp.asarray(rt_sample),
    )
    want = _per_entry(st, jnp.asarray(rows), jnp.asarray(ts), jnp.asarray(valid),
                      _j(cols), jnp.asarray(thr), rt_sample=jnp.asarray(rt_sample))
    _assert_same(got, want)
    return got


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 2**31 + 11])
def test_random_entry_batches_match_per_entry(seed):
    rng = np.random.default_rng(seed)
    n, ts0 = 256, 40_250
    rows, ts, valid = _random_runs(rng, n, ts0)
    table, runs = _run_table(rows, ts, n, 1)
    assert 0 < runs < n
    sec, minute, thr = _entry_cols(rng, n)
    _check_entries(_seeded_state(rng, ts0), rows, ts, valid, table, sec, minute, thr)


@pytest.mark.parametrize("seed", [6, 7, 8, 9, 10])
def test_random_exit_batches_match_per_entry(seed):
    rng = np.random.default_rng(seed)
    n, ts0 = 128, 71_500
    rows, ts, valid = _random_runs(rng, n, ts0)
    table, _ = _run_table(rows, ts, n, 1)
    cols, thr, rt_sample = _exit_cols(rng, n)
    _check_exits(_seeded_state(rng, ts0), rows, ts, valid, table, cols, thr, rt_sample)


@pytest.mark.parametrize("shape", ["singles", "one_run", "no_table", "padded"])
def test_run_shapes(shape):
    """Runs of one (G = n), one run over everything, no table at all, and
    a table padded far past its runs."""
    rng = np.random.default_rng(42)
    n, ts0 = 64, 9_000
    if shape == "one_run":
        rows = np.tile(np.array([3, 4, -1, 0], np.int32), (n, 1))
        ts = np.full(n, ts0, np.int32)
        valid = np.ones(n, bool)
    else:
        rows, ts, valid = _random_runs(rng, n, ts0, single=shape == "singles")
    table = {
        "singles": np.arange(n, dtype=np.int32),
        "one_run": np.array([n - 1], np.int32),
        "no_table": None,
        "padded": None,
    }[shape]
    if shape == "padded":
        ends = np.flatnonzero(np.r_[np.any(np.diff(rows, axis=0) != 0, 1)
                                    | (np.diff(ts) != 0), True])
        table = np.full(n, -1, np.int32)
        table[: ends.size] = ends
    sec, minute, thr = _entry_cols(rng, n)
    _check_entries(_seeded_state(rng, ts0), rows, ts, valid, table, sec, minute, thr)


def test_runs_on_one_row_across_bucket_edges():
    """Four runs on one row set: the newest window wins each bucket, and
    the run one second older than the stored window drops whole."""
    st = nodes.make_stats(8)
    rows = np.tile(np.array([1, 2, -1, 0], np.int32), (12, 1))
    # 999 | 1000 1000 | 1499 | 1500 1500 1500 | 2000 ... ; then 500 (older)
    ts = np.array([999, 1000, 1000, 1499, 1500, 1500, 1500, 2000, 2000, 2000, 500, 500],
                  np.int32)
    valid = np.ones(12, bool)
    table, runs = _run_table(rows, ts, 12, 1)
    assert runs == 6
    sec = {MetricEvent.PASS: np.arange(1, 13, dtype=np.int32),
           MetricEvent.BLOCK: np.zeros(12, np.int32)}
    minute = {MetricEvent.PASS: np.arange(1, 13, dtype=np.int32) * 10}
    thr = np.ones(12, np.int32)
    got = _check_entries(st, rows, ts, valid, table, sec, minute, thr)
    w = nodes.SECOND_CFG.window_len_ms
    b = 2000 // w % nodes.SECOND_CFG.sample_count
    assert int(got.second.window_start[1, b]) == 2000
    assert int(got.second.counts[1, b, MetricEvent.PASS]) == 8 + 9 + 10
    assert int(got.threads[1]) == 12  # the gauge takes every run, old or not


def test_all_invalid_runs_roll_nothing():
    """A run whose entries are all invalid writes no bucket, not even the
    roll to its newer window; a valid run beside it still rolls."""
    rng = np.random.default_rng(3)
    st = _seeded_state(rng, 5_000)
    rows = np.array([[5, 6, -1, 0]] * 4 + [[7, 8, 9, -1]] * 4, np.int32)
    ts = np.full(8, 9_999, np.int32)
    valid = np.array([False] * 4 + [True, False, True, False])
    table, runs = _run_table(rows, ts, 8, 1)
    assert runs == 2
    sec, minute, thr = _entry_cols(rng, 8)
    got = _check_entries(st, rows, ts, valid, table, sec, minute, thr)
    np.testing.assert_array_equal(np.asarray(got.second.window_start[5]),
                                  np.asarray(st.second.window_start[5]))
    assert 9_500 in np.asarray(got.second.window_start[7]).tolist()


def test_exits_min_rt_traces_and_compensation():
    """Exit runs: per-run minimum RT over true exits only; traces (thr 0)
    and the speculative tier's compensation ops (count 0, thr +n) add no
    RT sample but move the gauge."""
    st = nodes.make_stats(8)
    rows = np.array([[1, 2, -1, 0]] * 6 + [[3, 4, -1, -1]] * 3, np.int32)
    ts = np.full(9, 4_321, np.int32)
    valid = np.ones(9, bool)
    count = np.array([1, 1, 0, 1, 0, 1, 0, 0, 1], np.int32)
    thr = np.array([-1, -1, 0, -1, 3, -1, 2, 0, -1], np.int32)
    rt = np.array([90, 40, 5, 70, 1, 60, 2, 3, 55], np.int32)
    rt_sample = np.where((thr < 0) & (count > 0), rt, I32_MAX).astype(np.int32)
    cols = {MetricEvent.SUCCESS: count, MetricEvent.RT: rt,
            MetricEvent.EXCEPTION: np.zeros(9, np.int32)}
    table, runs = _run_table(rows, ts, 9, 1)
    assert runs == 2
    got = _check_exits(st, rows, ts, valid, table, cols, thr, rt_sample)
    b = 4_321 // nodes.SECOND_CFG.window_len_ms % nodes.SECOND_CFG.sample_count
    assert int(got.second.min_rt[1, b]) == 40
    assert int(got.second.min_rt[3, b]) == 55
    assert int(got.threads[1]) == -4 + 3


# ----------------------------------------------------------------------
# Through the engine: bulk groups, single ops and exits in one flush
# ----------------------------------------------------------------------

COUNTS = {"ra": 4, "rb": 9, "rc": 15, "rd": 40}


def _spans_of(kinds):
    """Runs of a staged sequence of resources: one per change of resource
    (every op of a flush shares its ts)."""
    return sum(1 for i, r in enumerate(kinds) if i == 0 or kinds[i - 1] != r)


def _oracle_exit(node, t, rt, err):
    node.add_rt_and_success(t, int(rt), 1)
    if err:
        node.second.add(t, MetricEvent.EXCEPTION, 1)
        node.minute.add(t, MetricEvent.EXCEPTION, 1)
    node.cur_thread_num -= 1


def test_engine_mixed_flushes_match_oracle(manual_clock, engine):
    """Bulk groups beside single ``submit_entry`` ops and exits, over
    flushes that cross 500 ms and 1 s edges: every verdict and every
    windowed statistic of the resources and the inbound node agree with
    the sequential oracle, and the staging span counts the runs."""
    import sentinel_tpu as st
    from sentinel_tpu.models import constants as C
    from sentinel_tpu.testing.oracle import OracleDefaultController, OracleNode

    st.flow_rule_manager.load_rules([st.FlowRule(r, count=c) for r, c in COUNTS.items()])
    res = list(COUNTS)
    node = {r: OracleNode() for r in res}
    inbound = OracleNode()
    ctrl = {r: OracleDefaultController(c, 1) for r, c in COUNTS.items()}
    rng = np.random.default_rng(2027)
    prev_single, prev_bulk = [], []
    t = 1_230
    for _ in range(9):
        t += int(rng.integers(170, 700))
        t += t % 500 == 0
        manual_clock.set_ms(t)
        ex_single, ex_bulk = [], []
        for r, op in prev_single:
            if op.verdict.admitted:
                rt, err = int(rng.integers(1, 90)), int(rng.random() < 0.3)
                engine.submit_exit(op.rows, rt=rt, err=err, resource=r)
                ex_single.append((r, [rt], [err]))
        for r, g in prev_bulk:
            k = g.admitted_count
            if k:
                rt = rng.integers(1, 90, k).astype(np.int32)
                err = (rng.random(k) < 0.3).astype(np.int32)
                engine.submit_exit_bulk(g.rows, k, rt=rt, err=err, resource=r)
                ex_bulk.append((r, rt, err))
        singles = [res[i] for i in rng.integers(0, len(res), int(rng.integers(0, 6)))]
        single_ops = [engine.submit_entry(r, entry_type=C.EntryType.IN) for r in singles]
        order = [res[i] for i in rng.permutation(len(res))]
        sizes = rng.integers(1, 30, len(order))
        bulk = [engine.submit_bulk(r, int(n), entry_type=C.EntryType.IN)
                for r, n in zip(order, sizes)]
        engine.flush()

        for r, rts, errs in ex_single + ex_bulk:
            for rt, err in zip(rts, errs):
                _oracle_exit(node[r], t, rt, err)
                _oracle_exit(inbound, t, rt, err)
        want = []
        for r in singles + [r for r, n in zip(order, sizes) for _ in range(n)]:
            ok = ctrl[r].can_pass(node[r], t)
            for nd in (node[r], inbound):
                if ok:
                    nd.add_pass(t, 1)
                    nd.cur_thread_num += 1
                else:
                    nd.add_block(t, 1)
            want.append(ok)
        got = [op.verdict.admitted for op in single_ops]
        for g in bulk:
            got += g.admitted.tolist()
        assert got == want, t

        rows = {r: engine.nodes.lookup_cluster_row(r) for r in res}
        stats = engine.rows_stats(list(rows.values()) + [0], now=t)
        for nd, row in [(node[r], rows[r]) for r in res] + [(inbound, 0)]:
            sec, minute = nd.second.values(t), nd.minute.values(t)
            s = stats[row]
            assert s["pass_qps"] == sec[MetricEvent.PASS]
            assert s["block_qps"] == sec[MetricEvent.BLOCK]
            assert s["success_qps"] == sec[MetricEvent.SUCCESS]
            assert s["exception_qps"] == sec[MetricEvent.EXCEPTION]
            assert s["min_rt"] == nd.second.min_rt_value(t)
            assert s["cur_thread_num"] == nd.cur_thread_num
            assert s["total_pass_minute"] == minute[MetricEvent.PASS]
            assert s["total_block_minute"] == minute[MetricEvent.BLOCK]
            assert s["total_success_minute"] == minute[MetricEvent.SUCCESS]
            assert s["total_exception_minute"] == minute[MetricEvent.EXCEPTION]
            want_rt = sec[MetricEvent.RT] / sec[MetricEvent.SUCCESS] if sec[MetricEvent.SUCCESS] else 0.0
            assert s["avg_rt"] == pytest.approx(want_rt)

        span = engine.telemetry.spans()[-1]
        assert span.runs == _spans_of(singles + order)
        assert span.x_runs == _spans_of([r for r, _, _ in ex_single + ex_bulk])
        n = len(singles) + int(sizes.sum())
        assert span.runs_padded == max(8, 1 << (n - 1).bit_length())  # G = n below 4,096
        prev_single = list(zip(singles, single_ops))
        prev_bulk = list(zip(order, bulk))


@pytest.mark.parametrize("shards,block_len", [(1, 8192), (4, 4096)])
def test_run_table_cuts_at_shards_and_pads(shards, block_len):
    """Runs never cross a shard boundary; each shard's block lists its
    run ends in local indices, then -1; the block length is a power of
    two from max(1/16 of the shard, 4,096) up to the shard."""
    n = 1 << 16
    per = n // shards
    rows = np.full((n, 4), -1, np.int32)
    ts = np.zeros(n, np.int32)
    live = 60_000
    bounds = np.unique(np.r_[0, np.sort(np.random.default_rng(5).choice(
        np.arange(1, live), 5_000, replace=False)), live])
    for i, (a, b) in enumerate(zip(bounds[:-1], bounds[1:])):
        rows[a:b] = (i % 7, i % 11, -1, 0)
        ts[a:b] = 1_000 + (i % 3)
    table, runs = _run_table(rows, ts, live, shards)
    ends = set(bounds[1:] - 1) | {k * per - 1 for k in range(1, shards + 1) if k * per <= live}
    assert runs == len(ends)
    block = table.reshape(shards, -1)
    assert block.shape[1] == block_len  # ~5,000 runs in one; ~1,250 a shard in four
    got = set()
    for s in range(shards):
        col = block[s]
        k = int((col >= 0).sum())
        assert (col[:k] >= 0).all() and (col[k:] == -1).all()
        assert (np.diff(col[:k]) > 0).all() and (col[:k] < per).all()
        got |= set((col[:k] + s * per).tolist())
    assert got == ends
    assert _run_table(rows, ts, 0, shards)[1] == 0


@pytest.mark.mesh
def test_mesh_matches_one_device_with_groups_across_shards():
    """The sharded flush on the 8-device mesh gives the same verdicts and
    bit-identical statistics as one device, with bulk groups and exit
    groups that straddle shard boundaries (runs cut there)."""
    import sentinel_tpu as st
    from sentinel_tpu.models import constants as C
    from sentinel_tpu.runtime.engine import Engine
    from sentinel_tpu.utils.clock import ManualClock

    def drive(mesh):
        clock = ManualClock(start_ms=10_000)
        eng = Engine(clock=clock)
        if mesh:
            eng.enable_mesh(8)
        eng.set_flow_rules([st.FlowRule(r, count=c) for r, c in COUNTS.items()])
        rng = np.random.default_rng(11)
        verdicts, prev = [], []
        try:
            for step in range(3):
                clock.advance(int(rng.integers(300, 700)))
                for g in prev:  # 1 + 37 + ... exits: groups cross the 8-exit shards
                    if g.admitted_count:
                        k = g.admitted_count
                        eng.submit_exit_bulk(g.rows, k, rt=rng.integers(1, 50, k),
                                             resource=g.resource)
                single = eng.submit_entry("rb")
                prev = [eng.submit_bulk(r, int(n), entry_type=C.EntryType.IN)
                        for r, n in zip(COUNTS, rng.integers(5, 45, len(COUNTS)))]
                eng.flush()
                verdicts.append([single.verdict.admitted]
                                + [g.admitted.tolist() for g in prev])
                span = eng.telemetry.spans()[-1]
                # One run per op group on one device; the mesh cuts more.
                assert (span.runs > 5) if mesh else (span.runs == 5)
            return verdicts, jax.device_get(eng.stats)
        finally:
            eng.close()

    v1, s1 = drive(False)
    v8, s8 = drive(True)
    assert v1 == v8
    _assert_same(s1, s8)
