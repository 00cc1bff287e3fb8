"""Pipelined flushes: ``Engine.flush_async`` + lazy materialization.

``flush()`` blocks on the device→host fetch of its own results, which
serializes every flush behind a full round-trip. ``flush_async``
dispatches and returns; results materialize on first access, at the
next ``flush()``/``drain()``, or when the in-flight bound is hit. The
reference has no analog (every entry is a synchronous CAS race,
sentinel-core SphU.java:84); this is the batch-inversion's pipelining
dividend. These tests pin:

- verdict/bulk-result laziness and materialize-on-access,
- exact sync/async verdict equality on a shared random stream,
- FIFO in-flight bounding (``max_inflight``),
- block-log delivery riding with materialization,
- rule reloads between dispatch and materialization keeping the
  dispatched tables' attribution.
"""

from __future__ import annotations

import numpy as np
import pytest

from sentinel_tpu.models.rules import DegradeRule, FlowRule
from sentinel_tpu.runtime.engine import Engine
from sentinel_tpu.utils.clock import ManualClock


def _engine(rules, clock=None):
    eng = Engine(initial_rows=1024, clock=clock or ManualClock(0))
    eng.set_flow_rules(rules)
    return eng


def test_flush_async_defers_and_materializes_on_access():
    clock = ManualClock(1000)
    eng = _engine([FlowRule(resource="r", count=50)], clock)
    ops = [eng.submit_entry("r", ts=clock.now_ms()) for _ in range(100)]
    ret = eng.flush_async()
    assert len(ret) == 100
    # Not yet fetched: raw slots are empty, one record queued.
    assert ops[0]._verdict is None
    assert len(eng._pending_fetches) == 1
    # First access materializes the whole chunk.
    assert ops[0].verdict is not None
    assert all(o._verdict is not None for o in ops)
    assert len(eng._pending_fetches) == 0
    assert sum(o.verdict.admitted for o in ops) == 50


def test_bulk_async_lazy_arrays():
    clock = ManualClock(1000)
    eng = _engine([FlowRule(resource="r", count=30)], clock)
    g = eng.submit_bulk("r", 100, ts=clock.now_ms())
    eng.flush_async()
    assert g._admitted is None
    assert g.admitted_count == 30  # property materializes
    assert g._admitted is not None and g._reason is not None
    assert int((~g.admitted).sum()) == 70


def test_drain_and_sync_flush_materialize_everything():
    clock = ManualClock(1000)
    eng = _engine([FlowRule(resource="r", count=10)], clock)
    o1 = [eng.submit_entry("r", ts=clock.now_ms()) for _ in range(20)]
    eng.flush_async()
    eng.drain()
    assert all(o._verdict is not None for o in o1)
    # sync flush after async: drains pendings first, keeps window state.
    o2 = [eng.submit_entry("r", ts=clock.now_ms()) for _ in range(20)]
    eng.flush_async()
    o3 = [eng.submit_entry("r", ts=clock.now_ms()) for _ in range(5)]
    eng.flush()
    assert all(o._verdict is not None for o in o2 + o3)
    admitted = sum(o.verdict.admitted for o in o1 + o2 + o3)
    assert admitted == 10  # one second-window, count=10, same ts


def test_inflight_bound_fifo():
    clock = ManualClock(1000)
    eng = _engine([FlowRule(resource="r", count=1e9)], clock)
    eng.max_inflight = 2
    groups = []
    for _ in range(5):
        groups.append(eng.submit_bulk("r", 64, ts=clock.now_ms()))
        eng.flush_async()
    # Only the newest 2 remain unfetched; the first 3 were forced FIFO.
    assert len(eng._pending_fetches) == 2
    assert all(g._admitted is not None for g in groups[:3])
    assert all(g._admitted is None for g in groups[3:])
    eng.drain()
    assert all(g.admitted_count == 64 for g in groups)


def test_async_equals_sync_on_random_stream():
    """Differential: the same submit/exit stream through flush_async
    must produce bit-identical verdicts to sync flushes."""
    rules = [
        FlowRule(resource="a", count=7),
        FlowRule(resource="b", count=3, grade=0),  # thread grade
        FlowRule(resource="c", count=20),
    ]
    def run(async_mode: bool):
        # Fresh rng per run: the exit-choice draws below must be
        # identical across both modes.
        rng = np.random.default_rng(42)
        stream = []
        t = 1000
        for _ in range(300):
            t += int(rng.integers(0, 40))
            stream.append((rng.choice(["a", "b", "c"]), t))
        clock = ManualClock(0)
        eng = _engine(rules, clock)
        eng.set_degrade_rules(
            [DegradeRule(resource="a", grade=1, count=0.5, time_window=5)]
        )
        verdicts = []
        ops = []
        for i, (res, ts) in enumerate(stream):
            clock.set_ms(ts)
            op = eng.submit_entry(res, ts=ts)
            ops.append(op)
            if i % 7 == 3:
                (eng.flush_async() if async_mode else eng.flush())
            if i % 11 == 5 and ops:
                # Exit a random earlier admitted op (thread release).
                # o.verdict (not _verdict) so the async run materializes
                # here too and both modes submit identical exits.
                j = int(rng.integers(0, len(ops)))
                o = ops[j]
                if o is not None and o.verdict is not None and o.verdict.admitted:
                    eng.submit_exit(o.rows, ts=ts, count=1, rt=5)
        eng.flush() if not async_mode else (eng.flush_async(), eng.drain())
        return [
            (o.verdict.admitted, o.verdict.reason, o.verdict.wait_ms)
            for o in ops
            if o is not None
        ]

    assert run(False) == run(True)


def test_block_log_rides_with_materialization(tmp_path, monkeypatch):
    clock = ManualClock(1000)
    eng = _engine([FlowRule(resource="r", count=0)], clock)
    logged = []
    monkeypatch.setattr(
        eng.block_log, "log_batch", lambda items: logged.extend(items)
    )
    for _ in range(4):
        eng.submit_entry("r", ts=clock.now_ms())
    eng.flush_async()
    assert logged == []  # nothing fetched yet
    eng.drain()
    assert len(logged) == 4
    assert all(item[0] == "r" and item[1] == "FlowException" for item in logged)


def test_reload_between_dispatch_and_materialize_keeps_attribution():
    clock = ManualClock(1000)
    rule = FlowRule(resource="r", count=0)
    eng = _engine([rule], clock)
    op = eng.submit_entry("r", ts=clock.now_ms())
    eng.flush_async()
    # Reload swaps the tables; the dispatched chunk still attributes
    # against the index it was checked with.
    eng.set_flow_rules([FlowRule(resource="r", count=100)])
    v = op.verdict
    assert v is not None and not v.admitted
    assert v.blocked_rule is not None and v.blocked_rule.count == 0


def test_failed_fetch_raises_to_every_reader(monkeypatch):
    """A device failure during the deferred fetch must surface on every
    later result read — never as 'nothing admitted' (admitted_count 0
    / verdict None)."""
    clock = ManualClock(1000)
    eng = _engine([FlowRule(resource="r", count=5)], clock)
    g = eng.submit_bulk("r", 16, ts=clock.now_ms())
    op = eng.submit_entry("r", ts=clock.now_ms())
    eng.flush_async()

    boom = RuntimeError("device wedged")

    def broken_fill(*a, **kw):
        raise boom

    monkeypatch.setattr(eng, "_fill_results", broken_fill)
    with pytest.raises(RuntimeError, match="device wedged"):
        eng.drain()
    # Subsequent reads keep raising the stored failure.
    with pytest.raises(RuntimeError, match="device wedged"):
        g.admitted_count
    with pytest.raises(RuntimeError, match="device wedged"):
        op.verdict
    # The queue is not stranded: later flushes work once fills succeed.
    monkeypatch.undo()
    g2 = eng.submit_bulk("r", 8, ts=clock.now_ms())
    eng.flush_async()
    assert g2.admitted_count >= 0
    eng.drain()


def test_reset_settles_pending_async_flushes(monkeypatch):
    """reset() must settle dispatched-but-unfetched chunks: their
    block-log records belong to the pre-reset engine, and a stored
    fetch failure must not surface into the first post-reset flush."""
    clock = ManualClock(1000)
    eng = _engine([FlowRule(resource="r", count=0)], clock)
    logged = []
    monkeypatch.setattr(
        eng.block_log, "log_batch", lambda items: logged.extend(items)
    )
    ops = [eng.submit_entry("r", ts=clock.now_ms()) for _ in range(4)]
    eng.flush_async()
    assert logged == []
    eng.reset()
    # Settled during reset, not delivered into post-reset traffic.
    assert len(logged) == 4
    assert all(o._verdict is not None for o in ops)
    assert len(eng._pending_fetches) == 0
    # A post-reset flush sees a clean engine.
    op = eng.submit_entry("r", ts=clock.now_ms())
    eng.flush()
    assert op.verdict.admitted  # the count=0 rule was cleared by reset

    # Failed pre-reset fetch: reset logs and completes; the error does
    # not leak into post-reset flushes (readers of the old ops still
    # see it).
    eng2 = _engine([FlowRule(resource="q", count=5)], clock)
    op2 = eng2.submit_entry("q", ts=clock.now_ms())
    eng2.flush_async()
    monkeypatch.setattr(
        eng2, "_fill_results",
        lambda *a, **kw: (_ for _ in ()).throw(RuntimeError("wedged")),
    )
    eng2.reset()  # swallows + logs
    monkeypatch.undo()
    op3 = eng2.submit_entry("q", ts=clock.now_ms())
    eng2.flush()  # must NOT raise the pre-reset failure
    assert op3.verdict is not None
    with pytest.raises(RuntimeError, match="wedged"):
        op2.verdict


def test_flush_async_on_empty_engine_is_noop():
    eng = _engine([FlowRule(resource="r", count=5)])
    assert eng.flush_async() == []
    assert len(eng._pending_fetches) == 0
    eng.drain()


@pytest.mark.slow
@pytest.mark.mesh
def test_flush_async_on_mesh_conserves_budget():
    """Deferred fetch over the sharded (multi-chip) kernel: budgets
    still conserved across chips, lazily materialized."""
    clock = ManualClock(1000)
    eng = _engine([FlowRule(resource="r", count=20)], clock)
    eng.enable_mesh(8)
    ops = [eng.submit_entry("r", ts=clock.now_ms()) for _ in range(128)]
    eng.flush_async()
    assert ops[0]._verdict is None
    assert sum(o.verdict.admitted for o in ops) == 20
    eng.drain()
    eng.disable_mesh()


@pytest.mark.slow
def test_async_pipeline_under_thread_contention():
    """Concurrent submitters + async flusher + readers: no deadlock,
    exact totals."""
    import threading

    clock = ManualClock(1000)
    eng = _engine([FlowRule(resource="r", count=1e9)], clock)
    groups: list = []
    glock = threading.Lock()
    stop = threading.Event()

    def submitter():
        while not stop.is_set():
            g = eng.submit_bulk("r", 128, ts=clock.now_ms())
            with glock:
                groups.append(g)
            eng.flush_async()

    def reader():
        while not stop.is_set():
            with glock:
                g = groups[-1] if groups else None
            if g is not None:
                g.admitted_count  # may materialize concurrently

    threads = [threading.Thread(target=submitter) for _ in range(2)] + [
        threading.Thread(target=reader)
    ]
    for t in threads:
        t.start()
    import time

    time.sleep(2.0)
    stop.set()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "deadlocked thread"
    eng.drain()
    assert all(g.admitted_count == 128 for g in groups)
