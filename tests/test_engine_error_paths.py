"""Existing engine error paths the failure domain builds on — with
failover DISARMED, device errors must keep their original semantics:

* ``_dispatch_deferred``'s drain-after-failed-dispatch branch: a later
  chunk's dispatch failure still bounds the in-flight queue, and the
  swallowed drain error never masks the dispatch failure being raised;
* ``_drain_pending``'s per-record fallback: a failed coalesced fetch
  attributes the failure to exactly the faulted record while later
  records still materialize;
* dirty shutdown: a worker thread that outlives its close-join is
  reported (``closed_dirty``) instead of silently leaked.

Alongside them, the settle-equivalence cases pin one dispatched chunk's
settle — fetch, fill, fault handling, arena give-back, span settle —
as the same observable result at depth 0, depth 2 and
``flush_async`` + ``drain``: healthy, with a fetch fault under armed
failover, and with a fill error under disarmed failover.
"""

import threading
import time

import pytest

import sentinel_tpu as st
from sentinel_tpu.testing.faults import FaultInjector, InjectedFault
from sentinel_tpu.utils.config import config


def _mk_engine(manual_clock, depth=0, max_batch=None):
    from sentinel_tpu.runtime.engine import Engine

    eng = Engine(clock=manual_clock)
    eng.pipeline_depth = depth
    if max_batch is not None:
        eng.max_batch = max_batch
    eng.set_flow_rules([st.FlowRule("r", count=1e9)])
    return eng


class TestDispatchFailureDrain:
    def test_drain_error_never_masks_the_dispatch_failure(self, manual_clock):
        """Oversized pipelined flush: chunk 1 dispatches, chunk 2's
        dispatch fails — the except-path drain (which itself hits a
        fetch error) is swallowed and the ORIGINAL dispatch error is
        what the caller sees; chunk 1's ops stay readable and report
        their own fetch error."""
        eng = _mk_engine(manual_clock, depth=1, max_batch=4)
        inj = FaultInjector().install(eng)
        manual_clock.set_ms(1000)

        # 8 singles split into 2 chunks of 4; chunk 1 dispatches fine
        # (in-flight), chunk 2's dispatch raises. Its except-path drain
        # then fails too (chunk 1's fetch is faulted) — and is
        # swallowed.
        dispatch_err = InjectedFault("chunk-2 dispatch")
        fetch_err = InjectedFault("chunk-1 fetch")
        inj.fail_fetch(eng.flush_seq + 1, fetch_err)
        inj.fail_dispatch(eng.flush_seq + 2, dispatch_err)
        ops = [eng.submit_entry("r") for _ in range(7)]
        with pytest.raises(InjectedFault) as ei:
            eng.flush()
        assert ei.value is dispatch_err, "drain error must not mask dispatch"
        # Chunk 1's record is still in flight (queue bounded, not
        # poisoned); reading a verdict surfaces ITS OWN fetch error.
        with pytest.raises(InjectedFault) as ei2:
            _ = ops[0].verdict
        assert ei2.value is fetch_err
        # The queue is bounded afterwards.
        assert len(eng._pending_fetches) <= 1

    def test_queue_stays_bounded_after_failed_dispatch(self, manual_clock):
        eng = _mk_engine(manual_clock, depth=1, max_batch=4)
        inj = FaultInjector().install(eng)
        manual_clock.set_ms(1000)
        [eng.submit_entry("r") for _ in range(4)]
        eng.flush()  # one in-flight record
        inj.fail_dispatch(eng.flush_seq + 2)
        [eng.submit_entry("r") for _ in range(7)]
        with pytest.raises(InjectedFault):
            eng.flush()
        assert len(eng._pending_fetches) <= 1
        eng.drain()  # chunk 1 of the failed flush settles cleanly


class TestDrainPerRecordFallback:
    def test_failure_attributes_to_exactly_the_faulted_record(
        self, manual_clock
    ):
        """Two async records; the coalesced fetch fails because record
        A's fetch is faulted. The per-record fallback re-fetches each:
        A raises its own error, B's verdicts still materialize, and the
        drain re-raises A's error after finishing."""
        eng = _mk_engine(manual_clock)
        eng.max_inflight = 4
        inj = FaultInjector().install(eng)
        manual_clock.set_ms(1000)

        fetch_err = InjectedFault("record-A fetch")
        inj.fail_fetch(eng.flush_seq + 1, fetch_err)
        ops_a = [eng.submit_entry("r") for _ in range(3)]
        eng.flush_async()
        ops_b = [eng.submit_entry("r") for _ in range(3)]
        eng.flush_async()
        assert len(eng._pending_fetches) == 2

        tele0 = eng.telemetry.counters_snapshot()["coalesced_fallbacks"]
        with pytest.raises(InjectedFault) as ei:
            eng.drain()
        assert ei.value is fetch_err
        # B materialized despite A's failure (one wedged fetch must not
        # strand the queue) …
        assert all(op.verdict is not None and op.verdict.admitted
                   for op in ops_b)
        # … the batch fetch fell back per-record …
        assert (
            eng.telemetry.counters_snapshot()["coalesced_fallbacks"]
            == tele0 + 1
        )
        # … and A's readers see A's error, repeatably.
        for op in ops_a:
            with pytest.raises(InjectedFault):
                _ = op.verdict


def _settle_run(manual_clock, monkeypatch, mode, fault):
    """One chunk of 8 singles and a bulk group of 8 on a count-5 rule,
    settled through ``mode``; returns what a caller can observe."""
    from sentinel_tpu.runtime.engine import Engine

    armed = fault == "fetch_armed"
    config.set(config.FAILOVER_ENABLED, "true" if armed else "false")
    config.set(config.FAILOVER_CHECKPOINT_EVERY, "1")
    eng = Engine(clock=manual_clock)
    eng.pipeline_depth = 2 if mode == "depth2" else 0
    eng.set_flow_rules([st.FlowRule("r", count=5)])
    logged = []
    monkeypatch.setattr(eng.block_log, "log_batch", logged.extend)
    given = []
    give_all = eng._arena.give_all
    monkeypatch.setattr(
        eng._arena, "give_all", lambda st_: (given.append(len(st_)), give_all(st_))
    )
    inj = FaultInjector().install(eng)
    manual_clock.set_ms(1000)
    err = RuntimeError("fill failed")
    if fault == "fetch_armed":
        inj.fail_fetch(eng.flush_seq + 1)
    elif fault == "fill_unarmed":
        def broken_fill(*a, **kw):
            raise err

        monkeypatch.setattr(eng, "_fill_results", broken_fill)
    ops = [eng.submit_entry("r", ts=1000) for _ in range(8)]
    g = eng.submit_bulk("r", 8, ts=1000)
    drains0 = eng.telemetry.hist_drain.count
    raised = {}
    for step in ("flush", "drain"):
        try:
            if step == "drain":
                eng.drain()
            elif mode == "async":
                eng.flush_async()
            else:
                eng.flush()
        except RuntimeError as exc:
            raised[step] = exc
    (span,) = eng.telemetry.spans()[-1:]
    obs = {
        "raised": {k: v is err for k, v in raised.items()},
        "logged": list(logged),
        "given": list(given),
        "degraded_state": eng.failover.degraded,
        "span": (span.deferred, span.settled, span.quarantined),
        "drain_samples": eng.telemetry.hist_drain.count - drains0,
        "fetch_fill_ms": (span.fetch_ms, span.fill_ms),
    }
    if fault == "fill_unarmed" and mode != "depth0":
        # Every reader re-raises the stored error, repeatably.
        for _ in range(2):
            for read in (lambda: ops[0].verdict, lambda: g.admitted_count):
                with pytest.raises(RuntimeError) as ei:
                    read()
                assert ei.value is err
        obs["verdicts"] = obs["bulk"] = None
    else:
        obs["verdicts"] = [
            None if v is None else (v.admitted, v.reason, v.wait_ms, v.degraded)
            for v in (op.verdict for op in ops)
        ]
        obs["bulk"] = None if g.admitted is None else (
            g.admitted.tolist(), g.reason.tolist(), g.wait_ms.tolist()
        )
    return obs


class TestSettleEquivalence:
    @pytest.fixture(autouse=True)
    def _config_sandbox(self):
        with config._lock:
            saved = dict(config._runtime)
        yield
        with config._lock:
            config._runtime.clear()
            config._runtime.update(saved)

    @pytest.mark.parametrize("fault", ["healthy", "fetch_armed", "fill_unarmed"])
    @pytest.mark.parametrize("mode", ["depth0", "depth2", "async"])
    def test_settle_paths_agree(self, manual_clock, monkeypatch, mode, fault):
        """The same chunk settles to the same verdicts, reasons and
        block-log items at every depth; a fault's provenance, its
        error's route to the caller, the arena give-back and depth 0's
        own telemetry are pinned per mode."""
        ref = _settle_run(manual_clock, monkeypatch, "depth0", fault)
        monkeypatch.undo()
        obs = _settle_run(manual_clock, monkeypatch, mode, fault)
        deferred = mode != "depth0"
        if fault != "fill_unarmed" or not deferred:
            assert obs["verdicts"] == ref["verdicts"]
            assert obs["bulk"] == ref["bulk"]
        assert obs["logged"] == ref["logged"]
        assert obs["degraded_state"] == (fault == "fetch_armed")
        assert obs["span"][0] == deferred
        if fault == "healthy":
            adm = [v[0] for v in obs["verdicts"]] + obs["bulk"][0]
            assert sum(adm) == 5 and not any(v[3] for v in obs["verdicts"])
            assert len(obs["logged"]) > 0
            assert obs["raised"] == {}
            # Staging goes back to the arena after the successful fetch.
            assert len(obs["given"]) == 1 and obs["given"][0] > 0
            assert obs["span"] == (deferred, True, False)
        elif fault == "fetch_armed":
            # Degraded provenance on every op; the device error never
            # reaches a caller; the faulted chunk's staging is dropped.
            assert all(v is not None and v[3] for v in obs["verdicts"])
            assert obs["raised"] == {}
            assert obs["given"] == []
            assert obs["span"] == (deferred, True, True)
        else:
            # At depth 0 flush() raises and the chunk's ops stay
            # unfilled; deferred, the drain raises the stored error.
            assert obs["raised"] == {"drain" if deferred else "flush": True}
            assert obs["logged"] == []
            assert obs["given"] == []
            assert obs["span"][1:] == (False, False)
            if not deferred:
                assert obs["verdicts"] == [None] * 8 and obs["bulk"] is None
        if not deferred:
            # Depth 0 fills the span's fetch and fill phases and takes
            # no drain-histogram sample.
            assert obs["drain_samples"] == 0
            if fault == "healthy":
                assert min(obs["fetch_fill_ms"]) > 0


class TestDirtyShutdown:
    def test_stop_auto_flush_flags_a_stuck_flusher(self, manual_clock):
        eng = _mk_engine(manual_clock)
        inj = FaultInjector().install(eng)
        manual_clock.set_ms(1000)
        release = threading.Event()
        # Wedge the auto-flusher inside its flush's fetch.
        inj.hang_fetch(eng.flush_seq + 1, seconds=30.0, until=release)
        eng.submit_entry("r")
        eng.start_auto_flush(interval_ms=1)
        deadline = time.monotonic() + 5.0
        # Wait until the flusher is actually inside the hang.
        while not any(k == "hang" for k, _ in inj.fired):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert not eng.closed_dirty
        eng.stop_auto_flush(join_timeout_s=0.2)
        assert eng.closed_dirty
        release.set()  # unwedge; the daemon thread exits on its own

    def test_join_clean_reports_a_stuck_thread(self):
        from sentinel_tpu.datasource.base import join_clean

        release = threading.Event()
        t = threading.Thread(target=release.wait, daemon=True)
        t.start()
        try:
            assert join_clean(None, 0.1, "x") is True
            assert join_clean(t, 0.05, "x") is False
        finally:
            release.set()
            t.join(timeout=1)
        assert join_clean(t, 0.1, "x") is True

    def test_longpoll_close_flags_stuck_watcher(self):
        """A long-poll source whose watcher ignores the stop signal for
        longer than the close join marks itself closed_dirty instead of
        pretending the shutdown was clean."""
        from sentinel_tpu.datasource.base import join_clean
        from sentinel_tpu.datasource.longpoll import LongPollPushDataSource

        release = threading.Event()

        class StuckSource(LongPollPushDataSource):
            _thread_name = "stuck-test-watcher"

            def __init__(self):
                super().__init__(lambda raw: [], 1024)

            def read_source(self):
                return None

            def _poll_once(self):
                release.wait(30.0)
                raise RuntimeError("done")

            def _on_poll_error(self, e):
                pass

            def close(self):  # shorter join than the stock 5 s
                self._stop.set()
                self.closed_dirty = self.closed_dirty or not join_clean(
                    self._thread, 0.1, type(self).__name__
                )

        src = StuckSource()
        src._thread = threading.Thread(
            target=src._watch_loop, daemon=True
        )
        src._thread.start()
        time.sleep(0.05)
        src.close()
        try:
            assert src.closed_dirty
        finally:
            release.set()
            src._thread.join(timeout=1)
