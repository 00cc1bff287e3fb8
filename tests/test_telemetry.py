"""Engine flight recorder: spans, histograms, blocked sketch, exports.

Covers metrics/telemetry.py + metrics/histogram.py, the kernel's
device-side top-K blocked-resource fold (runtime/flush.py sketch_k),
the Prometheus ``sentinel_engine_*`` family, the ``telemetry``
transport command, ParamIndex intern-cache counters, and the
metric-log ``__engine__`` roll-in."""

import json
import time

import numpy as np
import pytest

import sentinel_tpu as st
from sentinel_tpu.metrics.histogram import LatencyHistogram
from sentinel_tpu.metrics.metric_log import MetricTimer, MetricWriter
from sentinel_tpu.metrics.telemetry import SpaceSaving, TelemetryBus
from sentinel_tpu.models.rules import ParamFlowRule
from sentinel_tpu.utils.config import config


class TestLatencyHistogram:
    def test_pow2_bucket_placement(self):
        h = LatencyHistogram(base_ms=1.0, n_buckets=4)  # bounds 1,2,4,8
        for ms, want in [(0.0, 0), (1.0, 0), (1.5, 1), (2.0, 1), (2.5, 2),
                         (4.0, 2), (7.9, 3), (8.0, 3), (8.1, 4), (1e9, 4)]:
            h2 = LatencyHistogram(base_ms=1.0, n_buckets=4)
            h2.record(ms)
            counts, _ = h2.snapshot_counts()
            assert counts[want] == 1, (ms, want, counts)
        assert h.count == 0

    def test_record_many_matches_record(self):
        vals = [0.01, 0.5, 1.7, 3.3, 100.0, 1e6, 0.0]
        a = LatencyHistogram()
        b = LatencyHistogram()
        for v in vals:
            a.record(v)
        b.record_many(vals)
        ca, sa = a.snapshot_counts()
        cb, sb = b.snapshot_counts()
        assert (ca == cb).all() and sa == pytest.approx(sb)

    def test_merge_and_percentile(self):
        a = LatencyHistogram(base_ms=1.0, n_buckets=8)
        b = LatencyHistogram(base_ms=1.0, n_buckets=8)
        for _ in range(99):
            a.record(1.0)  # bucket 0
        b.record(100.0)  # bucket 7
        a.merge(b)
        assert a.count == 100
        assert a.percentile(0.5) == 1.0
        assert a.percentile(0.995) == 128.0  # the tail observation's bound
        with pytest.raises(ValueError):
            a.merge(LatencyHistogram(base_ms=2.0, n_buckets=8))

    def test_prometheus_lines_cumulative(self):
        h = LatencyHistogram(base_ms=1.0, n_buckets=3)  # bounds 1,2,4
        for v in (0.5, 1.5, 3.0, 99.0):
            h.record(v)
        lines = h.prometheus_lines("x_ms", "help")
        assert "# TYPE x_ms histogram" in lines
        buckets = [l for l in lines if l.startswith("x_ms_bucket")]
        counts = [int(l.rsplit(" ", 1)[1]) for l in buckets]
        assert counts == [1, 2, 3, 4]  # cumulative, +Inf last
        assert 'le="+Inf"' in buckets[-1]
        assert any(l.startswith("x_ms_count") and l.endswith("4") for l in lines)


class TestSpaceSaving:
    def test_exact_below_capacity(self):
        s = SpaceSaving(capacity=8)
        for i in range(5):
            s.offer(f"k{i}", i + 1)
        s.offer("k4", 10)
        top = dict((k, c) for k, c, _ in s.topk(8))
        assert top["k4"] == 15 and top["k0"] == 1
        assert all(e == 0 for _, _, e in s.topk(8))

    def test_eviction_overestimates_bounded(self):
        s = SpaceSaving(capacity=2)
        s.offer("a", 100)
        s.offer("b", 1)
        s.offer("c", 50)  # evicts b (floor 1): count 51, error 1
        top = {k: (c, e) for k, c, e in s.topk(3)}
        assert "b" not in top
        assert top["c"] == (51, 1)
        assert top["a"] == (100, 0)


class TestFlightRecorder:
    def test_spans_per_flush_and_counters(self, manual_clock, engine):
        st.flow_rule_manager.load_rules([st.FlowRule("fr", count=1e9)])
        engine.submit_many([{"resource": "fr"} for _ in range(10)])
        engine.flush()
        spans = engine.telemetry.spans()
        assert spans, "flush must record a span"
        s = spans[-1]
        assert s.n_entries == 10 and s.rows == 10
        assert s.settled and not s.deferred
        assert s.encode_ms >= 0.0 and s.dispatch_ms >= 0.0
        c = engine.telemetry.counters_snapshot()
        assert c["flushes"] >= 1 and c["ops"] >= 10
        assert engine.telemetry.hist_flush.count >= 1
        assert engine.telemetry.hist_e2e.count >= 1

    def test_ring_is_bounded(self, manual_clock, engine):
        engine.telemetry = TelemetryBus(ring=4)
        st.flow_rule_manager.load_rules([st.FlowRule("rb", count=1e9)])
        for _ in range(7):
            engine.submit_entry("rb")
            engine.flush()
        tele = engine.telemetry
        assert len(tele.spans()) == 4
        assert tele.counters_snapshot()["flushes"] == 7

    def test_pipelined_spans_settle_lazily(self, manual_clock, engine):
        st.flow_rule_manager.load_rules([st.FlowRule("pp", count=1e9)])
        engine.pipeline_depth = 2
        try:
            for _ in range(5):
                engine.submit_bulk("pp", 16)
                engine.flush()
            tele = engine.telemetry
            deferred = [s for s in tele.spans() if s.deferred]
            assert deferred, "depth-2 flushes must record deferred spans"
            assert any(not s.settled for s in deferred), (
                "a depth-2 pipeline keeps unsettled spans in flight"
            )
            engine.drain()
            assert all(s.settled for s in tele.spans())
            # Every span's occupancy sample is within the depth bound.
            assert all(0 <= s.inflight <= 2 for s in deferred)
            assert tele.counters_snapshot()["deferred_flushes"] >= 5
        finally:
            engine.pipeline_depth = 0

    def test_disabled_records_nothing(self, manual_clock):
        from sentinel_tpu.runtime.engine import Engine

        config.set(config.TELEMETRY_ENABLED, "false")
        try:
            eng = Engine()
            assert not eng.telemetry.enabled
            assert eng._blk_topk_k == 0  # kernel top-K fold compiled away
            eng.set_flow_rules([st.FlowRule("off", count=1)])
            for _ in range(3):
                eng.submit_entry("off")
                eng.flush()
            assert eng.telemetry.spans() == []
            assert eng.telemetry.counters_snapshot()["flushes"] == 0
            assert eng.telemetry.blocked_sketch.topk(5) == []
            # The breakdown reads the (unrecorded) spans: all zeros.
            assert set(eng.last_flush_host_ms.values()) == {0.0}
        finally:
            config.set(config.TELEMETRY_ENABLED, "true")

    def test_arena_counter_deltas(self, manual_clock, engine):
        st.flow_rule_manager.load_rules([st.FlowRule("ar", count=1e9)])
        for _ in range(3):
            engine.submit_many([{"resource": "ar"} for _ in range(8)])
            engine.flush()
        spans = [s for s in engine.telemetry.spans() if s.n_entries == 8]
        # Steady state: the repeated shape is served from the pool.
        assert spans[-1].arena_hits > 0
        c = engine.telemetry.counters_snapshot()
        assert c["arena_hits"] + c["arena_misses"] > 0


class TestBlockedSketch:
    def test_topk_matches_exact_recount(self, manual_clock, engine):
        """Differential: the device sketch must equal a host-side exact
        recount of blocked acquire weight per resource."""
        st.flow_rule_manager.load_rules(
            [
                st.FlowRule("s_hot", count=1),
                st.FlowRule("s_warm", count=3),
                st.FlowRule("s_free", count=1e9),
            ]
        )
        manual_clock.set_ms(100)
        reqs = (
            [{"resource": "s_hot", "ts": 100} for _ in range(6)]
            + [{"resource": "s_warm", "ts": 100} for _ in range(5)]
            + [{"resource": "s_free", "ts": 100} for _ in range(4)]
        )
        ops = engine.submit_many(reqs)
        engine.flush()
        exact = {}
        for op, req in zip(ops, reqs):
            v = op.verdict
            assert v is not None
            if not v.admitted:
                exact[req["resource"]] = exact.get(req["resource"], 0) + 1
        assert exact, "test must actually block something"
        got = dict(engine.telemetry.last_blocked_topk)
        assert got == exact
        # The running sketch agrees too (single flush, no merging yet).
        sk = {k: c for k, c, _ in engine.telemetry.blocked_sketch.topk(8)}
        for k, w in exact.items():
            assert sk[k] == w

    def test_host_recount_fallback_matches_device_fold(self, manual_clock, engine):
        """Flush paths without the kernel fold (the sharded mesh flush)
        feed the sketch via a host-side recount of the filled verdicts
        — it must agree with what the device fold produced for the same
        chunk."""
        st.flow_rule_manager.load_rules([st.FlowRule("hr", count=2)])
        manual_clock.set_ms(100)
        ops = engine.submit_many(
            [{"resource": "hr", "ts": 100} for _ in range(6)]
        )
        engine.flush()
        device_topk = list(engine.telemetry.last_blocked_topk)
        assert device_topk  # the kernel fold saw the blocks
        engine.telemetry.last_blocked_topk = []
        engine._fold_blocked_recount([op for op in ops if op is not None], [])
        assert engine.telemetry.last_blocked_topk == device_topk

    @pytest.mark.mesh
    def test_mesh_flush_feeds_sketch(self, manual_clock, engine):
        """The sharded path has no device fold; the host recount must
        still populate the sketch."""
        st.flow_rule_manager.load_rules([st.FlowRule("ms", count=4)])
        engine.enable_mesh(8)
        try:
            manual_clock.set_ms(100)
            g = engine.submit_bulk("ms", 64, ts=100)
            engine.flush()
            blocked = int((~g.admitted).sum())
            assert blocked > 0
            assert dict(engine.telemetry.last_blocked_topk)["ms"] == blocked
        finally:
            engine.disable_mesh()

    def test_bulk_acquire_weights(self, manual_clock, engine):
        """Weighted recount through the bulk path: blocked weight is the
        acquire sum, not the op count."""
        st.flow_rule_manager.load_rules([st.FlowRule("s_bulk", count=5)])
        manual_clock.set_ms(100)
        acquire = np.array([2, 2, 2, 3, 4], dtype=np.int32)
        g = engine.submit_bulk("s_bulk", 5, ts=100, acquire=acquire)
        engine.flush()
        blocked_w = int(acquire[~g.admitted].sum())
        assert blocked_w > 0
        assert dict(engine.telemetry.last_blocked_topk)["s_bulk"] == blocked_w


class TestInternCacheCounters:
    def _param_engine(self, engine):
        st.flow_rule_manager.load_rules([st.FlowRule("ic", count=1e9)])
        engine.set_param_rules(
            {"ic": [ParamFlowRule("ic", param_idx=0, count=1e9)]}
        )

    def test_bulk_hits_misses_and_reload_reset(self, manual_clock, engine):
        self._param_engine(engine)
        col = [f"ip{i % 4}" for i in range(16)]
        engine.submit_bulk("ic", 16, ts=100, args_column=[(v,) for v in col])
        engine.flush()
        stats1 = engine.param_index.cache_stats()
        assert stats1["misses"] == 4  # 4 distinct values resolve once
        engine.submit_bulk("ic", 16, ts=200, args_column=[(v,) for v in col])
        engine.flush()
        stats2 = engine.param_index.cache_stats()
        assert stats2["hits"] >= 16  # second window: all values cached
        assert stats2["misses"] == 4
        # Span attribution: the flush that drained the submissions
        # carries the intern delta.
        span = engine.telemetry.spans()[-1]
        assert span.intern_hits >= 16
        # Reload invalidates the cache wholesale — counters reset.
        self._param_engine(engine)
        stats3 = engine.param_index.cache_stats()
        assert stats3 == {"hits": 0, "misses": 0, "evictions": 0, "interned": 0}
        # Telemetry snapshot surfaces the live (post-reload) counters.
        snap = engine.telemetry.snapshot(engine)
        assert snap["param_cache"]["hits"] == 0


class TestExports:
    def test_prometheus_engine_series(self, manual_clock, engine):
        from sentinel_tpu.transport.prometheus import render_metrics

        st.flow_rule_manager.load_rules([st.FlowRule("pm", count=1)])
        manual_clock.set_ms(50)
        for _ in range(3):
            st.try_entry("pm")
        text = render_metrics(engine)
        for needle in (
            "sentinel_engine_flush_duration_ms_bucket",
            "sentinel_engine_drain_duration_ms_bucket",
            "sentinel_engine_e2e_duration_ms_bucket",
            "sentinel_engine_pipeline_occupancy",
            "sentinel_engine_pipeline_mean_inflight",
            "sentinel_engine_last_flush_encode_ms",
            "sentinel_engine_last_flush_dispatch_ms",
            "sentinel_engine_last_flush_fill_ms",
            "sentinel_engine_flushes_total",
            "sentinel_engine_coalesced_fallback_total",
            "sentinel_engine_param_cache_hits_total",
        ):
            assert needle in text, needle
        assert 'sentinel_engine_blocked_weight{resource="pm"}' in text
        # The flush histogram actually accumulated observations.
        count_line = [
            l for l in text.splitlines()
            if l.startswith("sentinel_engine_flush_duration_ms_count")
        ][0]
        assert int(count_line.rsplit(" ", 1)[1]) >= 1

    def test_pipeline_occupancy_gauge(self, manual_clock, engine):
        from sentinel_tpu.transport.prometheus import engine_telemetry_lines

        st.flow_rule_manager.load_rules([st.FlowRule("po", count=1e9)])
        engine.pipeline_depth = 2
        try:
            engine.pipeline_stats(reset=True)
            for _ in range(8):
                engine.submit_bulk("po", 8)
                engine.flush()
            lines = engine_telemetry_lines(engine)
        finally:
            engine.pipeline_depth = 0
            engine.drain()
        occ = [
            float(l.rsplit(" ", 1)[1])
            for l in lines
            if l.startswith("sentinel_engine_pipeline_occupancy ")
        ][0]
        assert 0.0 < occ <= 1.0

    def test_telemetry_command(self, manual_clock, engine):
        from sentinel_tpu.transport import handlers
        from sentinel_tpu.transport.command_center import CommandRequest

        st.flow_rule_manager.load_rules([st.FlowRule("tc", count=1)])
        for _ in range(3):
            st.try_entry("tc")
        resp = handlers.telemetry_handler(
            CommandRequest(path="telemetry", params={"spans": "2"}, body="")
        )
        assert resp.success
        d = json.loads(resp.result)
        assert d["enabled"] is True
        assert d["counters"]["flushes"] >= 3
        assert d["flush_ms"]["count"] >= 3
        assert len(d["spans"]) == 2
        assert {"resource": "tc", "weight": 1} in d["last_flush_blocked_topk"]
        assert d["pipeline_depth"] == 0
        bad = handlers.telemetry_handler(
            CommandRequest(path="telemetry", params={"spans": "x"}, body="")
        )
        assert not bad.success

    def test_metric_log_engine_rollin(self, manual_clock, engine, tmp_path):
        st.flow_rule_manager.load_rules([st.FlowRule("ml", count=1e9)])
        for sec in range(2):
            for i in range(4):
                manual_clock.set_ms(sec * 1000 + i * 10)
                with st.entry("ml"):
                    pass
        manual_clock.set_ms(2500)
        timer = MetricTimer(
            engine, writer=MetricWriter(base_dir=str(tmp_path), app_name="tele")
        )
        lines = timer.run_once()
        eng_lines = [l for l in lines if l.resource == "__engine__"]
        assert len(eng_lines) == 2  # seconds 0 and 1
        # entry() flushes per call: >= 4 flushes and >= 4 ops per second
        # (exits flush too).
        assert all(l.pass_qps >= 4 for l in eng_lines)
        assert all(l.success_qps >= 4 for l in eng_lines)
        # Sorted into the per-second stream, parseable from disk.
        ts = [l.timestamp for l in lines]
        assert ts == sorted(ts)


@pytest.mark.slow
class TestOverhead:
    def test_enabled_within_2pct_of_disabled(self, manual_clock):
        """Recorder overhead contract: the telemetry-enabled engine
        stays within 2% of telemetry-disabled on the deferred-mode
        loop (median of repeats; slow tier — wall-clock sensitive)."""
        from sentinel_tpu.runtime.engine import Engine

        def run(enabled: bool) -> float:
            config.set(config.TELEMETRY_ENABLED, "true" if enabled else "false")
            try:
                eng = Engine()
                eng.set_flow_rules([st.FlowRule("ov", count=1e9)])
                reqs = [{"resource": "ov", "ts": 100} for _ in range(2048)]
                eng.submit_many(reqs)
                eng.flush()  # warm-up/compile
                best = float("inf")
                for _ in range(5):
                    t0 = time.perf_counter()
                    for _ in range(10):
                        eng.submit_many(reqs)
                        eng.flush()
                    best = min(best, time.perf_counter() - t0)
                return best
            finally:
                config.set(config.TELEMETRY_ENABLED, "true")

        t_off = run(False)
        t_on = run(True)
        assert t_on <= t_off * 1.02 + 0.01, (t_on, t_off)


# Every phase span and the phase that contains it.
_PHASE_PARENT = {
    "sentinel.flush.drain": "sentinel.flush",
    "sentinel.flush.swap": "sentinel.flush",
    "sentinel.encode": "sentinel.flush",
    "sentinel.encode.staging": "sentinel.encode",
    "sentinel.encode.shaping": "sentinel.encode",
    "sentinel.encode.param": "sentinel.encode",
    "sentinel.dispatch": "sentinel.flush",
    "sentinel.fetch": "sentinel.flush",
    "sentinel.fill": "sentinel.flush",
    "sentinel.post_flush": "sentinel.flush",
    "sentinel.submit.param": "sentinel.submit",
}
# FlushSpan field that reads each timed phase.
_PHASE_FIELD = {
    "sentinel.flush.swap": "swap_ms",
    "sentinel.encode": "encode_ms",
    "sentinel.encode.shaping": "shaping_encode_ms",
    "sentinel.encode.param": "param_encode_ms",
    "sentinel.dispatch": "dispatch_ms",
    "sentinel.fetch": "fetch_ms",
    "sentinel.fill": "fill_ms",
}


def _phase_world(engine):
    """A tiny engine with a rate-limiter rule and a hot-param rule."""
    from sentinel_tpu.models import constants as C

    st.flow_rule_manager.load_rules([
        st.FlowRule("ph_rl", count=20,
                    control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER,
                    max_queueing_time_ms=200),
        st.FlowRule("ph_p", count=1e9),
    ])
    engine.set_param_rules(
        {"ph_p": [ParamFlowRule("ph_p", param_idx=0, count=3)]}
    )


def _phase_flush(engine, clock, t: int):
    """Exits of an earlier group, two bulk groups, one flush; returns
    the verdict columns."""
    clock.set_ms(t)
    g0 = engine.submit_bulk("ph_p", 4, ts=t, args_column=[("z",)] * 4)
    engine.flush()
    engine.submit_exit_bulk(g0.rows, g0.admitted_count or 1, rt=5, resource="ph_p")
    g1 = engine.submit_bulk("ph_rl", 16, ts=t)
    g2 = engine.submit_bulk(
        "ph_p", 16, ts=t, args_column=[(f"k{i % 5}",) for i in range(16)]
    )
    engine.flush()
    return [np.asarray(a) for g in (g1, g2)
            for a in (g.admitted, g.reason, g.wait_ms)]


class TestProfilerPhases:
    def _trace(self, engine, clock, tmp_path):
        import jax

        _phase_world(engine)
        _phase_flush(engine, clock, 1000)  # compiles every shape
        with jax.profiler.trace(str(tmp_path)):
            verdicts = _phase_flush(engine, clock, 2000)
        from jax.profiler import ProfileData
        import glob

        (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        lines = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("sentinel."):
                        lines.setdefault(line.name, []).append((
                            ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                            dict(ev.stats),
                        ))
        return verdicts, lines

    def test_phase_spans_nest_and_share_the_flush_seq(
        self, manual_clock, engine, tmp_path
    ):
        _, lines = self._trace(engine, manual_clock, tmp_path)
        assert len(lines) == 1, list(lines)  # one flushing thread
        (events,) = lines.values()
        # Two flushes are profiled (g0's, then the measured one).
        flush = [e for e in events if e[0] == "sentinel.flush"]
        seq = flush[-1][3]["flush"]
        assert flush[-1][3]["step_num"] == seq
        mine = [e for e in events if e[3].get("flush") == seq]
        names = {e[0] for e in mine}
        assert set(_PHASE_PARENT) | {"sentinel.flush", "sentinel.submit"} <= names
        for name, t0, t1, _ in mine:
            parent = _PHASE_PARENT.get(name)
            if parent is None:
                continue
            assert any(p[0] == parent and p[1] <= t0 and t1 <= p[2]
                       for p in mine), (name, parent)
        shaping = next(e for e in mine if e[0] == "sentinel.encode.shaping")
        assert shaping[3]["items"] == 16 and shaping[3]["padded"] == 16
        fill = next(e for e in mine if e[0] == "sentinel.fill")[3]
        assert (fill["groups"], fill["spec_groups"]) == (2, 0)
        # One block-log item per (group, reason, blocking rule): the paced
        # group's flow blocks and the param group's blocks.
        assert fill["block_items"] == engine.telemetry.spans()[-1].block_items == 2
        # Accounting runs: the two groups over 32 entries, one exit group
        # over 8 exits; tables this small are as long as their side.
        staging = next(e for e in mine if e[0] == "sentinel.encode.staging")[3]
        assert (staging["runs"], staging["runs_padded"]) == (2, 32)
        assert (staging["x_runs"], staging["x_runs_padded"]) == (1, 8)

    def test_flush_span_phases_equal_profiler_spans(
        self, manual_clock, engine, tmp_path
    ):
        _, lines = self._trace(engine, manual_clock, tmp_path)
        (events,) = lines.values()
        span = engine.telemetry.spans()[-1]
        seq = max(e[3]["flush"] for e in events)
        for name, field in _PHASE_FIELD.items():
            (ev,) = [e for e in events if e[0] == name and e[3]["flush"] == seq]
            assert getattr(span, field) == pytest.approx(
                (ev[2] - ev[1]) / 1e6, abs=0.05
            ), name
        assert span.settle_ms == pytest.approx(span.fetch_ms + span.fill_ms)
        assert span.shaping_items == 16 and span.groups == 2
        assert (span.spec_groups, span.block_items) == (0, 2)
        assert span.as_dict()["block_items"] == 2
        view = engine.last_flush_host_ms
        assert view["kernel_ms"] == pytest.approx(span.dispatch_ms + span.fetch_ms)
        assert view["fill_ms"] == pytest.approx(span.fill_ms)

    def test_verdicts_identical_with_profiler_on_and_off(
        self, manual_clock, engine, tmp_path
    ):
        from sentinel_tpu.core import api

        traced, _ = self._trace(engine, manual_clock, tmp_path)
        manual_clock.set_ms(0)
        api.reset(clock=manual_clock)
        eng = api.get_engine()
        _phase_world(eng)
        _phase_flush(eng, manual_clock, 1000)
        plain = _phase_flush(eng, manual_clock, 2000)
        for a, b in zip(traced, plain):
            np.testing.assert_array_equal(a, b)


def test_kernel_stage_scopes_in_lowering(monkeypatch):
    """Every stage of the flush kernel carries its ``sentinel/<stage>``
    scope in the lowered program's op metadata."""
    from sentinel_tpu.models import constants as C
    from sentinel_tpu.runtime import engine as E
    from sentinel_tpu.runtime import flush as F
    from sentinel_tpu.rules.system_manager import SystemConfig
    from sentinel_tpu.utils.clock import ManualClock

    got = {}

    class _Captured(Exception):
        pass

    def record(*args, **kw):
        got.update(args=args, kw=kw)
        raise _Captured

    monkeypatch.setattr(E, "flush_step_full_jit", record)
    config.set(config.SKETCH_ENABLED, "true")
    try:
        eng = E.Engine(clock=ManualClock(start_ms=10_000))
    finally:
        config.set(config.SKETCH_ENABLED,
                   config.DEFAULTS[config.SKETCH_ENABLED])
    eng.set_flow_rules([
        st.FlowRule("rl", count=10, control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER),
    ])
    eng.set_degrade_rules([st.DegradeRule("rl", grade=1, count=0.5, time_window=1)])
    eng.set_param_rules({"api": [st.ParamFlowRule("api", param_idx=0, count=3)]})
    eng.set_system_config(SystemConfig(qps=1000.0))
    g = eng.submit_bulk("rl", 8)
    eng.submit_exit_bulk(g.rows, 4, rt=5, resource="rl")
    eng.submit_bulk("api", 8, args_column=[(f"k{i}",) for i in range(8)])
    with pytest.raises(_Captured):
        eng.flush()
    text = F.flush_step_full_jit.lower(*got["args"], **got["kw"]).as_text(
        debug_info=True
    )
    for stage in ("roll", "exits", "system", "param", "flow", "shaping",
                  "degrade", "account", "topk", "sketch"):
        assert f"sentinel/{stage}" in text, stage
