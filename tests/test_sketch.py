"""Sketch tier: count-min error bounds, candidate table, promotion/
demotion, O(1) device memory, failover mirror, exports.

The acceptance contract (ISSUE 9): a workload with >=100k distinct
unconfigured keys runs with O(1) device memory; its top hot keys are
auto-promoted to exact dense rows within a bounded number of flushes;
a promoted key's verdicts are bit-identical to a manually configured
dense rule from the promotion flush onward (pipeline depths {0, 2});
and the tier disabled is verdict-parity with today.
"""

from __future__ import annotations

import numpy as np
import pytest

import sentinel_tpu as st
from sentinel_tpu.models.rules import FlowRule, ParamFlowRule
from sentinel_tpu.runtime.engine import Engine
from sentinel_tpu.runtime.sketch import (
    SketchBatch,
    cm_estimate,
    key_id,
    make_sketch_state,
    sketch_fold,
)
from sentinel_tpu.utils.clock import ManualClock
from sentinel_tpu.utils.config import config


def _fold_stream(
    weights_by_key, width=1024, depth=4, cands=16, batch_size=256, decay_at=(),
):
    """Feed {key: weight} through the device fold in batches; returns
    (state, exact dict keyed by id)."""
    import jax.numpy as jnp

    state = make_sketch_state(depth, width, cands)
    items = [(key_id(k), w) for k, w in weights_by_key.items()]
    exact = {}
    step = 0
    for off in range(0, len(items), batch_size):
        chunk = items[off : off + batch_size]
        n = 1
        while n < max(len(chunk), 8):
            n <<= 1
        ids = np.full(n, -1, dtype=np.int32)
        w = np.zeros(n, dtype=np.int32)
        for j, (i, wt) in enumerate(chunk):
            ids[j] = i
            w[j] = wt
        decay = step in decay_at
        if decay:
            for i in list(exact):
                exact[i] >>= 1
        state = sketch_fold(
            state, SketchBatch(ids=jnp.asarray(ids), w=jnp.asarray(w)),
            decay=decay,
        )
        for i, wt in chunk:
            exact[i] = exact.get(i, 0) + wt
        step += 1
    return state, exact


class TestCountMinBounds:
    """Property tests of the device fold against exact host counts:
    the estimate is ALWAYS >= exact and within eps*N (eps = 8/width —
    loose vs the probabilistic 2/width-per-row bound, but deterministic
    for the fixed seeds) on adversarial distributions."""

    def _assert_bounds(self, weights_by_key, width=1024):
        state, exact = _fold_stream(weights_by_key, width=width)
        cm = np.asarray(state.cm)
        ids = np.asarray(sorted(exact), dtype=np.int64)
        est = cm_estimate(cm, ids)
        total = sum(exact.values())
        eps_n = max(1, (8 * total) // width)
        for i, e in zip(ids.tolist(), est.tolist()):
            assert e >= exact[i], f"count-min under-estimated id {i}"
            assert e - exact[i] <= eps_n, (
                f"id {i}: est {e} vs exact {exact[i]} exceeds eps*N={eps_n}"
            )

    def test_zipf(self):
        rng = np.random.default_rng(7)
        draws = rng.zipf(1.3, size=20000)
        weights = {}
        for v in draws.tolist():
            k = f"z{v}"
            weights[k] = weights.get(k, 0) + 1
        self._assert_bounds(weights)

    def test_all_distinct(self):
        self._assert_bounds({f"d{j}": 1 for j in range(5000)})

    def test_single_key(self):
        self._assert_bounds({"only": 123456})

    def test_candidate_table_holds_true_heavy_hitters(self):
        weights = {f"cold{j}": 1 for j in range(2000)}
        hot = {f"hot{j}": 500 + j for j in range(8)}
        weights.update(hot)
        state, exact = _fold_stream(weights, cands=16)
        ids = np.asarray(state.cand_ids).tolist()
        cnts = np.asarray(state.cand_cnt).tolist()
        by_id = {i: c for i, c in zip(ids, cnts) if i >= 0}
        for k, w in hot.items():
            i = key_id(k)
            assert i in by_id, f"heavy hitter {k} missing from candidates"
            assert by_id[i] >= w  # estimate >= exact
        # Candidate counts are count-min estimates as of the key's last
        # touch (the CM+candidate design): never above the current
        # point query (cells only grow between touches), and never
        # below the key's exact count when the key rode the final batch.
        cm = np.asarray(state.cm)
        for i, c in by_id.items():
            assert c <= int(cm_estimate(cm, np.asarray([i]))[0])

    def test_decay_halves_counts(self):
        import jax.numpy as jnp

        state = make_sketch_state(2, 64, 4)
        ids = np.full(8, -1, dtype=np.int32)
        w = np.zeros(8, dtype=np.int32)
        ids[0] = key_id("k")
        w[0] = 1000
        sb = SketchBatch(ids=jnp.asarray(ids), w=jnp.asarray(w))
        state = sketch_fold(state, sb, decay=False)
        empty = SketchBatch(
            ids=jnp.full((8,), -1, dtype=jnp.int32),
            w=jnp.zeros((8,), dtype=jnp.int32),
        )
        state = sketch_fold(state, empty, decay=True)
        est = int(cm_estimate(np.asarray(state.cm), np.asarray([key_id("k")]))[0])
        assert est == 500
        assert int(np.asarray(state.cand_cnt).max()) == 500


@pytest.fixture()
def sketch_config():
    """Arm the sketch tier with fast promotion for engine tests; the
    tier reads config at Engine construction."""
    config.set(config.SKETCH_ENABLED, "true")
    config.set(config.SKETCH_PROMOTE_QPS, "5")
    config.set(config.SKETCH_RESOURCE_QPS, "50")
    config.set(config.SKETCH_WINDOW_MS, "1000")
    config.set(config.SKETCH_DEMOTE_WINDOWS, "2")
    try:
        yield
    finally:
        for key in (
            config.SKETCH_ENABLED, config.SKETCH_PROMOTE_QPS,
            config.SKETCH_RESOURCE_QPS, config.SKETCH_WINDOW_MS,
            config.SKETCH_DEMOTE_WINDOWS,
        ):
            config.set(key, config.DEFAULTS[key])


def _sketch_rule(count=3.0):
    return ParamFlowRule(
        resource="api", param_idx=0, count=count, sketch_mode=True
    )


def _drive_until_promoted(eng, clk, hot="HOT", max_windows=6):
    """Feed hot+cold traffic until the tier promotes ``hot``; returns
    the number of flushes it took (bounded — that IS the assertion)."""
    flushes = 0
    for step in range(max_windows * 4):
        col = [(f"cold{step}_{j}",) for j in range(32)] + [(hot,)] * 32
        eng.submit_bulk("api", n=64, args_column=col)
        eng.flush()
        eng.drain()
        flushes += 1
        if hot in eng.sketch.promoted_values.get("api", ()):
            return flushes
        clk.advance(250)
    raise AssertionError(f"{hot} not promoted within {flushes} flushes")


class TestParamPromotion:
    def test_promoted_within_bounded_flushes(self, sketch_config):
        clk = ManualClock()
        eng = Engine(clock=clk)
        eng.set_param_rules({"api": [_sketch_rule()]})
        flushes = _drive_until_promoted(eng, clk)
        assert flushes <= 16
        # Cold values never interned a dense row; the promoted one does
        # at its first post-promotion resolve.
        assert eng.param_index.n_rows == 0
        eng.submit_bulk("api", n=4, args_column=[("HOT",)] * 4)
        eng.flush()
        eng.drain()
        assert eng.param_index.n_rows == 1
        eng.close()

    @pytest.mark.parametrize("depth", [0, 2])
    def test_promoted_key_matches_configured_dense_rule(
        self, sketch_config, depth
    ):
        """The acceptance differential: from the promotion flush
        onward, the promoted key's verdicts are BIT-IDENTICAL to a
        manually configured dense rule seeing the same stream."""
        clk = ManualClock()
        eng_a = Engine(clock=clk)
        eng_a.pipeline_depth = depth
        eng_a.set_param_rules({"api": [_sketch_rule(count=3.0)]})
        _drive_until_promoted(eng_a, clk)
        eng_a.drain()

        # Engine B: plain dense rule configured AT the promotion
        # boundary (pre-boundary history is all-pass on both sides, so
        # the comparison stream starts from identical rule state).
        config.set(config.SKETCH_ENABLED, "false")
        eng_b = Engine(clock=clk)
        eng_b.pipeline_depth = depth
        eng_b.set_param_rules(
            {"api": [ParamFlowRule(resource="api", param_idx=0, count=3.0)]}
        )
        config.set(config.SKETCH_ENABLED, "true")

        groups = []
        for step in range(12):
            col = [("HOT",)] * 4 + [(f"post{step}_{j}",) for j in range(4)]
            ga = eng_a.submit_bulk("api", n=8, args_column=col)
            gb = eng_b.submit_bulk("api", n=8, args_column=col)
            eng_a.flush()
            eng_b.flush()
            groups.append((ga, gb))
            clk.advance(170)
        eng_a.drain()
        eng_b.drain()
        for ga, gb in groups:
            # Only the promoted key's rows are comparable (cold rows
            # pass in A by design, are dense-checked in B).
            np.testing.assert_array_equal(
                ga.admitted[:4], gb.admitted[:4]
            )
            np.testing.assert_array_equal(ga.reason[:4], gb.reason[:4])
        eng_a.close()
        eng_b.close()

    def test_demotion_releases_dense_row(self, sketch_config):
        clk = ManualClock()
        eng = Engine(clock=clk)
        eng.set_param_rules({"api": [_sketch_rule()]})
        _drive_until_promoted(eng, clk)
        eng.submit_bulk("api", n=4, args_column=[("HOT",)] * 4)
        eng.flush()
        eng.drain()
        assert eng.param_index.n_rows == 1
        # Go cold: windows pass with no HOT traffic at all (the
        # promoted count decays geometrically, then demote.windows
        # consecutive cold windows must accumulate).
        for _ in range(12):
            eng.submit_bulk("api", n=8, args_column=[("c",)] * 8)
            eng.flush()
            eng.drain()
            clk.advance(1100)
        assert "HOT" not in eng.sketch.promoted_values.get("api", ())
        # The row was released back to the recycle pool.
        eng.flush()
        assert "HOT" not in eng.param_index._values[0]
        c = eng.telemetry.counters_snapshot()
        assert c["sketch_promotions"] >= 1
        assert c["sketch_demotions"] >= 1
        eng.close()


class TestUnboundedCardinality:
    def test_100k_distinct_keys_o1_device_memory(self, sketch_config):
        """>=100k distinct unconfigured keys: device state stays at the
        sketch's fixed capacity, no dense rows materialize for cold
        keys, and the hot key still promotes out of the noise."""
        clk = ManualClock()
        eng = Engine(clock=clk)
        eng.set_param_rules({"api": [_sketch_rule()]})
        tier = eng.sketch
        cm_shape = np.asarray(tier.dev_state.cm).shape
        stats_rows = eng.stats.n_rows
        seen = 0
        step = 0
        while seen < 100_000:
            n = 25_000
            col = [(f"u{seen + j}",) for j in range(n - 50)] + [("HOT",)] * 50
            eng.submit_bulk("api", n=n, args_column=col)
            eng.flush()
            eng.drain()
            seen += n - 50
            step += 1
            clk.advance(400)
        assert seen >= 100_000
        # O(1) device growth: sketch shape fixed, stats rows untouched,
        # param rows = promoted keys only (0 or 1), not 100k.
        assert np.asarray(tier.dev_state.cm).shape == cm_shape
        assert eng.stats.n_rows == stats_rows
        assert eng.param_index.n_rows <= 1
        assert eng.param_dyn.tokens.shape[0] == 8  # initial, never grown
        assert "HOT" in tier.promoted_values.get("api", ())
        # Host side stays bounded too: the id->name LRU obeys its cap.
        assert len(tier._names) <= tier.names_cap
        eng.close()


class TestResourcePromotion:
    def test_unconfigured_resource_gets_synthetic_rule_and_demotes(
        self, sketch_config
    ):
        clk = ManualClock()
        eng = Engine(clock=clk)
        for _ in range(6):
            eng.submit_bulk("burst", n=256)
            eng.flush()
            eng.drain()
            clk.advance(400)
        rules = {r.resource: r for r in eng.flow_index.get_rules()}
        assert "burst" in rules and rules["burst"].from_sketch
        assert rules["burst"].count == 50.0
        g = eng.submit_bulk("burst", n=200)
        eng.flush()
        eng.drain()
        assert int(g.admitted.sum()) <= 50  # the synthetic guard bites
        # Demotion: the decayed count must fall below the floor, then
        # demote.windows consecutive cold windows accumulate.
        for _ in range(10):
            eng.submit_bulk("other", n=8)
            eng.flush()
            eng.drain()
            clk.advance(1100)
        eng.flush()
        assert "burst" not in {r.resource for r in eng.flow_index.get_rules()}
        eng.close()

    def test_over_cap_resource_promotes_past_the_cap(self, sketch_config):
        clk = ManualClock()
        eng = Engine(clock=clk)
        eng.nodes.max_resources = 2
        eng.submit_bulk("r1", n=1)
        eng.submit_bulk("r2", n=1)  # cap reached (+ the entry node)
        assert eng.submit_bulk("capped-hot", n=64) is None  # pass-through
        for _ in range(6):
            # Pass-through (None) until the promotion grants the row
            # mid-loop; after that, ops flow normally.
            eng.submit_bulk("capped-hot", n=256)
            eng.flush()
            eng.drain()
            clk.advance(400)
        # Promotion granted the row the cap refused: ops now flow and
        # the synthetic rule guards them.
        assert "capped-hot" in {
            r.resource for r in eng.flow_index.get_rules()
        }
        g = eng.submit_bulk("capped-hot", n=200)
        assert g is not None
        eng.flush()
        eng.drain()
        assert int(g.admitted.sum()) <= 50
        eng.close()

    def test_past_cap_grants_are_cumulatively_budgeted(self, sketch_config):
        """Registry rows granted past the cap are permanent, so a churn
        of distinct over-cap heavy hitters must stop drawing new rows
        at the cumulative budget (8x promote.max) instead of regrowing
        unbounded per-key state through the promotion door."""
        from sentinel_tpu.models.rules import FlowRule as FR

        clk = ManualClock()
        eng = Engine(clock=clk)
        tier = eng.sketch
        eng.nodes.max_resources = 0  # everything is over-cap
        tier.promote_max = 1  # budget = 8
        with tier._lock:
            for i in range(12):
                tier._promoted_res[f"churn{i}"] = FR(
                    resource=f"churn{i}", count=50.0, from_sketch=True
                )
            tier._actions.append(("flow", None))
        tier.apply_actions()
        installed = {r.resource for r in eng.flow_index.get_rules()}
        assert len(installed) == 8  # budget, not all 12
        assert len(tier._cap_grants) == 8
        # Dropped promotions were evicted from the promoted set too.
        assert len(tier._promoted_res) == 8
        eng.close()

    def test_user_reload_reasserts_synthetics(self, sketch_config):
        clk = ManualClock()
        eng = Engine(clock=clk)
        for _ in range(6):
            eng.submit_bulk("burst", n=256)
            eng.flush()
            eng.drain()
            clk.advance(400)
        assert "burst" in {r.resource for r in eng.flow_index.get_rules()}
        # A user reload wipes synthetics; the controller re-asserts on
        # its next pass.
        eng.set_flow_rules([FlowRule("user-res", count=100)])
        assert "burst" not in {r.resource for r in eng.flow_index.get_rules()}
        for _ in range(3):
            eng.submit_bulk("burst", n=256)
            eng.flush()
            eng.drain()
            clk.advance(400)
        names = {r.resource for r in eng.flow_index.get_rules()}
        assert "burst" in names and "user-res" in names
        eng.close()


class TestDisabledParity:
    def test_sketch_mode_rule_is_dense_when_tier_disabled(self):
        """With the tier off, sketch_mode is ignored: the rule
        dense-tracks every value exactly like a plain rule (verdict
        parity with today)."""
        clk = ManualClock()
        eng_a = Engine(clock=clk)
        eng_a.set_param_rules({"api": [_sketch_rule(count=2.0)]})
        eng_b = Engine(clock=clk)
        eng_b.set_param_rules(
            {"api": [ParamFlowRule(resource="api", param_idx=0, count=2.0)]}
        )
        for step in range(6):
            col = [("x",)] * 4 + [(f"v{step}",)] * 2
            ga = eng_a.submit_bulk("api", n=6, args_column=col)
            gb = eng_b.submit_bulk("api", n=6, args_column=col)
            eng_a.flush()
            eng_b.flush()
            np.testing.assert_array_equal(ga.admitted, gb.admitted)
            clk.advance(300)
        eng_a.close()
        eng_b.close()

    def test_disarmed_engine_has_no_sketch_state(self):
        eng = Engine(clock=ManualClock())
        assert not eng.sketch.armed
        assert eng.sketch.dev_state is None
        eng.submit_bulk("res", n=8)
        eng.flush()
        eng.close()


class TestFailoverMirror:
    def test_degraded_folds_into_host_mirror(self, sketch_config):
        from sentinel_tpu.testing.faults import FaultInjector

        config.set(config.FAILOVER_ENABLED, "true")
        try:
            clk = ManualClock()
            eng = Engine(clock=clk)
            eng.set_param_rules({"api": [_sketch_rule()]})
            eng.submit_bulk("api", n=8, args_column=[("warm",)] * 8)
            eng.flush()
            faults = FaultInjector().install(eng)
            faults.fail_fetch(eng.flush_seq + 1)
            eng.submit_bulk("api", n=8, args_column=[("warm",)] * 8)
            eng.flush()  # trips DEGRADED (fetch fault, armed)
            assert not eng.failover.healthy
            # Degraded chunks feed the host mirror; promotion still
            # happens from it.
            for step in range(6):
                col = [(f"c{step}_{j}",) for j in range(16)] + [("HOT",)] * 48
                eng.submit_bulk("api", n=64, args_column=col)
                eng.flush()
                clk.advance(400)
            mirror_keys = {
                k.split("\x1f")[-1]
                for k in eng.sketch.host_mirror.counts
            }
            assert "HOT" in mirror_keys
            assert "HOT" in eng.sketch.promoted_values.get("api", ())
            c = eng.telemetry.counters_snapshot()
            assert c["sketch_host_folds"] >= 1
            eng.close()
        finally:
            config.set(
                config.FAILOVER_ENABLED,
                config.DEFAULTS[config.FAILOVER_ENABLED],
            )


class TestExports:
    def test_prometheus_families_and_command(self, sketch_config):
        from sentinel_tpu.transport.prometheus import render_metrics

        clk = ManualClock()
        eng = Engine(clock=clk)
        eng.set_param_rules({"api": [_sketch_rule()]})
        _drive_until_promoted(eng, clk)
        text = render_metrics(eng)
        for fam in (
            "sentinel_engine_sketch_enabled",
            "sentinel_engine_sketch_keys_total",
            "sentinel_engine_sketch_promotions_total",
            "sentinel_engine_sketch_demotions_total",
            "sentinel_engine_sketch_host_folds_total",
            "sentinel_engine_sketch_promoted",
            "sentinel_engine_sketch_occupancy",
            "sentinel_engine_sketch_est_error_ratio",
        ):
            assert fam in text, f"missing family {fam}"
        snap = eng.sketch.snapshot()
        assert snap["promoted_values"] == {"api": ["HOT"]}
        assert 0 < snap["occupancy"] <= 1.0
        assert snap["est_error_ratio"] >= 0.0
        assert any(
            c["key"] == "api|HOT" for c in snap["candidates_topk"]
        )
        eng.close()

    def test_telemetry_snapshot_carries_tier(self, sketch_config):
        clk = ManualClock()
        eng = Engine(clock=clk)
        eng.submit_bulk("res", n=8)
        eng.flush()
        out = eng.telemetry.snapshot(eng)
        assert "sketch_tier" in out
        eng.close()

    def test_export_topk_unified_default(self):
        """The former hand-rolled ``sketch_k or 10``: one config-backed
        home shared by every export."""
        from sentinel_tpu.metrics.telemetry import TelemetryBus

        bus = TelemetryBus(enabled=True, sketch_k=0)
        assert bus.export_topk_k == 10
        config.set(config.TELEMETRY_TOPK_EXPORT, "7")
        try:
            assert bus.export_topk_k == 7
        finally:
            config.set(
                config.TELEMETRY_TOPK_EXPORT,
                config.DEFAULTS[config.TELEMETRY_TOPK_EXPORT],
            )
        bus2 = TelemetryBus(enabled=True, sketch_k=5)
        assert bus2.export_topk_k == 5
        assert bus2.blocked_topk_k == 5


class TestColumnarKeyPath:
    """The vectorized host key path (PR-9's named follow-up): columnar
    CRC32 ids bit-identical to zlib, the bounded id-memo, and encode
    parity with a per-key twin."""

    def test_crc32_batch_differential(self):
        import random
        import string
        import zlib

        from sentinel_tpu.runtime.sketch import crc32_batch

        rng = random.Random(11)
        keys = [""]
        for _ in range(2000):
            n = rng.randint(0, 48)
            keys.append(
                "".join(rng.choice(string.printable) for _ in range(n))
            )
        keys += ["é¿ሴ日本語", "\x01res\x1fval", "v" * 300]
        raw = [k.encode("utf-8", "surrogatepass") for k in keys]
        got = crc32_batch(raw)
        want = np.array([zlib.crc32(b) for b in raw], dtype=np.uint32)
        assert (got == want).all()
        # Prefix-seeded streaming (the per-column init state).
        pc = zlib.crc32(b"\x02api\x1f")
        got2 = crc32_batch(raw, init=pc)
        want2 = np.array([zlib.crc32(b, pc) for b in raw], dtype=np.uint32)
        assert (got2 == want2).all()

    def test_ids_match_key_id_and_memo_bounded(self):
        from sentinel_tpu.runtime.sketch import key_id

        config.set(config.SKETCH_ENABLED, "true")
        config.set(config.SKETCH_NAMES_CAP, "256")
        try:
            eng = Engine(clock=ManualClock(1000))
            tier = eng.sketch
            prefix = "\x02api\x1f"
            tails = [f"v{i}" for i in range(64)]
            with tier._lock:
                ids = tier._ids_for_locked(prefix, tails)
                # Memo hits return the identical ids.
                ids2 = tier._ids_for_locked(prefix, tails)
            assert (ids == ids2).all()
            for t, i in zip(tails, ids.tolist()):
                assert i == key_id(prefix + t)
            # Overflowing the bound clears the memo, never corrupts ids.
            with tier._lock:
                tier._ids_for_locked(
                    prefix, [f"x{i}" for i in range(300)]
                )
                assert tier._id_memo_n <= 300
                ids3 = tier._ids_for_locked(prefix, tails)
            assert (ids3 == ids).all()
            eng.close()
        finally:
            config.set(config.SKETCH_ENABLED, config.DEFAULTS[config.SKETCH_ENABLED])
            config.set(
                config.SKETCH_NAMES_CAP, config.DEFAULTS[config.SKETCH_NAMES_CAP]
            )

    def test_encode_chunk_aggregation_parity(self):
        """The columnar collect (np.unique/bincount + memoized CRC)
        aggregates bit-identically to a per-key hash twin over a mixed
        bulk stream (repeats, ints, Nones)."""
        from sentinel_tpu.runtime.sketch import key_id

        config.set(config.SKETCH_ENABLED, "true")
        config.set(config.SKETCH_PROMOTE_QPS, "100")
        try:
            eng = Engine(clock=ManualClock(1000))
            rule = ParamFlowRule(
                resource="api", param_idx=0, count=1e9, sketch_mode=True
            )
            eng.set_param_rules({"api": [rule]})
            vals = ["a", "b", "a", None, "c", "b", "a", 7, 7, "d"] * 3
            g = eng.submit_bulk("api", n=len(vals),
                                args_column=[(v,) for v in vals])
            assert g is not None
            ids, w = eng.sketch.encode_chunk(
                [], [g], eng.flow_index, eng.param_index
            )
            # Per-key twin.
            want = {}
            for v in vals:
                if v is None:
                    continue
                i = key_id("\x02api\x1f" + str(v))
                want[i] = want.get(i, 0) + 1
            got = {
                int(i): int(wt) for i, wt in zip(ids, w) if i >= 0
            }
            assert got == want
            eng.flush()
            eng.drain()
            eng.close()
        finally:
            for k in (config.SKETCH_ENABLED, config.SKETCH_PROMOTE_QPS):
                config.set(k, config.DEFAULTS[k])


@pytest.fixture()
def cold_config():
    """Arm the cold-key admission ceiling alone: promotion disarmed, so
    every decision comes from the count-min estimate (ISSUE 13
    satellite — the admit-by-estimate gap HashPipe leaves open)."""
    config.set(config.SKETCH_ENABLED, "true")
    config.set(config.SKETCH_WINDOW_MS, "1000")
    config.set(config.SKETCH_COLD_QPS, "10")
    try:
        yield
    finally:
        for key in (
            config.SKETCH_ENABLED, config.SKETCH_WINDOW_MS,
            config.SKETCH_COLD_QPS,
        ):
            config.set(key, config.DEFAULTS[key])


class TestColdKeyCeiling:
    """sentinel.tpu.sketch.cold.qps: estimated-QPS ceiling on
    unpromoted, unconfigured resources. Ceiling at qps=10, window 1 s
    -> the twin estimate blocks at >= 2 * 10 * 1 = 20."""

    def _hot(self, eng, clk, n=64):
        g = eng.submit_bulk("coldhot", n=n)
        eng.flush()
        eng.drain()
        return g

    def test_hot_cold_key_blocked_then_decays_back(self, cold_config):
        from sentinel_tpu.core import errors as E

        clk = ManualClock(1000)
        eng = Engine(clock=clk)
        # First batch: the twin has never seen the key — passes (and
        # feeds the estimate past the ceiling).
        g = self._hot(eng, clk)
        assert g is not None and g.admitted.all()
        # Now every submit is blocked at the door: bulk, single, and
        # the deferred batch path all route through the ceiling.
        g2 = eng.submit_bulk("coldhot", n=8)
        assert not g2.admitted.any()
        assert g2.reason.tolist() == [E.BLOCK_SKETCH] * 8
        op = eng.submit_entry("coldhot")
        assert op.verdict.reason == E.BLOCK_SKETCH
        assert op.verdict.limit_type == "cold"
        many = eng.submit_many([{"resource": "coldhot"}] * 3)
        assert all(o.verdict.reason == E.BLOCK_SKETCH for o in many)
        assert eng.sketch.cold_blocks >= 12
        c = eng.telemetry.counters_snapshot()
        assert c["sketch_cold_blocks"] == eng.sketch.cold_blocks
        # Nothing was enqueued for the blocked traffic.
        assert not eng.has_pending()
        # Blocked traffic is NOT counted, so per-window halving decays
        # the estimate back under the ceiling and admission resumes
        # (the duty-cycle that approximates the ceiling rate).
        for _ in range(3):
            clk.advance(1100)
            eng.submit_bulk("other", n=1)
            eng.flush()
            eng.drain()
        g3 = eng.submit_bulk("coldhot", n=4)
        assert g3 is not None, "ceiling must lift after decay"
        eng.flush()
        eng.drain()
        assert g3.admitted.all()
        eng.close()

    def test_configured_and_promoted_resources_exempt(self, cold_config):
        clk = ManualClock(1000)
        eng = Engine(clock=clk)
        eng.set_flow_rules([FlowRule(resource="ruled", count=1e9)])
        for _ in range(3):
            g = eng.submit_bulk("ruled", n=64)
            eng.flush()
            eng.drain()
            assert g.admitted.all()  # user rules exempt at any volume
        # A tier-promoted resource is exempt too: the exact dense row
        # owns it from the promotion on.
        eng.sketch._promoted_res["promoted"] = FlowRule(
            resource="promoted", count=1e9, from_sketch=True
        )
        assert not eng.sketch.cold_blocked(
            "promoted", eng.flow_index, eng.param_index
        )
        eng.close()

    def test_over_cap_class_is_covered(self, cold_config):
        from sentinel_tpu.core import errors as E

        clk = ManualClock(1000)
        eng = Engine(clock=clk)
        eng.nodes.max_resources = 1
        eng.submit_bulk("takes-cap", n=1)
        # Over the cap: pass-through while cold...
        assert eng.submit_bulk("capped", n=64) is None
        eng.flush()
        eng.drain()
        # ...but once the estimate crosses the ceiling, the formerly
        # zero-protection class gets blocked verdicts.
        g = eng.submit_bulk("capped", n=8)
        assert g is not None and not g.admitted.any()
        assert g.reason.tolist() == [E.BLOCK_SKETCH] * 8
        op = eng.submit_entry("capped")
        assert op.verdict.reason == E.BLOCK_SKETCH
        eng.close()

    def test_enforced_while_degraded_from_host_twin(self, cold_config):
        """DEGRADED keeps the ceiling: the twin is fed by the SAME
        _collect the host fold runs, so losing the device loses
        nothing."""
        from sentinel_tpu.core import errors as E
        from sentinel_tpu.testing.faults import FaultInjector

        config.set(config.FAILOVER_ENABLED, "true")
        try:
            clk = ManualClock(1000)
            eng = Engine(clock=clk)
            eng.submit_bulk("warm", n=1)
            eng.flush()
            faults = FaultInjector().install(eng)
            faults.fail_fetch(eng.flush_seq + 1)
            eng.submit_bulk("warm", n=1)
            eng.flush()  # trips DEGRADED
            assert not eng.failover.healthy
            g = eng.submit_bulk("degraded-hot", n=64)
            eng.flush()  # host fold feeds the twin
            assert g.admitted.all()
            g2 = eng.submit_bulk("degraded-hot", n=8)
            assert not g2.admitted.any()
            assert g2.reason.tolist() == [E.BLOCK_SKETCH] * 8
            eng.close()
        finally:
            config.set(
                config.FAILOVER_ENABLED,
                config.DEFAULTS[config.FAILOVER_ENABLED],
            )

    def test_default_off_is_cold_pass(self):
        config.set(config.SKETCH_ENABLED, "true")
        try:
            clk = ManualClock(1000)
            eng = Engine(clock=clk)
            assert not eng.sketch.cold_armed  # cold.qps default 0
            for _ in range(4):
                g = eng.submit_bulk("anything", n=256)
                eng.flush()
                eng.drain()
                assert g.admitted.all()  # today's cold-pass behavior
            assert eng.sketch.cold_blocks == 0
            eng.close()
        finally:
            config.set(config.SKETCH_ENABLED,
                       config.DEFAULTS[config.SKETCH_ENABLED])

    def test_degrade_only_resource_exempt(self, cold_config):
        """Regression (review): 'no user rule of any kind' includes
        degrade rules — a breaker-guarded resource must never be
        throttled by the approximate cold path."""
        from sentinel_tpu.models.rules import DegradeRule

        clk = ManualClock(1000)
        eng = Engine(clock=clk)
        eng.set_degrade_rules(
            [DegradeRule(resource="breakered", count=1e9,
                         time_window=1)]
        )
        for _ in range(3):
            g = eng.submit_bulk("breakered", n=64)
            eng.flush()
            eng.drain()
            assert g.admitted.all()
        assert eng.sketch.cold_blocks == 0
        eng.close()


class TestColdValueCeiling:
    """sentinel.tpu.sketch.cold.qps extended to sketch-mode param
    VALUES (ISSUE 14 satellite): an unpromoted cold value of a
    sketch_mode rule — which has NO dense row and previously passed
    unthrottled at any volume — blocks at the same admit-by-estimate
    ceiling, from the same host count-min twin (so DEGRADED keeps it),
    with default 0 = parity."""

    def _engine(self, clk):
        eng = Engine(clock=clk)
        eng.set_param_rules(
            {
                "api": [
                    ParamFlowRule(
                        resource="api", param_idx=0, count=1e9,
                        sketch_mode=True,
                    )
                ]
            }
        )
        return eng

    def _heat(self, eng, value, n=64):
        g = eng.submit_bulk("api", n=n, args_column=[(value,)] * n)
        eng.flush()
        eng.drain()
        return g

    def test_hot_cold_value_blocked_other_values_pass(self, cold_config):
        from sentinel_tpu.core import errors as E

        clk = ManualClock(1000)
        eng = self._engine(clk)
        # First batch passes (cold twin empty) and feeds the estimate
        # past the ceiling (2 * 10 qps * 1 s = 20).
        g = self._heat(eng, "hot-ip")
        assert g is not None and g.admitted.all()
        # Singles on the hot value now refuse at the door with the
        # distinct value-grade attribution; nothing is enqueued.
        op = eng.submit_entry("api", args=("hot-ip",))
        assert op.verdict.reason == E.BLOCK_SKETCH
        assert op.verdict.limit_type == "cold_value"
        assert not eng.has_pending()
        # A DIFFERENT cold value on the same rule is untouched.
        op2 = eng.submit_entry("api", args=("cold-ip",))
        assert op2.verdict is None or op2.verdict.reason != E.BLOCK_SKETCH
        eng.flush()
        eng.drain()
        assert eng.sketch.cold_value_blocks >= 1
        c = eng.telemetry.counters_snapshot()
        assert c["sketch_cold_blocks"] == eng.sketch.cold_blocks
        eng.close()

    def test_bulk_full_block_dense_and_partial_declines(self, cold_config):
        from sentinel_tpu.core import errors as E

        clk = ManualClock(1000)
        eng = self._engine(clk)
        assert self._heat(eng, "hot-ip").admitted.all()
        # Uniform hot-value group: refused dense, never enqueued.
        g = eng.submit_bulk("api", n=6, args_column=[("hot-ip",)] * 6)
        assert not g.admitted.any()
        assert g.reason.tolist() == [E.BLOCK_SKETCH] * 6
        assert not eng.has_pending()
        # Mixed group: per-row verdicts need per-entry routing — the
        # same decline contract as the other bulk-refusing rule
        # classes (the columnar spine falls back to submit_entry).
        with pytest.raises(ValueError):
            eng.submit_bulk(
                "api", n=2, args_column=[("hot-ip",), ("cold-ip",)]
            )
        # The submit_many routing enforces per-op: hot blocked, cold
        # passes, on the same call.
        ops = eng.submit_many(
            [
                {"resource": "api", "args": ("hot-ip",)},
                {"resource": "api", "args": ("cold-ip",)},
            ]
        )
        assert ops[0].verdict.reason == E.BLOCK_SKETCH
        assert ops[1]._verdict is None  # enqueued, not refused
        eng.flush()
        eng.drain()
        assert ops[1].verdict.admitted
        eng.close()

    def test_promoted_value_exempt_and_decay_lifts(self, cold_config):
        clk = ManualClock(1000)
        eng = self._engine(clk)
        assert self._heat(eng, "hot-ip").admitted.all()
        # Promotion grants the exact dense row: the approximate
        # ceiling must never touch a promoted value.
        eng.sketch.promoted_values = {"api": frozenset({"hot-ip"})}
        op = eng.submit_entry("api", args=("hot-ip",))
        assert op._verdict is None  # enqueued normally
        eng.flush()
        eng.drain()
        assert op.verdict.admitted
        eng.sketch.promoted_values = {}
        op = eng.submit_entry("api", args=("hot-ip",))
        assert op.verdict is not None and not op.verdict.admitted
        # Blocked traffic never feeds back: halving decay lifts the
        # ceiling again (the per-value duty cycle).
        for _ in range(3):
            clk.advance(1100)
            eng.submit_bulk("other", n=1)
            eng.flush()
            eng.drain()
        g = eng.submit_bulk("api", n=4, args_column=[("hot-ip",)] * 4)
        assert g is not None
        eng.flush()
        eng.drain()
        assert g.admitted.all()
        eng.close()

    def test_enforced_while_degraded(self, cold_config):
        from sentinel_tpu.core import errors as E
        from sentinel_tpu.testing.faults import FaultInjector

        config.set(config.FAILOVER_ENABLED, "true")
        try:
            clk = ManualClock(1000)
            eng = self._engine(clk)
            eng.submit_bulk("warm", n=1)
            eng.flush()
            faults = FaultInjector().install(eng)
            faults.fail_fetch(eng.flush_seq + 1)
            eng.submit_bulk("warm", n=1)
            eng.flush()  # trips DEGRADED
            assert not eng.failover.healthy
            g = self._heat(eng, "deg-ip")  # host fold feeds the twin
            assert g.admitted.all()
            g2 = eng.submit_bulk(
                "api", n=8, args_column=[("deg-ip",)] * 8
            )
            assert not g2.admitted.any()
            assert g2.reason.tolist() == [E.BLOCK_SKETCH] * 8
            eng.close()
        finally:
            config.set(
                config.FAILOVER_ENABLED,
                config.DEFAULTS[config.FAILOVER_ENABLED],
            )

    def test_default_zero_is_parity(self):
        config.set(config.SKETCH_ENABLED, "true")
        try:
            clk = ManualClock(1000)
            eng = self._engine(clk)
            assert not eng.sketch.cold_armed
            for _ in range(4):
                g = eng.submit_bulk(
                    "api", n=128, args_column=[("v",)] * 128
                )
                eng.flush()
                eng.drain()
                assert g.admitted.all()
            assert eng.sketch.cold_value_blocks == 0
            eng.close()
        finally:
            config.set(config.SKETCH_ENABLED,
                       config.DEFAULTS[config.SKETCH_ENABLED])
