"""Closed-form shaping path (shaping rounds = −1).

Pins the closed form against the sequential scan (rounds = 0, the
reference controller recurrences) on identical batches and state, for
same-ts uniform-acquire traffic of any per-rule multiplicity: the
pacer rank math for RATE_LIMITER and WARM_UP_RATE_LIMITER, and the
once-per-segment syncToken for the warm-up behaviors.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import sentinel_tpu as st
from sentinel_tpu.models import constants as C
from sentinel_tpu.rules.flow_table import FlowIndex, FlowRuleDynState
from sentinel_tpu.rules.shaping import ShapingBatch, run_shaping


def _index(n_rules, rng):
    rules = [
        st.FlowRule(
            resource=f"r{i}",
            count=float(rng.integers(5, 80)),
            control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER,
            max_queueing_time_ms=int(rng.integers(0, 600)),
        )
        for i in range(n_rules)
    ]
    return FlowIndex(rules)


def _batch(rng, s, n_rules, ts_val, acq_val):
    gid = rng.integers(0, n_rules, s).astype(np.int32)
    valid = rng.random(s) < 0.9
    return ShapingBatch(
        valid=jnp.asarray(valid),
        gid=jnp.asarray(gid),
        row=jnp.asarray(gid),
        eidx=jnp.arange(s, dtype=jnp.int32),
        flat_pos=jnp.arange(s, dtype=jnp.int32),
        ts=jnp.full(s, ts_val, dtype=jnp.int32),
        acquire=jnp.full(s, acq_val, dtype=jnp.int32),
    )


class TestPacerClosedFormParity:
    @pytest.mark.parametrize("seed", range(8))
    def test_random_batches_match_scan(self, seed):
        rng = np.random.default_rng(seed)
        n_rules, s = 7, 512  # ~73 items/rule — far past the rounds cap
        index = _index(n_rules, rng)
        dyn = index.make_dyn_state()
        # Random pre-state: some rules mid-pace, some never-seen.
        latest = np.where(
            rng.random(n_rules) < 0.3,
            -(10**9),
            rng.integers(500, 2500, n_rules),
        ).astype(np.int32)
        dyn = dyn._replace(latest_passed_time=jnp.asarray(latest))
        ts_val = int(rng.integers(1000, 3000))
        acq = int(rng.integers(1, 3))
        pb = _batch(rng, s, n_rules, ts_val, acq)
        zeros = jnp.zeros(s, dtype=jnp.int32)
        dyn_cf, ok_cf, wait_cf = run_shaping(
            index.device, dyn, pb, zeros, zeros, 1.0, rounds=-1
        )
        dyn_sc, ok_sc, wait_sc = run_shaping(
            index.device, dyn, pb, zeros, zeros, 1.0, rounds=0
        )
        assert np.array_equal(np.asarray(ok_cf), np.asarray(ok_sc))
        assert np.array_equal(np.asarray(wait_cf), np.asarray(wait_sc))
        assert np.array_equal(
            np.asarray(dyn_cf.latest_passed_time),
            np.asarray(dyn_sc.latest_passed_time),
        )

    def test_large_cost_times_rank_does_not_overflow(self):
        """count=1 + acquire=1000 → cost = 1,000,000 ms; rank×cost
        wraps int32 past ~2149 items. The cap-based admission must
        still admit exactly what the scan admits (1 item)."""
        rng = np.random.default_rng(0)
        index = FlowIndex([
            st.FlowRule(
                "r0", count=1.0,
                control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER,
                max_queueing_time_ms=500,
            )
        ])
        dyn = index.make_dyn_state()
        s = 4096
        pb = ShapingBatch(
            valid=jnp.ones(s, dtype=bool),
            gid=jnp.zeros(s, dtype=jnp.int32),
            row=jnp.zeros(s, dtype=jnp.int32),
            eidx=jnp.arange(s, dtype=jnp.int32),
            flat_pos=jnp.arange(s, dtype=jnp.int32),
            ts=jnp.full(s, 1000, dtype=jnp.int32),
            acquire=jnp.full(s, 1000, dtype=jnp.int32),
        )
        zeros = jnp.zeros(s, dtype=jnp.int32)
        dyn_cf, ok_cf, _ = run_shaping(index.device, dyn, pb, zeros, zeros, 1.0, rounds=-1)
        dyn_sc, ok_sc, _ = run_shaping(index.device, dyn, pb, zeros, zeros, 1.0, rounds=0)
        assert int(np.asarray(ok_cf).sum()) == int(np.asarray(ok_sc).sum()) == 1
        assert np.array_equal(
            np.asarray(dyn_cf.latest_passed_time),
            np.asarray(dyn_sc.latest_passed_time),
        )

    def test_engine_bulk_rate_limiter_ladder(self, manual_clock, engine):
        """A bulk group on a rate-limited resource (multiplicity far
        past the rounds cap → previously the scan): 1 immediate + the
        queueing ladder, exact waits."""
        engine.set_flow_rules([
            st.FlowRule(
                "paced", count=10.0,  # cost 100 ms
                control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER,
                max_queueing_time_ms=300,
            )
        ])
        manual_clock.set_ms(1000)
        n = 100
        g = engine.submit_bulk("paced", n, ts=np.full(n, 1000, dtype=np.int32))
        engine.flush()
        adm = np.asarray(g.admitted)
        waits = np.asarray(g.wait_ms)
        assert adm.sum() == 4  # immediate + 100/200/300ms queue slots
        assert waits[adm].tolist() == [0, 100, 200, 300]

        # Next flush chains off the advanced pacer state.
        manual_clock.set_ms(1050)
        g2 = engine.submit_bulk("paced", n, ts=np.full(n, 1050, dtype=np.int32))
        engine.flush()
        adm2 = np.asarray(g2.admitted)
        waits2 = np.asarray(g2.wait_ms)
        # latest = 1300; waits from 1300+100-1050=350 > 300 → none fit.
        assert adm2.sum() == 0, (adm2.sum(), waits2[adm2])

    def test_mixed_behavior_not_eligible(self, engine):
        """Selector predicate: a behavior mix no longer disqualifies the
        closed form — one ts and one acquire ≥ 1 select it; mixed ts,
        mixed acquire and acquire 0 keep the exact recurrence."""
        gid = np.array([0, 1, 1], dtype=np.int32)  # RATE_LIMITER + WARM_UP
        ts = np.array([1000, 1000, 1000], dtype=np.int32)
        acq = np.array([1, 1, 1], dtype=np.int32)
        assert engine._shaping_rounds_for(gid, ts, acq) == -1
        ts2 = np.array([1000, 1200, 1200], dtype=np.int32)
        assert engine._shaping_rounds_for(gid, ts2, acq) != -1
        acq2 = np.array([1, 2, 1], dtype=np.int32)
        assert engine._shaping_rounds_for(gid, ts, acq2) != -1
        acq0 = np.zeros(3, dtype=np.int32)
        assert engine._shaping_rounds_for(gid, ts, acq0) != -1


_MIXES = {
    "warm_up": (C.CONTROL_BEHAVIOR_WARM_UP,),
    "warm_up_rate_limiter": (C.CONTROL_BEHAVIOR_WARM_UP_RATE_LIMITER,),
    "all_three": (
        C.CONTROL_BEHAVIOR_RATE_LIMITER,
        C.CONTROL_BEHAVIOR_WARM_UP,
        C.CONTROL_BEHAVIOR_WARM_UP_RATE_LIMITER,
    ),
}


def _shaped_index(behaviors, n_rules, rng):
    return FlowIndex([
        st.FlowRule(
            resource=f"r{i}",
            count=float(rng.integers(5, 80)),
            control_behavior=behaviors[i % len(behaviors)],
            warm_up_period_sec=int(rng.integers(1, 11)),
            max_queueing_time_ms=int(rng.integers(0, 2000)),
        )
        for i in range(n_rules)
    ])


def _pre_state(index, rng, ts_val):
    """Random per-rule pre-state: pacers never seen, behind or ahead of
    ts; tokens below, at and above the warning line (and at the cap);
    lastfill in ts's own second (no sync), one second back, several
    seconds back, or never filled."""
    n = index.device.n_rules
    warn = np.asarray(index.device.warmup_warning_token)
    maxtok = np.asarray(index.device.warmup_max_token)
    latest = np.select(
        [rng.random(n) < 0.3, rng.random(n) < 0.5],
        [np.full(n, -(10**9)), ts_val - rng.integers(0, 600, n)],
        ts_val + rng.integers(0, 400, n),
    ).astype(np.int32)
    pick = rng.integers(0, 6, n)
    stored = np.choose(pick, [
        np.zeros(n), np.maximum(warn - 1, 0), warn, warn + 1, maxtok,
        np.floor(rng.random(n) * (maxtok + 1)),
    ]).astype(np.float32)
    sec = ts_val - ts_val % 1000
    back = np.choose(rng.integers(0, 4, n), [
        np.zeros(n), np.full(n, 1000), 1000 * rng.integers(2, 6, n),
        np.full(n, sec + 10**9),
    ])
    return index.make_dyn_state()._replace(
        latest_passed_time=jnp.asarray(latest),
        stored_tokens=jnp.asarray(stored),
        last_filled_time=jnp.asarray((sec - back).astype(np.int32)),
    )


def _assert_parity(index, dyn, pb, passc, prevp):
    """Closed form (rounds −1) equals the scan (rounds 0) bit for bit:
    verdicts, waits and all three per-rule state columns."""
    dyn_cf, ok_cf, wait_cf = run_shaping(
        index.device, dyn, pb, passc, prevp, 1.0, rounds=-1
    )
    dyn_sc, ok_sc, wait_sc = run_shaping(
        index.device, dyn, pb, passc, prevp, 1.0, rounds=0
    )
    assert np.array_equal(np.asarray(ok_cf), np.asarray(ok_sc))
    assert np.array_equal(np.asarray(wait_cf), np.asarray(wait_sc))
    for col in FlowRuleDynState._fields:
        a = np.asarray(getattr(dyn_cf, col))
        b = np.asarray(getattr(dyn_sc, col))
        assert np.array_equal(a.view(np.uint32), b.view(np.uint32)), col
    return dyn_cf, np.asarray(ok_cf), np.asarray(wait_cf)


class TestWarmUpClosedFormParity:
    @pytest.mark.parametrize("seed", range(5))
    @pytest.mark.parametrize("mix", sorted(_MIXES))
    def test_random_batches_match_scan(self, mix, seed):
        rng = np.random.default_rng(1000 + seed)
        n_rules, s = 9, 512
        index = _shaped_index(_MIXES[mix], n_rules, rng)
        ts_val = int(rng.integers(1000, 30000))
        dyn = _pre_state(index, rng, ts_val)
        pb = _batch(rng, s, n_rules, ts_val, int(rng.integers(1, 3)))
        # Per-item pass counts: items of one rule differ, so the
        # syncToken must take the segment's FIRST item's prevq.
        passc = jnp.asarray(rng.integers(0, 60, s).astype(np.int32))
        prevp = jnp.asarray(rng.integers(0, 40, s).astype(np.int32))
        _, ok, _ = _assert_parity(index, dyn, pb, passc, prevp)
        assert 0 < int(ok.sum()) < s  # both verdicts occur

    def test_first_item_prevq_drives_the_sync(self):
        """Only the segment's first item syncs: its prevq, not a later
        item's, is what the tokens lose."""
        index = FlowIndex([st.FlowRule(
            "w", count=20.0, control_behavior=C.CONTROL_BEHAVIOR_WARM_UP,
            warm_up_period_sec=10,
        )])
        s = 64
        dyn = index.make_dyn_state()._replace(
            stored_tokens=jnp.asarray([150.0], dtype=jnp.float32),
            last_filled_time=jnp.asarray([1000], dtype=jnp.int32),
        )
        pb = ShapingBatch(
            valid=jnp.ones(s, dtype=bool),
            gid=jnp.zeros(s, dtype=jnp.int32),
            row=jnp.arange(s, dtype=jnp.int32),
            eidx=jnp.arange(s, dtype=jnp.int32),
            flat_pos=jnp.arange(s, dtype=jnp.int32),
            ts=jnp.full(s, 2100, dtype=jnp.int32),
            acquire=jnp.ones(s, dtype=jnp.int32),
        )
        passc = jnp.zeros(s, dtype=jnp.int32)
        prevp = np.zeros(s, dtype=np.int32)
        prevp[0] = 30
        dyn_cf, _, _ = _assert_parity(index, dyn, pb, passc, jnp.asarray(prevp))
        # 150 > warning 100 and prevq 30 ≥ threshold 6: no refill, −30.
        assert float(dyn_cf.stored_tokens[0]) == 120.0
        assert int(dyn_cf.last_filled_time[0]) == 2000

    @pytest.mark.parametrize("behavior", [
        C.CONTROL_BEHAVIOR_WARM_UP, C.CONTROL_BEHAVIOR_WARM_UP_RATE_LIMITER,
    ])
    @pytest.mark.parametrize("lastfill", [2000, -(10**9)])
    def test_4096_item_segment_on_one_rule(self, behavior, lastfill):
        index = FlowIndex([st.FlowRule(
            "w", count=40.0, control_behavior=behavior,
            warm_up_period_sec=5, max_queueing_time_ms=1500,
        )])
        s = 4096
        dyn = index.make_dyn_state()._replace(
            latest_passed_time=jnp.asarray([2300], dtype=jnp.int32),
            stored_tokens=jnp.asarray([110.0], dtype=jnp.float32),
            last_filled_time=jnp.asarray([lastfill], dtype=jnp.int32),
        )
        pb = ShapingBatch(
            valid=jnp.ones(s, dtype=bool),
            gid=jnp.zeros(s, dtype=jnp.int32),
            row=jnp.zeros(s, dtype=jnp.int32),
            eidx=jnp.arange(s, dtype=jnp.int32),
            flat_pos=jnp.arange(s, dtype=jnp.int32),
            ts=jnp.full(s, 2450, dtype=jnp.int32),
            acquire=jnp.ones(s, dtype=jnp.int32),
        )
        # Intra-batch charge: each item sees the ones before it.
        passc = jnp.arange(s, dtype=jnp.int32)
        prevp = jnp.full(s, 12, dtype=jnp.int32)
        _, ok, wait = _assert_parity(index, dyn, pb, passc, prevp)
        assert 0 < int(ok.sum()) < s
        if behavior == C.CONTROL_BEHAVIOR_WARM_UP_RATE_LIMITER:
            assert int(wait.max()) > 0


class TestEngineBulkWarmUp:
    def test_closed_form_matches_scan_and_oracle(
        self, manual_clock, engine, monkeypatch
    ):
        """A flush of a 100-entry WARM_UP group and a 100-entry
        RATE_LIMITER group takes the closed form (span rounds −1); over
        three flushes crossing second boundaries its verdicts equal the
        same flushes forced through the scan, and the oracle's."""
        from sentinel_tpu.core import api
        from sentinel_tpu.runtime.engine import Engine
        from sentinel_tpu.testing.oracle import (
            OracleNode, OracleRateLimiter, OracleWarmUp,
        )

        rules = [
            st.FlowRule("wu", count=20.0,
                        control_behavior=C.CONTROL_BEHAVIOR_WARM_UP,
                        warm_up_period_sec=10),
            st.FlowRule("rl", count=10.0,
                        control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER,
                        max_queueing_time_ms=450),
        ]
        times = (900, 1100, 2300)
        n = 100

        def drive(eng):
            eng.set_flow_rules(rules)
            out = []
            for t in times:
                manual_clock.set_ms(t)
                gw = eng.submit_bulk("wu", n)
                gr = eng.submit_bulk("rl", n)
                eng.flush()
                out.append((
                    eng.telemetry.spans()[-1].shaping_rounds,
                    [np.asarray(g.admitted).tolist() for g in (gw, gr)],
                    [np.asarray(g.wait_ms).tolist() for g in (gw, gr)],
                ))
            return out

        closed = drive(engine)
        assert [r for r, _, _ in closed] == [-1, -1, -1]

        monkeypatch.setattr(
            Engine, "_shaping_rounds_for", staticmethod(lambda g, t, a: 0)
        )
        api.reset(clock=manual_clock)
        manual_clock.set_ms(0)
        scan = drive(api.get_engine())
        assert [r for r, _, _ in scan] == [0, 0, 0]
        assert [v[1:] for v in closed] == [v[1:] for v in scan]

        wu, onode = OracleWarmUp(20.0, 10), OracleNode()
        rl = OracleRateLimiter(10.0, 450)
        for t, (_, (adm_w, adm_r), (_, wait_r)) in zip(times, closed):
            want_w = []
            for _ in range(n):
                ok = wu.can_pass(onode, t)
                (onode.add_pass if ok else onode.add_block)(t, 1)
                want_w.append(ok)
            want_r = [rl.can_pass(t) for _ in range(n)]
            assert adm_w == want_w, t
            assert list(zip(adm_r, wait_r)) == [
                (ok, w if ok else 0) for ok, w in want_r
            ], t
        assert 0 < sum(sum(v[1][0]) for v in closed) < 3 * n
