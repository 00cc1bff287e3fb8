"""Sharded Engine mode: the real product API (rules → submit → flush →
verdicts → stats) running over the 8-device CPU mesh — the deployable
cluster unit, ≙ the reference's standalone token server
(SentinelDefaultTokenServer.java:37) collapsed into ICI collectives.
"""

import pytest

pytestmark = pytest.mark.slow


@pytest.fixture()
def mesh_engine(manual_clock, engine):
    engine.enable_mesh(8)
    return engine


@pytest.mark.mesh
class TestEngineMesh:
    def test_budget_conserved_through_engine_api(self, mesh_engine):
        """128 same-window entries against count=20 admit exactly 20 —
        end to end through rules manager, submit_many and verdicts."""
        import sentinel_tpu as st

        st.flow_rule_manager.load_rules([st.FlowRule("res", count=20)])
        now = mesh_engine.clock.now_ms()
        ops = mesh_engine.submit_many(
            [{"resource": "res", "ts": now} for _ in range(128)]
        )
        mesh_engine.flush()
        admitted = [op.verdict.admitted for op in ops]
        assert sum(admitted) == 20
        stats = mesh_engine.cluster_node_stats("res")
        assert stats["pass_qps"] == pytest.approx(20.0)
        assert stats["total_block_minute"] == 108

    def test_thread_grade_and_exits_on_mesh(self, mesh_engine, manual_clock):
        import sentinel_tpu as st

        st.flow_rule_manager.load_rules(
            [st.FlowRule("svc", grade=0, count=4)]  # THREAD grade
        )
        ops = mesh_engine.submit_many([{"resource": "svc"} for _ in range(16)])
        mesh_engine.flush()
        assert sum(op.verdict.admitted for op in ops) == 4
        stats = mesh_engine.cluster_node_stats("svc")
        assert stats["cur_thread_num"] == 4
        # Release two slots; two more fit.
        first = next(op for op in ops if op.verdict.admitted)
        for _ in range(2):
            mesh_engine.submit_exit(first.rows, rt=5, resource="svc")
        ops2 = mesh_engine.submit_many([{"resource": "svc"} for _ in range(8)])
        mesh_engine.flush()
        assert sum(op.verdict.admitted for op in ops2) == 2

    def test_breaker_trips_and_recovers_on_mesh(self, mesh_engine, manual_clock):
        """Degrade slot exercised end-to-end in sharded mode: error
        completions trip the breaker on whichever chips carried them;
        the merged OPEN state blocks everywhere; the HALF_OPEN probe
        recovers it."""
        import sentinel_tpu as st

        st.flow_rule_manager.load_rules([st.FlowRule("d", count=1000)])
        st.degrade_rule_manager.load_rules(
            [st.DegradeRule(resource="d", grade=1, count=0.5, time_window=2,
                            min_request_amount=5)]
        )
        manual_clock.set_ms(1000)
        ops = mesh_engine.submit_many([{"resource": "d"} for _ in range(8)])
        mesh_engine.flush()
        assert all(op.verdict.admitted for op in ops)
        for op in ops:
            mesh_engine.submit_exit(op.rows, rt=5, err=1, resource="d")
        mesh_engine.flush()
        manual_clock.set_ms(1100)
        blocked = mesh_engine.submit_many([{"resource": "d"} for _ in range(8)])
        mesh_engine.flush()
        assert not any(op.verdict.admitted for op in blocked)
        # After the retry window one probe goes through (HALF_OPEN).
        manual_clock.set_ms(3200)
        probe = mesh_engine.submit_many([{"resource": "d"} for _ in range(8)])
        mesh_engine.flush()
        assert sum(op.verdict.admitted for op in probe) == 1

    def test_breaker_counts_survive_multi_chip_window_roll(self, mesh_engine, manual_clock):
        """Several chips rolling the same breaker window in one flush
        must merge to the true counts (a naive old+psum(new-old) merge
        goes negative when 2+ chips roll), and the merged window must
        trip."""
        import sentinel_tpu as st

        st.flow_rule_manager.load_rules([st.FlowRule("w", count=1000)])
        st.degrade_rule_manager.load_rules(
            [st.DegradeRule(resource="w", grade=1, count=0.5, time_window=5,
                            min_request_amount=4)]
        )
        # Window 1: 4 completions, 2 errors — under min_request? No:
        # 4 >= 4 but ratio 0.5 is not > 0.5 → stays CLOSED.
        manual_clock.set_ms(500)
        ops = mesh_engine.submit_many([{"resource": "w", "ts": 500} for _ in range(4)])
        mesh_engine.flush()
        for i, op in enumerate(ops):
            mesh_engine.submit_exit(op.rows, rt=5, err=1 if i < 2 else 0,
                                    resource="w", ts=500)
        mesh_engine.flush()
        # Window 2 (rolls on every chip carrying an exit): 4 errors
        # spread across chips → merged 4/4 must read exactly 4/4, trip.
        manual_clock.set_ms(1500)
        ops2 = mesh_engine.submit_many([{"resource": "w", "ts": 1500} for _ in range(4)])
        mesh_engine.flush()
        for op in ops2:
            mesh_engine.submit_exit(op.rows, rt=5, err=1, resource="w", ts=1500)
        mesh_engine.flush()
        manual_clock.set_ms(1600)
        blocked = mesh_engine.submit_many([{"resource": "w"} for _ in range(8)])
        mesh_engine.flush()
        assert not any(op.verdict.admitted for op in blocked)

    def test_occupy_borrows_conserved_on_mesh_engine(self, mesh_engine, manual_clock):
        """Prioritized entries on the mesh borrow at most maxCount in
        total across all chips."""
        import sentinel_tpu as st
        from sentinel_tpu.utils.config import config

        config.set(config.OCCUPY_TIMEOUT_MS, "1000")
        try:
            mesh_engine.enable_mesh(8)  # recompile with the new timeout
            st.flow_rule_manager.load_rules([st.FlowRule("p", count=4)])
            manual_clock.set_ms(1000)
            ops = mesh_engine.submit_many(
                [{"resource": "p", "ts": 1000} for _ in range(4)]
            )
            mesh_engine.flush()
            assert sum(op.verdict.admitted for op in ops) == 4
            manual_clock.set_ms(1100)
            prio = mesh_engine.submit_many(
                [{"resource": "p", "ts": 1100, "prio": True} for _ in range(16)]
            )
            mesh_engine.flush()
            granted = [op for op in prio if op.verdict.admitted]
            assert len(granted) == 4  # borrow budget == maxCount
            assert all(op.verdict.wait_ms > 0 for op in granted)
            stats = mesh_engine.cluster_node_stats("p")
            assert stats["waiting"] == 4
        finally:
            config.set(config.OCCUPY_TIMEOUT_MS, "500")

    def test_rate_limiter_parity_with_single_chip(self, mesh_engine, manual_clock):
        """The pacer scan on the mesh sees the GLOBAL (rule, ts)-ordered
        stream: verdicts and queue waits match a single-chip engine on
        the identical op stream exactly (a chip-local pacer would admit
        up to n_chips× the configured rate)."""
        import sentinel_tpu as st
        from sentinel_tpu.models import constants as C
        from sentinel_tpu.runtime.engine import Engine

        rules = [
            st.FlowRule(
                "rl", count=10,
                control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER,
                max_queueing_time_ms=500,
            )
        ]
        mesh_engine.set_flow_rules(rules)
        ref = Engine(clock=manual_clock)
        ref.set_flow_rules(rules)
        manual_clock.set_ms(1000)
        reqs = [{"resource": "rl", "ts": 1000 + 7 * i} for i in range(24)]
        ops_m = mesh_engine.submit_many([dict(r) for r in reqs])
        mesh_engine.flush()
        ops_r = ref.submit_many([dict(r) for r in reqs])
        ref.flush()
        got = [(o.verdict.admitted, o.verdict.wait_ms) for o in ops_m]
        want = [(o.verdict.admitted, o.verdict.wait_ms) for o in ops_r]
        assert got == want
        # cost=100ms, maxq=500ms: 1 immediate + queued while wait ≤ 500.
        assert 1 < sum(a for a, _ in got) < len(reqs)

    def test_warmup_parity_with_single_chip(self, mesh_engine, manual_clock):
        """Warm-up token ramp on the mesh: cold-start admission across
        two flushes matches single-chip exactly (replicated syncToken +
        global intra-batch charge)."""
        import sentinel_tpu as st
        from sentinel_tpu.models import constants as C
        from sentinel_tpu.runtime.engine import Engine

        rules = [
            st.FlowRule(
                "wu", count=100,
                control_behavior=C.CONTROL_BEHAVIOR_WARM_UP,
                warm_up_period_sec=10,
            )
        ]
        mesh_engine.set_flow_rules(rules)
        ref = Engine(clock=manual_clock)
        ref.set_flow_rules(rules)
        for t in (1000, 2500):
            manual_clock.set_ms(t)
            reqs = [{"resource": "wu", "ts": t} for _ in range(64)]
            ops_m = mesh_engine.submit_many([dict(r) for r in reqs])
            mesh_engine.flush()
            ops_r = ref.submit_many([dict(r) for r in reqs])
            ref.flush()
            got = [o.verdict.admitted for o in ops_m]
            want = [o.verdict.admitted for o in ops_r]
            assert got == want
            # Cold system: some but not all of the burst is admitted.
            assert 0 < sum(got) < len(reqs)

    def test_warmup_parity_with_upstream_blocked_entries(self, mesh_engine, manual_clock):
        """Upstream-blocked (authority) entries still charge the
        warm-up passQps input on both paths — the mesh rebuild uses the
        same unmasked charge population as flow_admission, so verdicts
        stay identical even when the batch mixes blocked origins in."""
        import sentinel_tpu as st
        from sentinel_tpu.models import constants as C
        from sentinel_tpu.models.rules import AuthorityRule
        from sentinel_tpu.runtime.engine import Engine

        rules = [
            st.FlowRule(
                "wb", count=100,
                control_behavior=C.CONTROL_BEHAVIOR_WARM_UP,
                warm_up_period_sec=10,
            )
        ]
        auth = {"wb": AuthorityRule(resource="wb", limit_app="bad",
                                    strategy=C.AUTHORITY_BLACK)}
        mesh_engine.set_flow_rules(rules)
        mesh_engine.set_authority_rules(auth)
        ref = Engine(clock=manual_clock)
        ref.set_flow_rules(rules)
        ref.set_authority_rules(auth)
        manual_clock.set_ms(1000)
        reqs = [
            {"resource": "wb", "ts": 1000, "origin": "bad" if i % 3 == 0 else "ok"}
            for i in range(48)
        ]
        ops_m = mesh_engine.submit_many([dict(r) for r in reqs])
        mesh_engine.flush()
        ops_r = ref.submit_many([dict(r) for r in reqs])
        ref.flush()
        got = [(o.verdict.admitted, o.verdict.reason) for o in ops_m]
        want = [(o.verdict.admitted, o.verdict.reason) for o in ops_r]
        assert got == want
        assert any(not a for a, _ in got)

    def test_param_bucket_conserved_and_parity_on_mesh(self, mesh_engine, manual_clock):
        """One hot value's token bucket spans all chips: exactly
        ``count`` admissions globally, verdict-for-verdict equal to
        single-chip."""
        import sentinel_tpu as st
        from sentinel_tpu.runtime.engine import Engine

        rules = {"pp": [st.ParamFlowRule(resource="pp", param_idx=0, count=5)]}
        mesh_engine.set_param_rules(rules)
        ref = Engine(clock=manual_clock)
        ref.set_param_rules(rules)
        manual_clock.set_ms(1000)
        reqs = [
            {"resource": "pp", "ts": 1000, "args": ("user-1",)} for _ in range(16)
        ]
        ops_m = mesh_engine.submit_many([dict(r) for r in reqs])
        mesh_engine.flush()
        ops_r = ref.submit_many([dict(r) for r in reqs])
        ref.flush()
        got = [o.verdict.admitted for o in ops_m]
        assert got == [o.verdict.admitted for o in ops_r]
        assert sum(got) == 5

    def test_param_thread_grade_with_exits_on_mesh(self, mesh_engine, manual_clock):
        """Per-value concurrency on the mesh: the global gauge caps at
        the threshold; exits release slots for the next flush."""
        import sentinel_tpu as st
        from sentinel_tpu.models import constants as C

        mesh_engine.set_param_rules(
            {"tg": [st.ParamFlowRule(resource="tg", param_idx=0, count=3,
                                     grade=C.FLOW_GRADE_THREAD)]}
        )
        ops = mesh_engine.submit_many(
            [{"resource": "tg", "args": ("v",)} for _ in range(8)]
        )
        mesh_engine.flush()
        assert sum(op.verdict.admitted for op in ops) == 3
        winner = next(op for op in ops if op.verdict.admitted)
        for _ in range(2):
            mesh_engine.submit_exit(
                winner.rows, rt=5, resource="tg",
                param_rows=winner.param_thread_rows,
            )
        ops2 = mesh_engine.submit_many(
            [{"resource": "tg", "args": ("v",)} for _ in range(8)]
        )
        mesh_engine.flush()
        assert sum(op.verdict.admitted for op in ops2) == 2

    def test_shaping_and_default_budget_together_on_mesh(self, mesh_engine, manual_clock):
        """A DEFAULT rule and a rate-limiter rule on one resource: the
        cross-chip budget demotion and the global pacer compose — and
        match single-chip verdict-for-verdict."""
        import sentinel_tpu as st
        from sentinel_tpu.models import constants as C
        from sentinel_tpu.runtime.engine import Engine

        rules = [
            st.FlowRule("mix", count=20),
            st.FlowRule(
                "mix", count=50,
                control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER,
                max_queueing_time_ms=500,
            ),
        ]
        mesh_engine.set_flow_rules(rules)
        ref = Engine(clock=manual_clock)
        ref.set_flow_rules(rules)
        manual_clock.set_ms(1000)
        reqs = [{"resource": "mix", "ts": 1000} for _ in range(128)]
        ops_m = mesh_engine.submit_many([dict(r) for r in reqs])
        mesh_engine.flush()
        ops_r = ref.submit_many([dict(r) for r in reqs])
        ref.flush()
        got = [o.verdict.admitted for o in ops_m]
        assert got == [o.verdict.admitted for o in ops_r]
        # DEFAULT budget (20) binds tighter than the pacer here.
        assert sum(got) == 20

    def test_origin_split_budget_is_exact(self, mesh_engine, manual_clock):
        """One rule checked against several origin rows in a batch: the
        sharded budget is keyed per check ROW with per-slot caps
        (parallel/ici._split_and_spend), the same key the single-chip
        rank math segments on — so origin-split admits EXACTLY what
        single-chip does (earlier rounds MIN-capped the rule across
        rows, over-blocking the lightly-loaded origin)."""
        import sentinel_tpu as st
        from sentinel_tpu.models import constants as C
        from sentinel_tpu.runtime.engine import Engine

        rules = [st.FlowRule("os", count=10, limit_app=C.LIMIT_APP_OTHER)]
        mesh_engine.set_flow_rules(rules)
        ref = Engine(clock=manual_clock)
        ref.set_flow_rules(rules)
        manual_clock.set_ms(1000)
        pre = [{"resource": "os", "origin": "o1", "ts": 1000} for _ in range(6)]
        a = mesh_engine.submit_many([dict(r) for r in pre])
        mesh_engine.flush()
        b = ref.submit_many([dict(r) for r in pre])
        ref.flush()
        assert sum(o.verdict.admitted for o in a) == 6
        assert sum(o.verdict.admitted for o in b) == 6
        manual_clock.set_ms(1100)
        reqs = [
            {"resource": "os", "origin": "o1" if i % 2 == 0 else "o2", "ts": 1100}
            for i in range(16)
        ]
        gm = mesh_engine.submit_many([dict(r) for r in reqs])
        mesh_engine.flush()
        gr = ref.submit_many([dict(r) for r in reqs])
        ref.flush()
        adm_m = sum(o.verdict.admitted for o in gm)
        adm_r = sum(o.verdict.admitted for o in gr)
        # Single-chip (row-exact): o1 admits its remaining 4, o2 all 8.
        assert adm_r == 12
        # Mesh, row-keyed: identical — o1 its remaining 4, o2 all 8.
        assert adm_m == 12
        # Per-origin verdicts match single-chip exactly.
        assert [o.verdict.admitted for o in gm] == [o.verdict.admitted for o in gr]
        # Never over any single row's cap.
        for origin in ("o1", "o2"):
            adm_o = sum(
                o.verdict.admitted for o, r in zip(gm, reqs) if r["origin"] == origin
            )
            assert adm_o <= 10

class TestMeshLifecycle:
    """Mesh API edges: enable/disable plumbing that never builds a
    sharded kernel — deliberately NOT ``mesh``-marked."""

    def test_non_pow2_mesh_rejected(self, manual_clock, engine):
        with pytest.raises(ValueError, match="power of two"):
            engine.enable_mesh(3)

    def test_disable_mesh_returns_to_single_chip(self, mesh_engine):
        import sentinel_tpu as st
        from sentinel_tpu.models import constants as C

        mesh_engine.disable_mesh()
        mesh_engine.set_flow_rules(
            [st.FlowRule("s", count=10,
                         control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER)]
        )
        op = mesh_engine.submit_entry("s")
        mesh_engine.flush()
        assert op.verdict.admitted

    @pytest.mark.mesh
    def test_disable_mesh_puts_tables_and_state_on_one_device(
        self, mesh_engine
    ):
        import jax

        import sentinel_tpu as st

        mesh_engine.set_flow_rules([st.FlowRule("s", count=10)])
        mesh_engine.submit_entry("s")
        mesh_engine.flush()  # state now written by a sharded flush
        mesh_engine.disable_mesh()
        op = mesh_engine.submit_entry("s")
        mesh_engine.flush()
        assert op.verdict.admitted
        trees = (mesh_engine.flow_index.device, mesh_engine.degrade_index.device,
                 mesh_engine.stats, mesh_engine.flow_dyn,
                 mesh_engine.degrade_dyn, mesh_engine.param_dyn)
        devs = {d for leaf in jax.tree_util.tree_leaves(trees)
                for d in leaf.sharding.device_set}
        assert devs == {jax.devices()[0]}
