"""Benchmark: batched rule-check throughput on one chip.

North-star config from BASELINE.json: ~1M flow rules loaded, 100k+
buffered entries checked + accounted in one flush. The reference
publishes no numbers (BASELINE.md), so ``vs_baseline`` is reported
against the north-star target of 1 ms per 131072-entry flush,
normalized per entry so partial ladder stages stay comparable
(vs_baseline > 1.0 means faster than target).

Every backend touch happens in a SUBPROCESS with a timeout: a device
call that wedges blocks inside native code, where no Python-level
signal handler can run, and the parent must outlive it. The parent
never imports jax (it would hold the chip its children need). It walks
a size ladder child by child, reports the LAST (largest) completed
stage, and emits exactly ONE JSON line on stdout:
{"metric", "value", "unit", "vs_baseline", ...}.

Without ``--platform cpu`` each child requires a TPU and exits non-zero
without one; a run in which no stage completes exits non-zero too.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# (n_rules == n_rows, n_entries, timed_iters); last stage is the
# north-star config. The TPU ladder starts at 131k rules: a 16k stage
# only measures per-dispatch overhead, not the kernel.
LADDER = [
    (1 << 17, 1 << 15, 20),
    (1 << 20, 1 << 17, 10),
]
CPU_LADDER = [(1 << 14, 1 << 14, 20)] + LADDER[:1]
TARGET_S_PER_ENTRY = 1e-3 / float(1 << 17)  # 1 ms / 131072 entries


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _emit(out: dict) -> None:
    print(json.dumps(out), flush=True)


def _host_identity() -> dict:
    """Measured host-speed token: a fixed hash + spin calibration plus
    the cpu count. Two VMs can read identically as
    ("cpu", jax_version) yet differ ~5x in real speed — exactly the
    r09→r10 re-anchor hole where the gate went red on a hardware
    identity change, not a code regression. tools/benchgate.py folds
    this token into baseline matching so a cross-box comparison SKIPs
    with a reason instead of gating red. Best-of-3 (min) against
    scheduler noise; the work is fixed, so the number is a property of
    the box, not the workload."""
    import zlib

    buf = b"\xa5" * (1 << 20)
    zlib.crc32(buf)  # warm the buffer through the cache once
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(4):
            zlib.crc32(buf)
        n = 0
        while n < 100_000:
            n += 1
        best = min(best, time.perf_counter() - t0)
    return {
        "host_cpu_count": os.cpu_count() or 0,
        "host_spin_ms": round(best * 1e3, 3),
    }


def _run_mixed_stage(n_rules: int, n_entries: int, iters: int) -> dict:
    """Mixed-workload stage: flow (k=2, incl. rate-limiter shaping) +
    degrade breakers + hot-param buckets + exits, all in one flush —
    "the slot chain at scale", not just the k=1 DEFAULT kernel
    (round-2 weak #5). Reported alongside the headline metric.
    """
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.metrics.nodes import make_stats
    from sentinel_tpu.models import constants as C
    from sentinel_tpu.models.rules import DegradeRule
    from sentinel_tpu.rules.degrade_table import DegradeIndex
    from sentinel_tpu.rules.flow_table import FlowRuleDynState, FlowTableDevice
    from sentinel_tpu.rules.param_table import ParamBatch, make_param_state
    from sentinel_tpu.rules.shaping import ShapingBatch
    from sentinel_tpu.runtime.flush import SystemDevice, flush_step_full_jit
    from __graft_entry__ import _example_batch

    rng = __import__("numpy").random.default_rng(1)
    np_ = __import__("numpy")
    n_rows = n_rules
    k = 2
    nd = min(1024, n_rules)  # degrade rules (real bean layer at this size)
    _log(f"mixed stage rules={n_rules} entries={n_entries}: building state")
    stats = make_stats(n_rows)
    dindex = DegradeIndex(
        [DegradeRule(resource=f"r{i}", grade=1, count=0.5, time_window=10)
         for i in range(nd)]
    )
    inf = float("inf")
    sysdev = SystemDevice(
        qps=jnp.float32(inf), max_thread=jnp.float32(inf), max_rt=jnp.float32(inf),
        load_threshold=jnp.float32(-1.0), cpu_threshold=jnp.float32(-1.0),
        cur_load=jnp.float32(-1.0), cur_cpu=jnp.float32(-1.0),
    )
    # Rule table: 1/8 of rules are rate-limiter shaped, the rest DEFAULT.
    gids = np_.arange(n_rules)
    is_shaping = (gids % 8) == 7
    dev = FlowTableDevice(
        grade=jnp.ones(n_rules, dtype=jnp.int32),
        count=jnp.full(n_rules, 20.0, dtype=jnp.float32),
        behavior=jnp.asarray(
            np_.where(is_shaping, C.CONTROL_BEHAVIOR_RATE_LIMITER,
                      C.CONTROL_BEHAVIOR_DEFAULT).astype(np_.int32)
        ),
        max_queueing_time_ms=jnp.full(n_rules, 500, dtype=jnp.int32),
        cost1_ms=jnp.full(n_rules, 50, dtype=jnp.int32),
        warmup_warning_token=jnp.zeros(n_rules, dtype=jnp.int32),
        warmup_max_token=jnp.zeros(n_rules, dtype=jnp.int32),
        warmup_slope=jnp.zeros(n_rules, dtype=jnp.float32),
        warmup_refill_threshold=jnp.zeros(n_rules, dtype=jnp.int32),
    )
    dyn = FlowRuleDynState(
        latest_passed_time=jnp.full(n_rules, -(10**9), dtype=jnp.int32),
        stored_tokens=jnp.zeros(n_rules, dtype=jnp.float32),
        last_filled_time=jnp.full(n_rules, -(10**9), dtype=jnp.int32),
    )
    batch = _example_batch(n_entries, n_rows, n_rules, k)
    res = np_.asarray(batch.e_rows)[:, 0]
    # Slot 1: a shaping rule for every 8th entry.
    idx = np_.arange(n_entries)
    sh_mask = (idx % 8) == 7
    sh_gid = (res // 8) * 8 + 7  # nearest shaping gid
    gid2 = np_.asarray(batch.e_rule_gid).copy()
    crow2 = np_.asarray(batch.e_check_row).copy()
    gid2[sh_mask, 1] = sh_gid[sh_mask] % n_rules
    crow2[sh_mask, 1] = sh_gid[sh_mask] % n_rules
    # Per-entry breaker check + exits completing breakers.
    dg = (res % nd).astype(np_.int32).reshape(-1, 1)
    m = np_.asarray(batch.x_valid).shape[0]
    x_rows = np_.full((m, 4), -1, dtype=np_.int32)
    x_rows[:, 0] = res[:m]
    batch = batch._replace(
        e_rule_gid=jnp.asarray(gid2),
        e_check_row=jnp.asarray(crow2),
        e_dgid=jnp.asarray(dg),
        x_valid=jnp.ones(m, dtype=bool),
        x_rows=jnp.asarray(x_rows),
        x_count=jnp.ones(m, dtype=jnp.int32),
        x_rt=jnp.full(m, 10, dtype=jnp.int32),
        x_thr=jnp.full(m, -1, dtype=jnp.int32),
        x_dgid=jnp.asarray((res[:m] % nd).astype(np_.int32).reshape(-1, 1)),
    )
    # Shaping batch (the lax.scan path).
    s = int(sh_mask.sum())
    sb = ShapingBatch(
        valid=jnp.ones(s, dtype=bool),
        gid=jnp.asarray((sh_gid[sh_mask] % n_rules).astype(np_.int32)),
        row=jnp.asarray((sh_gid[sh_mask] % n_rules).astype(np_.int32)),
        eidx=jnp.asarray(idx[sh_mask].astype(np_.int32)),
        flat_pos=jnp.asarray((idx[sh_mask] * k + 1).astype(np_.int32)),
        ts=batch.e_ts[jnp.asarray(idx[sh_mask])],
        acquire=jnp.ones(s, dtype=jnp.int32),
    )
    # Hot-param batch: every 4th entry checks one param bucket row.
    p_mask = (idx % 4) == 0
    p = int(p_mask.sum())
    prows = 1 << 14
    pdyn = make_param_state(prows)
    pb = ParamBatch(
        valid=jnp.ones(p, dtype=bool),
        prow=jnp.asarray((rng.integers(0, prows, p)).astype(np_.int32)),
        eidx=jnp.asarray(idx[p_mask].astype(np_.int32)),
        ts=batch.e_ts[jnp.asarray(idx[p_mask])],
        acquire=jnp.ones(p, dtype=jnp.int32),
        grade=jnp.full(p, C.FLOW_GRADE_QPS, dtype=jnp.int32),
        behavior=jnp.zeros(p, dtype=jnp.int32),
        token_count=jnp.full(p, 100, dtype=jnp.int32),
        burst=jnp.zeros(p, dtype=jnp.int32),
        duration_ms=jnp.full(p, 1000, dtype=jnp.int32),
        maxq=jnp.zeros(p, dtype=jnp.int32),
        cost_ms=jnp.zeros(p, dtype=jnp.int32),
        reset_rows=jnp.full(8, -1, dtype=jnp.int32),
        exit_rows=jnp.full(8, -1, dtype=jnp.int32),
    )

    # The same host-known rounds bounds the Engine computes: max items
    # per rule / per value row, pow2-bucketed (engine._rounds_bucket).
    from sentinel_tpu.runtime.engine import _rounds_bucket

    sh_rounds = _rounds_bucket((sh_gid[sh_mask] % n_rules).astype(np_.int32))
    p_rounds = _rounds_bucket(np_.asarray(pb.prow))
    _log(f"mixed: compiling + warm-up (sh_rounds={sh_rounds} p_rounds={p_rounds})")
    t0 = time.perf_counter()
    out = flush_step_full_jit(
        stats, dev, dyn, dindex.device, dindex.make_dyn_state(), pdyn, sysdev,
        batch, sb, pb, shaping_rounds=sh_rounds, param_rounds=p_rounds,
    )
    stats, dyn, ddyn, pdyn, _sk, result = out
    jax.block_until_ready(result.admitted)
    _log(f"mixed: compile+first-run {time.perf_counter() - t0:.1f}s; timing {iters} iters")
    t0 = time.perf_counter()
    for _ in range(iters):
        stats, dyn, ddyn, pdyn, _sk, result = flush_step_full_jit(
            stats, dev, dyn, dindex.device, ddyn, pdyn, sysdev, batch, sb, pb,
            shaping_rounds=sh_rounds, param_rounds=p_rounds,
        )
    jax.block_until_ready(result.admitted)
    dt = (time.perf_counter() - t0) / iters
    checks = n_entries / dt
    _log(f"mixed stage done: {dt*1e3:.3f} ms/flush, {checks:,.0f} entries/sec")
    return {
        "mixed_checks_per_sec": round(checks, 1),
        "mixed_flush_ms": round(dt * 1e3, 4),
        "mixed_n_rules": n_rules,
        "mixed_n_entries": n_entries,
    }


def _run_engine_stage(n_rules: int, n_ops: int, iters: int) -> dict:
    """Engine-level deferred-mode throughput: submit_many + flush through
    the real host path (string interning, slot resolution, encode,
    kernel, verdict fill) — the end-to-end ops/sec a product user sees
    (round-1 #7 bench case). Also measures the columnar bulk path
    (``submit_bulk``: one resolution per group, numpy-slice encode,
    array verdicts) at a proportionally larger op count."""
    from sentinel_tpu.models.rules import FlowRule
    from sentinel_tpu.runtime.engine import Engine

    _log(f"engine stage rules={n_rules} ops={n_ops}")
    eng = Engine(initial_rows=max(1024, n_rules * 2))
    eng.set_flow_rules([FlowRule(resource=f"r{i}", count=1e9) for i in range(n_rules)])
    reqs = [{"resource": f"r{i % n_rules}"} for i in range(n_ops)]
    ops = eng.submit_many(reqs)  # warm-up: interning + compile
    eng.flush()
    assert all(op.verdict is not None for op in ops if op is not None)
    t0 = time.perf_counter()
    for _ in range(iters):
        eng.submit_many(reqs)
        eng.flush()
    dt = (time.perf_counter() - t0) / iters
    ops_per_sec = n_ops / dt
    _log(f"engine stage done: {ops_per_sec:,.0f} ops/sec end-to-end")

    # Bulk path: the same end-to-end surface, columnar. 64 resources
    # per flush, bulk_n entries each.
    groups = 64
    bulk_n = max(1024, min(eng.max_batch // groups, 4096))
    gs = [eng.submit_bulk(f"r{i % n_rules}", bulk_n) for i in range(groups)]
    eng.flush()
    assert all(g.admitted is not None for g in gs)
    t0 = time.perf_counter()
    for _ in range(iters):
        for i in range(groups):
            eng.submit_bulk(f"r{i % n_rules}", bulk_n)
        eng.flush()
    dtb = (time.perf_counter() - t0) / iters
    bulk_ops_per_sec = groups * bulk_n / dtb
    _log(f"engine bulk done: {bulk_ops_per_sec:,.0f} ops/sec end-to-end")

    # Adapter (gateway) columnar path: gateway-shaped traffic — param
    # extraction per request + per-value hot-param admission — through
    # gateway_submit_bulk onto the same bulk surface. Verdict target:
    # ≥ bulk/2 (the adapter layer must not give back the bulk win).
    from sentinel_tpu.adapters.gateway import (
        GatewayFlowRule,
        GatewayParamFlowItem,
        GatewayRequestBatch,
        GatewayRequestInfo,
        PARAM_PARSE_STRATEGY_CLIENT_IP,
        gateway_rule_manager,
        gateway_submit_bulk,
    )
    from sentinel_tpu.models.rules import ParamFlowRule

    route = "gw_route"
    gateway_rule_manager.load_rules(
        [GatewayFlowRule(route, count=1e9,
                         param_item=GatewayParamFlowItem(
                             parse_strategy=PARAM_PARSE_STRATEGY_CLIENT_IP))]
    )
    eng.set_param_rules(
        {route: [ParamFlowRule(route, param_idx=0, count=1e9)]}
    )
    eng.set_flow_rules(
        [FlowRule(resource=f"r{i}", count=1e9) for i in range(n_rules)]
        + [FlowRule(resource=route, count=1e9)]
    )
    # One columnar group per flush — the gateway batching-window shape —
    # sized just under max_batch so the explicit flush() below does the
    # work (at exactly max_batch, flush-on-size fires inside submit and
    # the submit/flush breakdown splits in the wrong place).
    adapter_n = min(groups * bulk_n, eng.max_batch) - 1
    # Heavy-hitter mix (~256 requests per distinct value): same-ts
    # uniform-acquire batches take the closed-form rank path
    # (param_rounds = −1), so per-value multiplicity no longer forces
    # the sequential scan.
    n_ips = max(256, adapter_n // 256)
    infos = [
        GatewayRequestInfo(
            path="/api/x",
            client_ip=f"10.{(i % n_ips) >> 16 & 255}.{(i % n_ips) >> 8 & 255}.{i % n_ips & 255}",
        )
        for i in range(adapter_n)
    ]
    g = gateway_submit_bulk(route, infos, engine=eng)
    eng.flush()  # warm-up: interning + param-kernel compile
    assert g is not None and g.admitted is not None
    # Timed loop with host-side breakdown: parse_ms is the per-window
    # column extraction (the true ingest floor — one attribute read per
    # request into a GatewayRequestBatch column), submit_ms the
    # gateway parse + bulk enqueue, encode_ms / kernel_ms from the
    # engine's own flush attribution.
    t_parse = t_submit = t_encode = t_kernel = 0.0
    t0 = time.perf_counter()
    for _ in range(iters):
        ta = time.perf_counter()
        batch = GatewayRequestBatch(
            n=adapter_n, client_ip=[i.client_ip for i in infos]
        )
        tb = time.perf_counter()
        gateway_submit_bulk(route, batch, engine=eng)
        tc = time.perf_counter()
        eng.flush()
        ft = eng.last_flush_host_ms
        t_parse += tb - ta
        t_submit += tc - tb
        t_encode += ft["encode_ms"]
        t_kernel += ft["kernel_ms"]
    dta = (time.perf_counter() - t0) / iters
    adapter_ops_per_sec = adapter_n / dta
    _log(
        f"engine adapter (gateway bulk) done: {adapter_ops_per_sec:,.0f} ops/sec"
        f" ({adapter_ops_per_sec / bulk_ops_per_sec:.2f}x of bulk; "
        f"parse {t_parse / iters * 1e3:.1f} submit {t_submit / iters * 1e3:.1f} "
        f"encode {t_encode / iters:.1f} kernel {t_kernel / iters:.1f} ms)"
    )

    # Pipelined bulk: flush_async keeps up to max_inflight device
    # round-trips in flight, so host encode of flush N+1 overlaps the
    # fetch latency of flush N — the fetch round trip amortizes.
    n_flushes = max(iters * 4, 8)
    for i in range(groups):
        eng.submit_bulk(f"r{i % n_rules}", bulk_n)
    eng.flush_async()
    eng.drain()  # warm the async path
    t0 = time.perf_counter()
    for _ in range(n_flushes):
        for i in range(groups):
            eng.submit_bulk(f"r{i % n_rules}", bulk_n)
        eng.flush_async()
    eng.drain()
    dtp = (time.perf_counter() - t0) / n_flushes
    pipe_ops_per_sec = groups * bulk_n / dtp
    _log(f"engine pipelined done: {pipe_ops_per_sec:,.0f} ops/sec end-to-end")

    # Depth-2 flush pipeline through the ADAPTER surface: the same
    # gateway window loop as above, but flush() now keeps 2 flushes in
    # flight (sentinel.tpu.host.pipeline.depth semantics) with one
    # coalesced verdict fetch per drain. dispatch_ms is the
    # host-blocking part of a pipelined flush — host/device overlap is
    # visible as dispatch_ms < the sync loop's kernel_ms; occupancy is
    # mean in-flight depth / 2.
    eng.pipeline_depth = 2
    gateway_submit_bulk(route, batch, engine=eng, flush=True)
    eng.drain()  # warm the pipelined path
    eng.pipeline_stats(reset=True)
    t_p_dispatch = t_p_drain = 0.0
    t0 = time.perf_counter()
    for _ in range(iters):
        batch = GatewayRequestBatch(
            n=adapter_n, client_ip=[i.client_ip for i in infos]
        )
        gateway_submit_bulk(route, batch, engine=eng, flush=True)
        ft = eng.last_flush_host_ms
        t_p_dispatch += ft["dispatch_ms"]
        t_p_drain += ft["drain_ms"]
    eng.drain()
    # The trailing drain of the last `depth` in-flight flushes lands in
    # the final flush's breakdown AFTER its ft read above — add the
    # delta or drain_ms under-reports by the pipeline's tail.
    t_p_drain += eng.last_flush_host_ms["drain_ms"] - ft["drain_ms"]
    dtap = (time.perf_counter() - t0) / iters
    ps = eng.pipeline_stats(reset=True)
    occupancy = ps["mean_inflight"] / 2.0
    adapter_pipe_ops_per_sec = adapter_n / dtap
    eng.pipeline_depth = 0
    _log(
        f"engine adapter pipelined (depth 2) done:"
        f" {adapter_pipe_ops_per_sec:,.0f} ops/sec"
        f" (dispatch {t_p_dispatch / iters:.1f} drain {t_p_drain / iters:.1f} ms,"
        f" occupancy {occupancy:.2f})"
    )

    # Flight-recorder (runtime/capture.py) overhead: same-run A/B on
    # the bulk surface at pipeline depths {0, 2}. The journal's spill
    # path is one vectorized encode_entries_columns frame per bulk
    # group, so the ratio should sit near 1.0. The full-size bulk
    # flush is the wrong sample here — per-flush wall time on a shared
    # box drifts ±20%, far more than the effect — so the A/B uses a
    # smaller window (16 groups × 1024 rows, ~10× cheaper per sample),
    # INTERLEAVES off/on pairs (settle + 2 timed flushes per arm per
    # rep), and takes min(on)/min(off): the box noise is one-sided
    # additive, and min over 5 reps filters it.
    import shutil
    import tempfile

    from sentinel_tpu.runtime.capture import CaptureJournal

    cap_groups, cap_bulk_n = 16, 1024

    def _cap_timed_unit():
        for _j in range(2):
            for i in range(cap_groups):
                eng.submit_bulk(f"r{i % n_rules}", cap_bulk_n)
            eng.flush()
            eng.drain()

    cap_cols = {}
    cap_tmp = tempfile.mkdtemp(prefix="bench-capture-")
    try:
        for depth in (0, 2):
            eng.pipeline_depth = depth
            off_s, on_s = [], []
            cap = None
            for _rep in range(5):
                if cap is not None:
                    cap.close()
                    eng.capture = None
                    cap = None
                _cap_timed_unit()  # settle
                t0 = time.perf_counter()
                _cap_timed_unit()
                off_s.append(time.perf_counter() - t0)
                cap = CaptureJournal(eng, directory=cap_tmp)
                cap.segment_bytes = 1 << 30  # no rollover I/O in the loop
                eng.capture = cap
                _cap_timed_unit()  # settle
                t0 = time.perf_counter()
                _cap_timed_unit()
                on_s.append(time.perf_counter() - t0)
            cap_bytes = (
                cap.snapshot()["counters"]["bytes"] if cap is not None else 0
            )
            if cap is not None:
                cap.close()
                eng.capture = None
            ratio = min(on_s) / min(off_s)
            cap_cols[f"engine_capture_overhead_d{depth}"] = round(ratio, 4)
            if depth == 0:
                # Journal growth per armed flush (KiB) — the disk-rate
                # context for the overhead ratio. The last rep's
                # journal saw exactly 4 armed flushes (settle + timed).
                cap_cols["engine_capture_kb_per_flush"] = round(
                    cap_bytes / 4 / 1024.0, 1
                )
            _log(
                f"engine capture overhead depth {depth}: "
                f"{(ratio - 1) * 100:+.2f}% "
                f"(off {min(off_s) * 1e3:.0f} ms on {min(on_s) * 1e3:.0f} ms)"
            )
    finally:
        eng.pipeline_depth = 0
        shutil.rmtree(cap_tmp, ignore_errors=True)
    partial = {
        "engine_ops_per_sec": round(ops_per_sec, 1),
        "engine_n_rules": n_rules,
        "engine_n_ops": n_ops,
        "engine_bulk_ops_per_sec": round(bulk_ops_per_sec, 1),
        "engine_bulk_n_ops": groups * bulk_n,
        "engine_adapter_ops_per_sec": round(adapter_ops_per_sec, 1),
        "engine_adapter_vs_bulk": round(adapter_ops_per_sec / bulk_ops_per_sec, 3),
        # Host-side adapter breakdown (per flush, ms) — attributes the
        # adapter-vs-bulk gap for the next TPU window.
        "parse_ms": round(t_parse / iters * 1e3, 3),
        "submit_ms": round(t_submit / iters * 1e3, 3),
        "encode_ms": round(t_encode / iters, 3),
        "kernel_ms": round(t_kernel / iters, 3),
        "engine_pipelined_ops_per_sec": round(pipe_ops_per_sec, 1),
        "engine_pipelined_flushes": n_flushes,
        # Depth-2 flush pipeline (adapter surface): host-blocking
        # dispatch vs the sync loop's kernel_ms above shows the
        # host/device overlap directly for the next TPU capture.
        "engine_adapter_pipelined_ops_per_sec": round(adapter_pipe_ops_per_sec, 1),
        "dispatch_ms": round(t_p_dispatch / iters, 3),
        "drain_ms": round(t_p_drain / iters, 3),
        "pipeline_occupancy": round(occupancy, 3),
        # Flight-recorder arming cost (same-run on/off median ratio on
        # the bulk loop): ~1.0 means capture is free at flush scale.
        **cap_cols,
        # Flight-recorder view of the whole stage (metrics/telemetry.py):
        # latency tails + arena hit rate + blocked sketch — the numbers
        # the /metrics scrape and the telemetry command would serve.
        "telemetry": eng.telemetry.bench_summary(),
    }
    # Emit the completed measurements NOW: the latency block below
    # compiles one more (1-op, pad-8) kernel shape, and that compile
    # can outlive the stage timeout — the parent salvages the last JSON
    # line from a timed-out child.
    print(json.dumps(partial), flush=True)

    # Sync-mode latency: one entry, one flush, one verdict — the
    # worst-case interactive path (on TPU this is dominated by the
    # per-dispatch + fetch round-trip, not the kernel).
    lat_n = 20
    op = eng.submit_entry("r0")
    eng.flush()  # warm the 1-op shape
    t0 = time.perf_counter()
    for _ in range(lat_n):
        op = eng.submit_entry("r0")
        eng.flush()
    sync_ms = (time.perf_counter() - t0) / lat_n * 1e3
    assert op is not None and op.verdict is not None
    _log(f"engine sync latency: {sync_ms:.2f} ms/entry")
    return {"engine_sync_latency_ms": round(sync_ms, 3), **partial}


def _run_speculative_stage(n_rules: int, n_ops: int, iters: int) -> dict:
    """Speculative admission tier (runtime/speculative.py): per-entry
    wall latency of the host fast path (p50/p99 — the sub-100 µs story
    the ROADMAP targets, vs the ~ms sync device round-trip) plus the
    measured per-window drift after settlement reconciles the same ops
    against device truth."""
    from sentinel_tpu.models.rules import FlowRule
    from sentinel_tpu.runtime.engine import Engine
    from sentinel_tpu.utils.config import config

    n_rules, n_ops, iters = max(1, n_rules), max(1, n_ops), max(1, iters)
    _log(f"speculative stage rules={n_rules} ops={n_ops}")
    config.set(config.SPECULATIVE_ENABLED, "true")
    config.set(config.SPECULATIVE_FLUSH_BATCH, "256")
    eng = Engine(initial_rows=max(1024, n_rules * 2))
    # Production shape: the background flusher owns settlement, so the
    # admission thread never pays a device dispatch (engine.
    # _spec_maybe_settle skips when the auto-flusher runs).
    eng.start_auto_flush()
    # Thresholds sized so roughly half the stream blocks — both verdict
    # paths (admit and block) are on the timed path, like production.
    eng.set_flow_rules(
        [FlowRule(resource=f"r{i}", count=float(max(1, n_ops // (2 * n_rules))))
         for i in range(n_rules)]
    )
    names = [f"r{i % n_rules}" for i in range(n_ops)]
    for name in names[:256]:
        eng.entry_sync(name)  # warm: interning + first settle compile
    eng.flush()
    eng.drain()
    lat: list[float] = []
    t0 = time.perf_counter()
    for _ in range(iters):
        for name in names:
            ta = time.perf_counter()
            eng.entry_sync(name)
            lat.append(time.perf_counter() - ta)
        eng.flush()  # settle + reconcile between rounds
    eng.stop_auto_flush()
    eng.flush()
    eng.drain()
    dt = (time.perf_counter() - t0) / iters
    lat.sort()
    p50 = lat[len(lat) // 2] * 1e6
    p99 = lat[int(len(lat) * 0.99)] * 1e6

    # --- system-gate column (PR 7): the same timed loop with a system
    # rule configured — a wide-open QPS threshold, so the number is the
    # host gate's OVERHEAD on the fast path, not blocking behavior.
    from sentinel_tpu.models import constants as _C
    from sentinel_tpu.rules.system_manager import SystemConfig

    eng.start_auto_flush()
    eng.set_system_config(SystemConfig(qps=float(n_ops) * 100.0))
    lat_sys: list[float] = []
    for name in names:
        ta = time.perf_counter()
        eng.entry_sync(name, entry_type=_C.EntryType.IN)
        lat_sys.append(time.perf_counter() - ta)
    eng.stop_auto_flush()
    eng.flush()
    eng.drain()
    eng.set_system_config(None)
    lat_sys.sort()
    sys_p50 = lat_sys[len(lat_sys) // 2] * 1e6
    sys_p99 = lat_sys[int(len(lat_sys) * 0.99)] * 1e6

    # --- shed column (PR 7): verdict latency of the ingest valve's
    # BLOCK_SHED fast path (runtime/ingest.py) — the "fast distinct
    # verdict under saturation" number.
    from sentinel_tpu.runtime.ingest import IngestValve

    config.set(config.INGEST_DEADLINE_MS, "1")
    eng.ingest = IngestValve(eng)
    eng.ingest.force_latency_ms(1000.0)  # everything sheds
    lat_shed: list[float] = []
    for name in names[: max(1, min(len(names), 4096))]:
        ta = time.perf_counter()
        _op, v = eng.entry_sync(name)
        lat_shed.append(time.perf_counter() - ta)
    config.set(config.INGEST_DEADLINE_MS, "0")
    shed_total = eng.ingest.counters["shed_entries"]
    eng.ingest = IngestValve(eng)
    lat_shed.sort()
    shed_p50 = lat_shed[len(lat_shed) // 2] * 1e6
    shed_p99 = lat_shed[int(len(lat_shed) * 0.99)] * 1e6

    snap = eng.speculative.snapshot()
    c = snap["counters"]
    _log(
        f"speculative stage done: p50 {p50:.1f} µs p99 {p99:.1f} µs "
        f"(system-gated p50 {sys_p50:.1f} µs, shed p50 {shed_p50:.1f} µs; "
        f"{n_ops / dt:,.0f} ops/s incl. settles; "
        f"over {c['over_admits']} under {c['under_admits']} "
        f"across {c['windows']} windows, max/window "
        f"{snap['max_over_admit_window']})"
    )
    return {
        "spec_entry_p50_us": round(p50, 2),
        "spec_entry_p99_us": round(p99, 2),
        "spec_ops_per_sec": round(n_ops / dt, 1),
        "spec_over_admits": c["over_admits"],
        "spec_under_admits": c["under_admits"],
        "spec_reconciled": c["reconciled"],
        "spec_windows": c["windows"],
        "spec_max_over_admit_window": snap["max_over_admit_window"],
        "spec_declined": c["spec_declined"],
        "spec_shaped": c["spec_shaped"],
        "spec_system_blocks": c["spec_system_blocks"],
        "spec_entry_sys_p50_us": round(sys_p50, 2),
        "spec_entry_sys_p99_us": round(sys_p99, 2),
        "shed_entry_p50_us": round(shed_p50, 2),
        "shed_entry_p99_us": round(shed_p99, 2),
        "shed_total": shed_total,
    }


def _run_sketch_stage(n_rules: int, n_ops: int, iters: int) -> dict:
    """Sketch tier (runtime/sketch.py): engine flush throughput over a
    high-cardinality param-value stream with the tier ON (cold values
    pass via the fixed-size device sketch) vs OFF (today: every value
    interns a dense row, LRU churning) — the update-cost A/B — plus a
    promotion-storm latency (wall ms until 16 simultaneous hot keys all
    hold exact dense rows) and the candidate-table occupancy."""
    from sentinel_tpu.models.rules import ParamFlowRule
    from sentinel_tpu.runtime.engine import Engine
    from sentinel_tpu.utils.config import config

    n_ops, iters = max(64, n_ops), max(1, iters)
    _log(f"sketch stage ops={n_ops}")
    rule = ParamFlowRule(
        resource="api", param_idx=0, count=1e9, sketch_mode=True
    )

    def _stream(eng, warm_decay: bool = False) -> float:
        """Flush ``iters`` batches of n_ops distinct-per-batch values;
        returns ops/sec. ``warm_decay`` warms BOTH decay-flag kernel
        variants before timing (sleep past one decay window, flush
        again): the decay=True variant otherwise compiles INSIDE the
        timed loop and a ~1 s one-time XLA compile swamps the 3-iter
        measurement — BENCH_r07's ON number was exactly that artifact.
        """
        uid = [0]

        def batch():
            col = [(f"v{uid[0] + j}",) for j in range(n_ops)]
            uid[0] += n_ops
            return col

        eng.submit_bulk("api", n=n_ops, args_column=batch())
        eng.flush()  # compile + warm (decay=False variant)
        if warm_decay:
            time.sleep(1.05)  # roll one real decay window
            eng.submit_bulk("api", n=n_ops, args_column=batch())
            eng.flush()  # compile + warm (decay=True variant)
        t0 = time.perf_counter()
        for _ in range(iters):
            eng.submit_bulk("api", n=n_ops, args_column=batch())
            eng.flush()
        eng.drain()
        return n_ops * iters / (time.perf_counter() - t0)

    try:
        config.set(config.SKETCH_ENABLED, "false")
        eng_off = Engine()
        eng_off.set_param_rules({"api": [rule]})
        off_ops = _stream(eng_off)
        eng_off.close()

        config.set(config.SKETCH_ENABLED, "true")
        config.set(config.SKETCH_PROMOTE_QPS, "50")
        config.set(config.SKETCH_WINDOW_MS, "1000")
        eng_on = Engine()
        eng_on.set_param_rules({"api": [rule]})
        on_ops = _stream(eng_on, warm_decay=True)

        # Promotion storm: 16 hot keys appear at once; wall time until
        # every one holds an exact dense row (bounded-flushes contract).
        hot = [f"hot{i}" for i in range(16)]
        t0 = time.perf_counter()
        storm_flushes = 0
        storm_ms = None
        for step in range(40):
            col = [(h,) for h in hot for _ in range(16)]
            eng_on.submit_bulk("api", n=len(col), args_column=col)
            eng_on.flush()
            eng_on.drain()
            storm_flushes += 1
            promoted = eng_on.sketch.promoted_values.get("api", frozenset())
            if all(h in promoted for h in hot):
                storm_ms = (time.perf_counter() - t0) * 1e3
                break
            time.sleep(0.06)  # real clock: let decay windows roll
        occupancy = eng_on.sketch.occupancy
        promoted_n = eng_on.sketch.promoted_count
        eng_on.close()
    finally:
        for key in (config.SKETCH_ENABLED, config.SKETCH_PROMOTE_QPS,
                    config.SKETCH_WINDOW_MS):
            config.set(key, config.DEFAULTS[key])

    import jax

    _log(
        f"sketch stage done: on {on_ops:,.0f} ops/s vs off {off_ops:,.0f}"
        f" ops/s; storm "
        + (f"{storm_ms:.0f} ms" if storm_ms is not None else "INCOMPLETE")
        + f" / {storm_flushes} flushes, promoted {promoted_n},"
        f" occupancy {occupancy:.2f}"
    )
    out = {
        "sketch_n_ops": n_ops,
        "sketch_ops_per_sec_on": round(on_ops, 1),
        "sketch_ops_per_sec_off": round(off_ops, 1),
        "sketch_promote_storm_flushes": storm_flushes,
        "sketch_promoted": promoted_n,
        "sketch_occupancy": round(occupancy, 4),
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "jax_version": jax.__version__,
    }
    if storm_ms is not None:
        # An incomplete storm (promotion never converged — noisy box
        # or a regression) OMITS the metric rather than recording a
        # bogus 0.0 a later benchgate baseline would gate against.
        out["sketch_promote_storm_ms"] = round(storm_ms, 1)
    return out


def _run_adapters_stage(n_rules: int, n_ops: int, iters: int) -> dict:
    """Adapter matrix (runtime/window.py): per-adapter ops/s with the
    batch window OFF (today's per-request submit+flush) vs ON (columnar
    windows), p50/p99 request latency in both modes, plus two same-run
    references: ``gateway_bulk`` (gateway_submit_bulk + columnar exit
    accounting — the columnar ceiling) and ``spine`` (the window
    machinery batch-driven: join + group + columnar submit + fan-out +
    bulk exits, no per-request concurrency harness — the adapter-edge
    cost the ≥0.8x-of-bulk acceptance bounds; the per-adapter
    concurrency numbers additionally pay driver + GIL cost, which is
    the 1-core box's tax, not the spine's).

    Adapters whose framework is not installed (flask/fastapi) are
    skipped with a log line — their metrics are simply absent and the
    gate treats them as not comparable."""
    import asyncio
    import threading

    import numpy as np  # noqa: F401

    from sentinel_tpu.core import api
    from sentinel_tpu.models import constants as KC
    from sentinel_tpu.models.rules import FlowRule
    from sentinel_tpu.rules.flow_manager import flow_rule_manager
    from sentinel_tpu.utils.config import config

    n_ops, iters = max(256, n_ops), max(1, iters)
    _log(f"adapters stage ops={n_ops}")
    out: dict = {"adapters_n_ops": n_ops}

    RES = "GET:/bench"
    OFF_OPS = max(128, n_ops // 8)  # off mode is ~one flush per request

    def _reset(window: bool):
        config.set(config.INGEST_BATCH_WINDOW_MS, "2" if window else "0")
        config.set(config.INGEST_BATCH_MAX, "256")
        eng = api.reset()
        flow_rule_manager.load_rules(
            [FlowRule(RES, count=1e9), FlowRule("route", count=1e9)]
        )
        return eng

    def _pcts(lat):
        lat.sort()
        return (
            lat[len(lat) // 2] * 1e6,
            lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e6,
        )

    # ---- same-run gateway-bulk reference (with exit accounting) ----
    from sentinel_tpu.adapters.gateway import (
        GatewayRequestBatch,
        gateway_submit_bulk,
    )

    eng = _reset(window=False)
    nb = 256
    batch = GatewayRequestBatch(n=nb, client_ip=["1.2.3.4"] * nb)

    def _bulk_once():
        op = gateway_submit_bulk("route", batch, flush=True)
        if op is not None:
            adm = op.admitted
            eng.submit_exit_bulk(
                op.rows, max(1, int(adm.sum())), rt=1, resource="route"
            )

    for _ in range(8):
        _bulk_once()
    eng.flush()
    eng.drain()
    rounds = max(1, n_ops // nb)
    bulk_best = 0.0
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(rounds):
            _bulk_once()
        eng.flush()
        eng.drain()
        bulk_best = max(bulk_best, rounds * nb / (time.perf_counter() - t0))
    out["adapters_gateway_bulk_ops_per_sec"] = round(bulk_best, 1)
    _log(f"adapters: gateway-bulk {bulk_best:,.0f} ops/s")

    # ---- the spine, batch-driven (window machinery cost per request) ----
    from sentinel_tpu.runtime.window import WindowRequest

    eng = _reset(window=True)
    w = eng.ingest_window

    def _spine_round(total):
        reqs = []
        now = eng.clock.now_ms()
        for _ in range(total):
            r = WindowRequest(
                RES, KC.CONTEXT_DEFAULT_NAME, "", 1, KC.EntryType.IN, (),
                now, None,
            )
            w.join(r)
            reqs.append(r)
        for r in reqs:
            if r.verdict is None and r.error is None:
                r.event.wait(60)
        for r in reqs:
            v = r.verdict
            if v is not None and v.admitted:
                w.note_exit(r.rows, RES, 1, 1, 0, bool(v.speculative))

    for _ in range(3):
        _spine_round(n_ops // 2)  # warm every window-size pad bucket
    spine_best = 0.0
    for _ in range(iters):
        t0 = time.perf_counter()
        _spine_round(n_ops)
        spine_best = max(spine_best, n_ops / (time.perf_counter() - t0))
    eng.flush()
    eng.drain()
    out["adapters_spine_on_ops_per_sec"] = round(spine_best, 1)
    out["adapters_spine_vs_bulk"] = round(spine_best / max(bulk_best, 1e-9), 4)
    _log(
        f"adapters: spine {spine_best:,.0f} ops/s "
        f"({out['adapters_spine_vs_bulk']:.2f}x of bulk)"
    )

    # ---- per-adapter drivers ----
    def _sync_driver(call, total, threads=64):
        lat: list = []
        lock = threading.Lock()
        per = max(1, total // threads)

        def worker():
            mine = []
            for _ in range(per):
                t0 = time.perf_counter()
                call()
                mine.append(time.perf_counter() - t0)
            with lock:
                lat.extend(mine)

        ths = [threading.Thread(target=worker) for _ in range(threads)]
        t0 = time.perf_counter()
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        dt = time.perf_counter() - t0
        return per * threads / dt, lat

    def _async_driver(acall, total, conc=256):
        lat: list = []

        async def _main():
            sem = asyncio.Semaphore(conc)

            async def one():
                async with sem:
                    t0 = time.perf_counter()
                    await acall()
                    lat.append(time.perf_counter() - t0)

            await asyncio.gather(*[one() for _ in range(total)])

        t0 = time.perf_counter()
        asyncio.run(_main())
        return total / (time.perf_counter() - t0), lat

    def _measure(name, driver, call, on_total, off_total):
        nonlocal eng
        for window in (False, True):
            eng = _reset(window)
            total = on_total if window else off_total
            if window:
                # Four full warm rounds: this driver's window sizes
                # set the padded kernel shapes (entry × exit pad-bucket
                # PAIRS each compile once), and an XLA compile inside a
                # timed round would swamp it (the r07 lesson).
                for _ in range(4):
                    driver(call, total)
            else:
                driver(call, max(128, total // 4))
            best, best_lat = 0.0, []
            # Window-on gets extra rounds: a ragged TAIL window whose
            # padded shape was never warmed costs a ~1.6 s XLA compile
            # in whichever round first sees it — best-of over more
            # rounds makes one clean round near-certain.
            for _ in range(iters + (4 if window else 0)):
                ops, lat = driver(call, total)
                if ops > best:
                    best, best_lat = ops, lat
            eng.flush()
            eng.drain()
            mode = "on" if window else "off"
            p50, p99 = _pcts(best_lat)
            out[f"adapters_{name}_{mode}_ops_per_sec"] = round(best, 1)
            out[f"adapters_{name}_{mode}_p50_us"] = round(p50, 1)
            out[f"adapters_{name}_{mode}_p99_us"] = round(p99, 1)
            _log(
                f"adapters: {name} window-{mode} {best:,.0f} ops/s "
                f"p50 {p50:,.0f}us p99 {p99:,.0f}us"
            )

    # WSGI (stands in for Flask's WSGI mount when flask is absent).
    from sentinel_tpu.adapters import (
        SentinelASGIMiddleware,
        SentinelWSGIMiddleware,
    )

    def _wsgi_inner(environ, start_response):
        start_response("200 OK", [])
        return [b"ok"]

    wapp = SentinelWSGIMiddleware(_wsgi_inner, total_resource=None)

    def _wsgi_call():
        environ = {"PATH_INFO": "/bench", "REQUEST_METHOD": "GET"}
        b"".join(wapp(environ, lambda s, h: None))

    _measure("wsgi", _sync_driver, _wsgi_call, n_ops, OFF_OPS)

    # ASGI (stands in for FastAPI's app-wide mount when absent).
    async def _asgi_inner(scope, receive, send):
        await send({"type": "http.response.start", "status": 200,
                    "headers": []})
        await send({"type": "http.response.body", "body": b"ok"})

    aapp = SentinelASGIMiddleware(_asgi_inner, total_resource=None)
    _scope = {"type": "http", "method": "GET", "path": "/bench"}

    async def _recv():
        return {"type": "http.request"}

    async def _send(msg):
        pass

    async def _asgi_call():
        await aapp(_scope, _recv, _send)

    _measure("asgi", _async_driver, _asgi_call, n_ops, OFF_OPS)

    # aiohttp middleware (gated on the framework being importable).
    try:
        from aiohttp.test_utils import make_mocked_request

        from sentinel_tpu.adapters.aiohttp_adapter import sentinel_middleware

        mw = sentinel_middleware()

        async def _handler(request):
            from aiohttp import web

            return web.Response(text="ok")

        # One shared mocked request: building one costs ~2 ms — that
        # would be the driver benching aiohttp's test kit, not the
        # adapter. The middleware only READS it (method/path/headers).
        _aio_req = make_mocked_request("GET", "/bench")

        async def _aio_call():
            await mw(_aio_req, _handler)

        _measure("aiohttp", _async_driver, _aio_call, n_ops, OFF_OPS)
    except ImportError:
        _log("adapters: aiohttp not installed — skipped")

    # gRPC server interceptor (no sockets: fake call details, real
    # grpc handler objects).
    try:
        import grpc  # noqa: F401

        from sentinel_tpu.adapters.grpc_adapter import (
            SentinelServerInterceptor,
        )

        class _Details:
            method = "/svc/Bench"
            invocation_metadata = ()

        interceptor = SentinelServerInterceptor()

        def _continuation(details):
            import grpc as _g

            return _g.unary_unary_rpc_method_handler(lambda req, ctx: "ok")

        class _Ctx:
            def abort(self, code, details):
                raise RuntimeError("aborted")

        def _grpc_call():
            handler = interceptor.intercept_service(_continuation, _Details())
            if handler is not None and handler.unary_unary is not None:
                try:
                    handler.unary_unary(None, _Ctx())
                except RuntimeError:
                    pass  # blocked → abort; still one admission decided

        _measure("grpc", _sync_driver, _grpc_call, n_ops, OFF_OPS)
    except ImportError:
        _log("adapters: grpcio not installed — skipped")

    # Flask / FastAPI ride the same spine through their own hooks; when
    # installed they get first-class rows, otherwise the WSGI/ASGI rows
    # above are their stand-ins (identical windowed entry path).
    for name, mod in (("flask", "flask"), ("fastapi", "fastapi")):
        try:
            __import__(mod)
        except ImportError:
            _log(f"adapters: {mod} not installed — skipped "
                 f"({'wsgi' if name == 'flask' else 'asgi'} row is the "
                 "stand-in; same windowed entry path)")

    import jax

    api.reset()
    for key in (config.INGEST_BATCH_WINDOW_MS, config.INGEST_BATCH_MAX):
        config.set(key, config.DEFAULTS[key])
    out.update(
        {
            "platform": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "jax_version": jax.__version__,
        }
    )
    return out


def _run_autotune_stage(n_rules: int, n_ops: int, iters: int) -> dict:
    """Self-tuning control plane (runtime/autotune.py): converge-from-
    cold A/B. First measure a pipelined bulk workload at each static
    pipeline depth (the hand-tuning an operator would do per box), then
    run the SAME workload autotune-on starting cold at depth 0 and
    report the chosen depth/window trajectory and steady-state ops/s
    against the best static setting. PR-12 acceptance: steady-state
    >= 0.9x static-best on this box, and the decision log is a monotone
    settle (no knob reversal under the steady stream)."""
    import jax

    from sentinel_tpu.models.rules import FlowRule
    from sentinel_tpu.runtime.engine import Engine
    from sentinel_tpu.utils.config import config

    n_rules, n_ops, iters = max(1, n_rules), max(256, n_ops), max(1, iters)
    groups = 16
    bulk_n = max(64, n_ops // groups)
    per_flush = groups * bulk_n
    _log(f"autotune stage rules={n_rules} ops/flush={per_flush}")

    def _mk() -> Engine:
        eng = Engine(initial_rows=max(1024, n_rules * 2))
        eng.set_flow_rules(
            [FlowRule(resource=f"r{i}", count=1e9) for i in range(n_rules)]
        )
        return eng

    def _workload(eng, rounds: int) -> None:
        for _ in range(rounds):
            for i in range(groups):
                eng.submit_bulk(f"r{i % n_rules}", bulk_n)
            eng.flush()
        eng.drain()

    def _measure(eng, rounds: int) -> float:
        t0 = time.perf_counter()
        _workload(eng, rounds)
        return per_flush * rounds / (time.perf_counter() - t0)

    rounds = max(8, iters * 8)
    tuned_keys = (
        config.PIPELINE_DEPTH, config.AUTOTUNE_ENABLED,
        config.AUTOTUNE_INTERVAL_MS, config.AUTOTUNE_COOLDOWN_MS,
        config.AUTOTUNE_MIN_FLUSHES, config.AUTOTUNE_DEPTH_MAX,
    )
    try:
        # --- static sweep: the hand-tuned baselines.
        static: dict[int, float] = {}
        config.set(config.AUTOTUNE_ENABLED, "false")
        for depth in (0, 1, 2):
            config.set(config.PIPELINE_DEPTH, str(depth))
            eng = _mk()
            _workload(eng, 2)  # warm: interning + kernel compile
            static[depth] = _measure(eng, rounds)
            eng.close()
            _log(f"autotune static depth={depth}: {static[depth]:,.0f} ops/s")
        best_depth = max(static, key=static.__getitem__)
        best_ops = static[best_depth]

        # --- converge from cold: depth 0, controller on, fast cadence
        # (real-clock ticks ride every drain; the decision interval is
        # shortened so convergence fits the bench budget).
        config.set(config.PIPELINE_DEPTH, "0")
        config.set(config.AUTOTUNE_ENABLED, "true")
        config.set(config.AUTOTUNE_INTERVAL_MS, "25")
        config.set(config.AUTOTUNE_COOLDOWN_MS, "50")
        # At this stage's big-flush cadence (one multi-ms flush per
        # tick window) a single settled span is already a large sample
        # — the production default of 8 is sized for kHz flush rates.
        config.set(config.AUTOTUNE_MIN_FLUSHES, "1")
        config.set(config.AUTOTUNE_DEPTH_MAX, "4")
        eng = _mk()
        _workload(eng, 2)  # warm compile (depth may move mid-round)
        converge_ops = _measure(eng, rounds)  # the cold->settled span
        # Best-of-2 steady measurement (the adapters stage's defense
        # against the box's tenancy noise — a single later-in-run
        # sample loses ~10% to drift alone).
        steady_ops = max(_measure(eng, rounds), _measure(eng, rounds))
        traj = [
            {"knob": d["knob"], "from": d["from"], "to": d["to"],
             "reason": d["reason"]}
            for d in eng.autotune.decisions
        ]
        final_depth = eng.pipeline_depth
        ticks = eng.autotune.counters["ticks"]
        eng.close()
    finally:
        for key in tuned_keys:
            config.set(key, config.DEFAULTS[key])

    ratio = steady_ops / best_ops if best_ops > 0 else 0.0
    depth_moves = [d["to"] for d in traj if d["knob"] == "depth"]
    monotone = all(b >= a for a, b in zip(depth_moves, depth_moves[1:]))
    _log(
        f"autotune stage done: steady {steady_ops:,.0f} ops/s vs static-best "
        f"depth={best_depth} {best_ops:,.0f} ({ratio:.2f}x, accept >=0.9); "
        f"final depth {final_depth}, {len(traj)} decisions over {ticks} "
        f"ticks, monotone={monotone}"
    )
    return {
        "autotune_n_rules": n_rules,
        "autotune_n_ops": per_flush,
        "autotune_static_ops_per_sec": {
            str(d): round(v, 1) for d, v in static.items()
        },
        "autotune_static_best_depth": best_depth,
        "autotune_static_best_ops_per_sec": round(best_ops, 1),
        "autotune_converge_ops_per_sec": round(converge_ops, 1),
        "autotune_steady_ops_per_sec": round(steady_ops, 1),
        "autotune_vs_static_best": round(ratio, 4),
        "autotune_final_depth": final_depth,
        "autotune_decisions": len(traj),
        "autotune_monotone": monotone,
        "autotune_trajectory": traj,
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "jax_version": jax.__version__,
    }


def _ipc_bench_worker(
    channel, wid, resources, rows_total, group, go_warm, go_timed, out_q
):
    """One bench worker process: attach, signal ready, run one full
    WARM quota round (interning, frame shapes, the engine-side settle
    compiles), then the timed round — so the measured span is steady-
    state transport, not XLA compiles. Top-level so the spawn child can
    import it by name."""
    from sentinel_tpu.ipc.worker import IngestClient

    cli = IngestClient(channel, wid)

    def _round() -> int:
        admitted = 0
        done = 0
        i = 0
        while done < rows_total:
            n = min(group, rows_total - done)
            a, _r, _w, _f = cli.bulk(resources[i % len(resources)], n)
            admitted += int(a.sum())
            done += n
            i += 1
        return admitted

    try:
        out_q.put(("ready", wid, 0))
        go_warm.wait(timeout=120)
        _round()
        out_q.put(("warm", wid, 0))
        go_timed.wait(timeout=300)
        admitted = _round()
        out_q.put(("done", wid, admitted))
    finally:
        cli.close()


def _ipc_sweep_worker(
    channel, wid, resources, quota, threads, cfg, go, out_q
):
    """One sweep worker process: ``threads`` concurrent entry() loops
    totaling ``quota`` admissions. ``cfg`` replays the mode under test
    into the child (micro-window on/off) — spawn children start from
    config defaults. Top-level so the spawn child imports it by name."""
    import threading as _th

    from sentinel_tpu.utils.config import config as _cfg

    for k, v in cfg.items():
        _cfg.set(k, v)
    from sentinel_tpu.ipc.worker import IngestClient

    cli = IngestClient(channel, wid)
    try:
        out_q.put(("ready", wid, 0))
        go.wait(timeout=300)
        per = max(1, quota // threads)

        def loop():
            for i in range(per):
                cli.entry(resources[i % len(resources)], timeout_ms=120000)

        ts = [_th.Thread(target=loop) for _ in range(threads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        out_q.put(("done", wid, (per * threads, dt, dict(cli.counters))))
    finally:
        cli.close()


def _bench_restart_setup(engine) -> None:
    """Supervised-engine setup for the restart-outage measurement
    (top-level so multiprocessing spawn children import it by name)."""
    from sentinel_tpu.models.rules import FlowRule

    engine.set_flow_rules([FlowRule(resource="r0", count=1e9)])


def _run_ipc_stage(n_rules: int, n_ops: int, iters: int) -> dict:
    """Multi-process ingest plane (sentinel_tpu/ipc): N-worker vs
    in-process A/B. The same bulk workload is pushed (a) by N real
    worker processes through the shared-memory rings and (b) by an
    in-process driver straight into submit_bulk — the delta is the
    plane's frame + ring cost, the ratio is the scale-out story's
    baseline number. Plus the single-entry shared-memory round-trip
    percentiles from an in-process client (frame encode -> ring ->
    plane decode -> columnar submit -> verdict frame)."""
    import jax

    from sentinel_tpu.ipc.plane import IngestPlane
    from sentinel_tpu.ipc.worker import IngestClient
    from sentinel_tpu.models.rules import FlowRule
    from sentinel_tpu.runtime.engine import Engine
    from sentinel_tpu.utils.config import config

    n_rules = max(1, min(n_rules, 64))
    n_ops = max(512, n_ops)
    # One bulk call = one frame: keep the group inside the slot's
    # entry-frame budget so a call never splits into two round trips.
    group = 224
    n_workers = 2
    resources = [f"r{i}" for i in range(n_rules)]
    _log(f"ipc stage rules={n_rules} ops={n_ops} workers={n_workers}")

    config.set(config.SPECULATIVE_ENABLED, "true")
    config.set(config.SPECULATIVE_FLUSH_BATCH, "4096")
    # No mid-measure reaps: the workers do not exit their admissions
    # (the rule is wide open), and a dead-worker sweep firing between
    # phases would run exit-bulk compiles inside the timed spans.
    config.set(config.IPC_WORKER_DEAD_MS, "120000")
    try:
        eng = Engine(initial_rows=max(1024, n_rules * 2))
        eng.set_flow_rules(
            [FlowRule(resource=r, count=1e9) for r in resources]
        )

        # --- in-process baseline: the same bulk cadence, no plane.
        def _inproc(total: int) -> float:
            t0 = time.perf_counter()
            done = 0
            i = 0
            while done < total:
                n = min(group, total - done)
                eng.submit_bulk(resources[i % n_rules], n)
                done += n
                i += 1
            eng.flush()
            eng.drain()
            return total / (time.perf_counter() - t0)

        _inproc(group * 4)  # warm: compile + interning
        inproc_ops = max(_inproc(n_ops), _inproc(n_ops))

        # --- the plane + N spawned workers, quota split evenly; one
        # full warm round before the timed one (see _ipc_bench_worker).
        plane = IngestPlane(eng)
        ctx = plane.spawn_context()
        go_warm = ctx.Event()
        go_timed = ctx.Event()
        out_q = ctx.Queue()
        quota = n_ops // n_workers
        procs = [
            ctx.Process(
                target=_ipc_bench_worker,
                args=(plane.channel(w), w, resources, quota, group,
                      go_warm, go_timed, out_q),
                daemon=True,
            )
            for w in range(n_workers)
        ]
        for p in procs:
            p.start()
        workers_ops = 0.0
        admitted = 0
        try:
            def _await(tag, timeout):
                seen = 0
                total = 0
                while seen < n_workers:
                    msg = out_q.get(timeout=timeout)
                    if msg[0] == tag:
                        seen += 1
                        total += msg[2]
                return total

            _await("ready", 120)
            go_warm.set()
            _await("warm", 300)
            t0 = time.perf_counter()
            go_timed.set()
            admitted = _await("done", 300)
            workers_ops = quota * n_workers / (time.perf_counter() - t0)
        finally:
            for p in procs:
                p.join(timeout=10)
                if p.is_alive():
                    p.terminate()

        # --- single-entry shared-memory round trip (in-process client).
        cli = IngestClient(plane.channel(n_workers), n_workers)
        for i in range(64):
            cli.entry(resources[i % n_rules])
        lats = []
        for i in range(1024):
            t0 = time.perf_counter()
            cli.entry(resources[i % n_rules])
            lats.append(time.perf_counter() - t0)
        eng.flush()
        lats.sort()
        p50 = lats[len(lats) // 2] * 1e6
        p99 = lats[int(len(lats) * 0.99)] * 1e6

        # --- span-armed decomposition (PR 18): the same round trip
        # with the fleet span journal recording, decomposing the e2e
        # verdict wait into client encode+push vs engine drain — plus
        # the armed/unarmed p50 ratio, the bench-side twin of the
        # slow-tier ≤2% overhead guard. Armed AFTER the headline
        # percentiles above, so those stay span-free.
        from sentinel_tpu.metrics.spans import get_journal as _get_spj

        spj = _get_spj()
        spans_before = len(spj.spans())
        spj.enabled = True
        lats_sp = []
        try:
            for i in range(1024):
                t0 = time.perf_counter()
                cli.entry(resources[i % n_rules])
                lats_sp.append(time.perf_counter() - t0)
        finally:
            spj.enabled = False
        eng.flush()
        lats_sp.sort()
        sp_p50 = lats_sp[len(lats_sp) // 2] * 1e6

        def _span_pcts(vals):
            vals = sorted(vals)
            if not vals:
                return 0.0, 0.0
            return (vals[len(vals) // 2], vals[int(len(vals) * 0.99)])

        new_spans = spj.spans()[spans_before:]
        admits_sp = [s for s in new_spans if s["name"] == "admit"]
        drains_sp = [s for s in new_spans if s["name"] == "drain"]
        e2e_p50, e2e_p99 = _span_pcts([s["dur"] for s in admits_sp])
        push_p50, _ = _span_pcts([s.get("push_ms", 0.0) for s in admits_sp])
        drain_p50, drain_p99 = _span_pcts([s["dur"] for s in drains_sp])
        span_overhead = sp_p50 / p50 if p50 > 0 else 0.0
        _log(
            f"ipc span decomposition: e2e p50 {e2e_p50 * 1e3:.0f} µs "
            f"(push {push_p50 * 1e3:.0f} µs, engine drain "
            f"{drain_p50 * 1e3:.0f} µs), armed/unarmed p50 ratio "
            f"{span_overhead:.3f}"
        )

        # --- concurrency sweep: 1/2/4 workers x per-call vs
        # micro-window (ISSUE 14). Per-call = PR-13 framing (one frame
        # per entry); window = the client-side micro-window coalescing
        # each worker's 8 concurrent request threads. Same plane, same
        # engine, same quota — the deltas are the frame amortization
        # story, the frames-per-entry columns its direct evidence.
        sweep_threads = 8
        sweep_quota = max(256, min(4096, n_ops // 2))
        window_cfg = {
            config.IPC_CLIENT_WINDOW_MS: "0.5",
            config.IPC_CLIENT_WINDOW_MAX: "256",
        }

        def _sweep_round(nw: int, mode_cfg: dict):
            ctx2 = plane.spawn_context()
            go = ctx2.Event()
            q2 = ctx2.Queue()
            procs2 = [
                ctx2.Process(
                    target=_ipc_sweep_worker,
                    args=(plane.channel(3 + w), 3 + w, resources,
                          sweep_quota, sweep_threads, mode_cfg, go, q2),
                    daemon=True,
                )
                for w in range(nw)
            ]
            for p in procs2:
                p.start()
            try:
                seen = 0
                while seen < nw:
                    if q2.get(timeout=300)[0] == "ready":
                        seen += 1
                go.set()
                total_ops = 0
                max_dt = 0.0
                frames = 0
                reqs = 0
                policy = 0
                sheds = 0
                seen = 0
                while seen < nw:
                    msg = q2.get(timeout=600)
                    if msg[0] != "done":
                        continue
                    ops, dt, c = msg[2]
                    seen += 1
                    total_ops += ops
                    max_dt = max(max_dt, dt)
                    frames += c.get("frames", 0)
                    reqs += c.get("entries", 0)
                    policy += c.get("policy_served", 0)
                    sheds += c.get("sheds", 0)
                ops_s = total_ops / max_dt if max_dt > 0 else 0.0
                fpe = frames / reqs if reqs else 0.0
                return ops_s, fpe, policy, sheds
            finally:
                for p in procs2:
                    p.join(timeout=15)
                    if p.is_alive():
                        p.terminate()

        sweep: dict = {"ipc_sweep_quota": sweep_quota}
        fpe_percall = fpe_window = 0.0
        sweep_policy = sweep_sheds = 0
        for mode, mode_cfg in (("percall", {}), ("window", window_cfg)):
            for nw in (1, 2, 4):
                ops_s, fpe, policy, sheds = _sweep_round(nw, mode_cfg)
                sweep[f"ipc_{mode}_w{nw}_ops_per_sec"] = round(ops_s, 1)
                sweep_policy += policy
                sweep_sheds += sheds
                if nw == 1:
                    if mode == "percall":
                        fpe_percall = fpe
                    else:
                        fpe_window = fpe
                _log(
                    f"ipc sweep {mode} w{nw}: {ops_s:,.0f} ops/s "
                    f"(frames/entry {fpe:.3f}, policy {policy}, "
                    f"sheds {sheds})"
                )
        # The sweep's honesty columns (the single-entry A/B's
        # ipc_client_policy_served twin): ops/s rows where workers fell
        # to the local policy path or shed are measuring fallbacks, not
        # transport — a nonzero count flags the round as suspect.
        sweep["ipc_sweep_policy_served"] = sweep_policy
        sweep["ipc_sweep_sheds"] = sweep_sheds
        sweep["ipc_frames_per_entry_percall"] = round(fpe_percall, 4)
        sweep["ipc_frames_per_entry_window"] = round(fpe_window, 4)
        sweep["ipc_window_amortization"] = round(
            fpe_percall / fpe_window, 2
        ) if fpe_window > 0 else 0.0

        plane_counters = dict(plane.snapshot()["counters"])
        cli_counters = dict(cli.counters)
        cli.close()
        plane.close()

        # --- adaptive-wakeup A/B: the same single-entry round trip
        # with spin-then-park ring waits (a fresh plane — doorbells
        # exist only when the plane is built under wakeup=adaptive).
        # Same-run, same box: the ratio is immune to the host-identity
        # hazard the benchgate token guards against.
        config.set(config.IPC_WAKEUP, "adaptive")
        plane2 = IngestPlane(eng)
        cli2 = IngestClient(plane2.channel(0), 0)
        for i in range(64):
            cli2.entry(resources[i % n_rules])
        lats2 = []
        for i in range(1024):
            t0 = time.perf_counter()
            cli2.entry(resources[i % n_rules])
            lats2.append(time.perf_counter() - t0)
        eng.flush()
        lats2.sort()
        ad_p50 = lats2[len(lats2) // 2] * 1e6
        ad_p99 = lats2[int(len(lats2) * 0.99)] * 1e6
        cli2_policy = cli2.counters.get("policy_served", 0)
        cli2.close()
        plane2.close()
        eng.close()

        # --- engine hot-restart outage (PR 15): supervised engine on
        # named rings, kill -9 the engine child, time until the probing
        # client is served device-backed verdicts again (includes the
        # dead-ms detection window, the restart backoff, the child's
        # cold boot and the durable warm restore). Failure omits the
        # column instead of poisoning the gate with a fake number.
        import os as _os
        import tempfile as _tempfile

        restart_cols: dict = {}
        ckpt = _os.path.join(
            "/dev/shm" if _os.path.isdir("/dev/shm")
            else _tempfile.gettempdir(),
            f"stpu-bench-ckpt-{_os.getpid()}.bin",
        )
        try:
            config.set(config.IPC_WAKEUP, config.DEFAULTS[config.IPC_WAKEUP])
            config.set(config.IPC_HEARTBEAT_MS, "50")
            config.set(config.IPC_ENGINE_DEAD_MS, "2000")
            config.set(config.SUPERVISE_BACKOFF_MS, "200")
            config.set(config.FAILOVER_ENABLED, "true")
            config.set(config.FAILOVER_CHECKPOINT_EVERY, "2")
            config.set(config.FAILOVER_CKPT_PATH, ckpt)
            from sentinel_tpu.ipc.supervise import measure_restart_outage

            out = measure_restart_outage(
                _bench_restart_setup, "r0", timeout_s=240
            )
            restart_cols = {
                "ipc_restart_outage_ms": round(out["outage_ms"], 1),
                "ipc_restart_reconnects": out["reconnects"],
                "ipc_restarts": out["restarts"],
            }
            _log(
                f"ipc restart outage {out['outage_ms']:.0f} ms "
                f"({out['restarts']} restart, {out['reconnects']} "
                "reconnect)"
            )
        except Exception as e:
            _log(f"ipc restart measurement failed ({e}) — column omitted")
        finally:
            try:
                _os.unlink(ckpt)
            except OSError:
                pass
            for key in (
                config.IPC_HEARTBEAT_MS, config.IPC_ENGINE_DEAD_MS,
                config.SUPERVISE_BACKOFF_MS, config.FAILOVER_ENABLED,
                config.FAILOVER_CHECKPOINT_EVERY, config.FAILOVER_CKPT_PATH,
                config.IPC_SHM_PREFIX,
            ):
                config.set(key, config.DEFAULTS[key])

        # --- warm standby + planned handoff (PR 20): the same kill -9
        # with a pre-forked compile-warmed standby armed (the outage
        # should be ≈ the detection window, the cold-boot term gone),
        # then one operator handoff cycle (zero policy-served is the
        # acceptance bit; the column is the worst held-verdict gap).
        standby_cols: dict = {}
        ckpt_sb = _os.path.join(
            "/dev/shm" if _os.path.isdir("/dev/shm")
            else _tempfile.gettempdir(),
            f"stpu-bench-sb-{_os.getpid()}.bin",
        )
        try:
            config.set(config.IPC_HEARTBEAT_MS, "50")
            config.set(config.IPC_ENGINE_DEAD_MS, "2000")
            config.set(config.IPC_ENGINE_DEAD_CONFIRM_MS, "1000")
            config.set(config.IPC_HANDOFF_WAIT_MS, "30000")
            config.set(config.SUPERVISE_BACKOFF_MS, "200")
            config.set(config.SUPERVISE_STANDBY, "true")
            config.set(config.SUPERVISE_STANDBY_WARM_MS, "500")
            config.set(config.FAILOVER_ENABLED, "true")
            config.set(config.FAILOVER_CHECKPOINT_EVERY, "2")
            config.set(config.FAILOVER_CKPT_PATH, ckpt_sb)
            from sentinel_tpu.ipc.supervise import (
                measure_handoff_outage,
                measure_standby_outage,
            )

            out = measure_standby_outage(
                _bench_restart_setup, "r0", timeout_s=240
            )
            standby_cols = {
                "ipc_standby_outage_ms": round(out["outage_ms"], 1),
                "ipc_standby_warm_boot_ms": round(
                    out["standby_warm_boot_ms"] or 0.0, 1
                ),
                "ipc_standby_takeovers": out["standby_takeovers"],
            }
            _log(
                f"ipc standby outage {out['outage_ms']:.0f} ms "
                f"(warm boot {out['standby_warm_boot_ms']:.0f} ms off "
                f"the outage path, {out['standby_takeovers']} takeover)"
            )
            out = measure_handoff_outage(
                _bench_restart_setup, "r0", timeout_s=240
            )
            standby_cols["ipc_handoff_outage_ms"] = round(
                out["handoff_outage_ms"], 1
            )
            standby_cols["ipc_handoff_policy_served"] = out["policy_served"]
            _log(
                f"ipc handoff worst verdict gap "
                f"{out['handoff_outage_ms']:.0f} ms "
                f"({out['policy_served']} policy-served, "
                f"{out['handoffs']} handoff)"
            )
        except Exception as e:
            _log(f"ipc standby measurement failed ({e}) — columns omitted")
        finally:
            try:
                _os.unlink(ckpt_sb)
            except OSError:
                pass
            for key in (
                config.IPC_HEARTBEAT_MS, config.IPC_ENGINE_DEAD_MS,
                config.IPC_ENGINE_DEAD_CONFIRM_MS, config.IPC_HANDOFF_WAIT_MS,
                config.SUPERVISE_BACKOFF_MS, config.SUPERVISE_STANDBY,
                config.SUPERVISE_STANDBY_WARM_MS, config.FAILOVER_ENABLED,
                config.FAILOVER_CHECKPOINT_EVERY, config.FAILOVER_CKPT_PATH,
                config.IPC_SHM_PREFIX,
            ):
                config.set(key, config.DEFAULTS[key])
    finally:
        for key in (
            config.SPECULATIVE_ENABLED, config.SPECULATIVE_FLUSH_BATCH,
            config.IPC_WORKER_DEAD_MS, config.IPC_WAKEUP,
        ):
            config.set(key, config.DEFAULTS[key])

    ratio = workers_ops / inproc_ops if inproc_ops > 0 else 0.0
    wakeup_speedup = p50 / ad_p50 if ad_p50 > 0 else 0.0
    _log(
        f"ipc stage done: {n_workers} workers {workers_ops:,.0f} ops/s vs "
        f"in-process {inproc_ops:,.0f} ({ratio:.2f}x); entry rt p50 "
        f"{p50:.0f} µs p99 {p99:.0f} µs (adaptive p50 {ad_p50:.0f} µs = "
        f"{wakeup_speedup:.2f}x); window amortization "
        f"{sweep['ipc_window_amortization']:.1f}x; admitted {admitted}; "
        f"client policy_served={cli_counters.get('policy_served', 0)} "
        f"sheds={cli_counters.get('sheds', 0)}"
    )
    return {
        "ipc_n_ops": n_ops,
        "ipc_n_workers": n_workers,
        "ipc_workers_ops_per_sec": round(workers_ops, 1),
        "ipc_inproc_ops_per_sec": round(inproc_ops, 1),
        "ipc_vs_inproc": round(ratio, 4),
        "ipc_entry_p50_us": round(p50, 1),
        "ipc_entry_p99_us": round(p99, 1),
        # Adaptive-wakeup same-run A/B (spin-then-park vs sleep-poll).
        "ipc_entry_adaptive_p50_us": round(ad_p50, 1),
        "ipc_entry_adaptive_p99_us": round(ad_p99, 1),
        "ipc_wakeup_speedup": round(wakeup_speedup, 3),
        # Span-journal decomposition of the entry round trip (ms -> µs):
        # e2e = the worker admit span (join -> verdict), push = its
        # client encode + ring-push leg, drain = the engine-side
        # dequeue -> decide -> respond span. The overhead ratio is the
        # armed/unarmed p50 A/B (same client, same run).
        "ipc_span_e2e_p50_us": round(e2e_p50 * 1e3, 1),
        "ipc_span_e2e_p99_us": round(e2e_p99 * 1e3, 1),
        "ipc_span_push_p50_us": round(push_p50 * 1e3, 1),
        "ipc_span_drain_p50_us": round(drain_p50 * 1e3, 1),
        "ipc_span_drain_p99_us": round(drain_p99 * 1e3, 1),
        "ipc_span_overhead": round(span_overhead, 3),
        **sweep,
        "ipc_frames": plane_counters.get("frames", 0),
        "ipc_admitted": admitted,
        # Honesty columns: a policy-served latency sample would mean
        # the measured number was the DEAD-ENGINE fallback, not the
        # ring round trip.
        "ipc_client_policy_served": cli_counters.get("policy_served", 0),
        "ipc_client_sheds": cli_counters.get("sheds", 0),
        "ipc_adaptive_policy_served": cli2_policy,
        **restart_cols,
        **standby_cols,
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "jax_version": jax.__version__,
        **_host_identity(),
    }


def _run_cluster_stage(n_rules: int, n_ops: int, iters: int) -> dict:
    """Batched cluster token plane (cluster/client.py + server.py):
    frames-per-token-decision and ops/s for the three wire stances
    against one real TCP token server — (a) per-call (the PR-15
    default: one frame per decision), (b) client micro-window
    (concurrent callers coalesce into FLOW_REQUEST_BATCH frames), and
    (c) micro-window + local quota leases (hot-flow admissions served
    with ZERO frames in steady state). Honesty columns count FAIL-
    family fallback serves per mode — a nonzero means that mode's
    number includes local-stance verdicts, not server verdicts."""
    import threading as _threading

    import jax

    from sentinel_tpu.cluster import (
        cluster_flow_rule_manager,
        cluster_server_config_manager,
    )
    from sentinel_tpu.cluster.client import ClusterTokenClient, client_stats
    from sentinel_tpu.cluster.server import SentinelTokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.models import constants as C
    from sentinel_tpu.models.rules import ClusterFlowConfig, FlowRule
    from sentinel_tpu.utils.config import config

    n_ops = max(256, n_ops)
    n_threads = 8
    per_thread = n_ops // n_threads
    n_ops = per_thread * n_threads
    flow_id = 42
    _log(f"cluster stage ops={n_ops} threads={n_threads}")

    # One wide-open rule: the stage measures the WIRE cost of a
    # decision, not admission math (the differential tests pin that).
    cluster_flow_rule_manager.clear()
    cluster_server_config_manager.load_global_flow_config(
        exceed_count=1.0, max_allowed_qps=1e12
    )
    cluster_flow_rule_manager.load_rules(
        "default",
        [FlowRule(
            "r", count=1e9, cluster_mode=True,
            cluster_config=ClusterFlowConfig(
                flow_id=flow_id,
                threshold_type=C.FLOW_THRESHOLD_GLOBAL,
            ),
        )],
    )
    server = SentinelTokenServer(port=0, service=DefaultTokenService())
    server.start()
    out: dict = {"cluster_n_ops": n_ops}

    def drive(mode: str) -> None:
        if mode == "percall":
            config.set(config.CLUSTER_CLIENT_WINDOW_MS, "0")
            config.set(config.CLUSTER_LEASE_ENABLED, "false")
        elif mode == "window":
            config.set(config.CLUSTER_CLIENT_WINDOW_MS, "2")
            config.set(config.CLUSTER_CLIENT_WINDOW_MAX, "64")
            config.set(config.CLUSTER_LEASE_ENABLED, "false")
        else:  # lease
            config.set(config.CLUSTER_CLIENT_WINDOW_MS, "2")
            config.set(config.CLUSTER_CLIENT_WINDOW_MAX, "64")
            config.set(config.CLUSTER_LEASE_ENABLED, "true")
            config.set(config.CLUSTER_LEASE_TTL_MS, "1000")
        client_stats.reset()
        client = ClusterTokenClient("127.0.0.1", server.port).start()
        try:
            client.request_token(flow_id)  # connect + warm outside the clock
            client_stats.reset()
            barrier = _threading.Barrier(n_threads + 1)

            def worker():
                barrier.wait()
                for _ in range(per_thread):
                    client.request_token(flow_id)

            threads = [
                _threading.Thread(target=worker) for _ in range(n_threads)
            ]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
        finally:
            client.stop()
        snap = client_stats.snapshot()
        # Frames actually sent: batch frames plus per-call RPCs (the
        # rpc histogram counts every awaited round trip; batched rows
        # share one sample per frame, so subtract the double count).
        frames = (
            snap["batch_frames"]
            if snap["batch_frames"]
            else snap["rpc"]["count"]
        )
        fpo = frames / n_ops if n_ops else 0.0
        out[f"cluster_{mode}_ops_per_sec"] = round(n_ops / dt, 1)
        out[f"cluster_frames_per_op_{mode}"] = round(fpo, 4)
        out[f"cluster_{mode}_fallbacks"] = snap["fallbacks"]
        if mode == "lease":
            out["cluster_lease_hit_rate"] = round(
                snap["lease_admits"] / max(1, snap["requests"]), 4
            )
        _log(
            f"cluster {mode}: {n_ops / dt:,.0f} ops/s, "
            f"{fpo:.3f} frames/op, fallbacks={snap['fallbacks']}"
        )
        _emit_partial = dict(out)
        print(json.dumps(_emit_partial), flush=True)

    try:
        for mode in ("percall", "window", "lease"):
            drive(mode)

        # --- span decomposition (PR 18): one single-threaded per-call
        # round with the fleet span journal armed — the client rpc
        # span (send -> response) against the shard's serve span
        # (decode -> decide -> reply, stamped by the same-process
        # server). rpc − serve ≈ the wire + reader-dispatch share.
        # Armed AFTER the headline modes, so their ops/s stay
        # span-free.
        from sentinel_tpu.metrics.spans import get_journal as _get_spj

        config.set(config.CLUSTER_CLIENT_WINDOW_MS, "0")
        config.set(config.CLUSTER_LEASE_ENABLED, "false")
        spj = _get_spj()
        spans_before = len(spj.spans())
        dec_ops = min(1024, n_ops)
        client = ClusterTokenClient("127.0.0.1", server.port).start()
        try:
            client.request_token(flow_id)  # connect outside the spans
            spj.enabled = True
            for _ in range(dec_ops):
                client.request_token(flow_id)
        finally:
            spj.enabled = False
            client.stop()

        def _pcts_ms(vals):
            vals = sorted(vals)
            if not vals:
                return 0.0, 0.0
            return (vals[len(vals) // 2], vals[int(len(vals) * 0.99)])

        new_spans = spj.spans()[spans_before:]
        rpc_p50, rpc_p99 = _pcts_ms(
            [s["dur"] for s in new_spans
             if s["cat"] == "client" and s["name"] == "rpc"]
        )
        srv_p50, srv_p99 = _pcts_ms(
            [s["dur"] for s in new_spans
             if s["cat"] == "shard" and s["name"] == "serve"]
        )
        out["cluster_rpc_p50_ms"] = round(rpc_p50, 4)
        out["cluster_rpc_p99_ms"] = round(rpc_p99, 4)
        out["cluster_serve_p50_ms"] = round(srv_p50, 4)
        out["cluster_serve_p99_ms"] = round(srv_p99, 4)
        out["cluster_wire_share"] = round(
            (rpc_p50 - srv_p50) / rpc_p50, 4
        ) if rpc_p50 > 0 else 0.0
        _log(
            f"cluster span decomposition: rpc p50 {rpc_p50:.3f} ms, "
            f"serve p50 {srv_p50:.3f} ms "
            f"(wire share {out['cluster_wire_share']:.2f})"
        )
    finally:
        server.stop()
        cluster_flow_rule_manager.clear()
        for key in (
            config.CLUSTER_CLIENT_WINDOW_MS, config.CLUSTER_CLIENT_WINDOW_MAX,
            config.CLUSTER_LEASE_ENABLED, config.CLUSTER_LEASE_TTL_MS,
        ):
            config.set(key, config.DEFAULTS[key])

    amort = (
        out.get("cluster_frames_per_op_percall", 1.0)
        / max(1e-9, out.get("cluster_frames_per_op_window", 1.0))
    )
    out["cluster_window_amortization"] = round(amort, 3)
    _log(
        f"cluster stage done: window amortization {amort:.1f}x, lease "
        f"hit rate {out.get('cluster_lease_hit_rate', 0.0):.2f}"
    )

    # ---- shard sweep (PR 17): 1/2/4 hash-partitioned shards, batched
    # rows, window vs lease stance. Wall-clock ops/s is RECORDED but
    # not the gate — this is typically a 1-core box, so aggregate
    # decision capacity is measured from the servers' own work clocks
    # (Σ per-shard decisions/busy_s), alongside frames/op and the
    # parallel-issue honesty counter (fraction of windows whose rows
    # spanned >1 shard and were issued concurrently).
    from sentinel_tpu.cluster.shards import (
        ShardMap,
        ShardedTokenClient,
        shard_of,
    )

    shard_flows = list(range(500, 532))
    cluster_flow_rule_manager.load_rules(
        "default",
        [FlowRule(
            "sr%d" % f, count=1e9, cluster_mode=True,
            cluster_config=ClusterFlowConfig(
                flow_id=f, threshold_type=C.FLOW_THRESHOLD_GLOBAL,
            ),
        ) for f in shard_flows],
    )
    batch_rows = [
        (shard_flows[i % len(shard_flows)], 1, False) for i in range(256)
    ]
    shard_threads = 4
    per_thread_batches = max(6, min(24, n_ops // (256 * shard_threads)))
    shard_ops = per_thread_batches * shard_threads * len(batch_rows)
    out["cluster_shard_ops"] = shard_ops

    def drive_shards(n_shards: int, stance: str) -> None:
        config.set(config.CLUSTER_CLIENT_WINDOW_MS, "0")
        config.set(
            config.CLUSTER_LEASE_ENABLED,
            "true" if stance == "lease" else "false",
        )
        config.set(config.CLUSTER_LEASE_TTL_MS, "1000")
        servers = [
            SentinelTokenServer(port=0, service=DefaultTokenService()).start()
            for _ in range(n_shards)
        ]
        client = ShardedTokenClient(
            ShardMap(0, [("127.0.0.1", s.port) for s in servers])
        ).start()
        capacity = 0.0
        try:
            client.request_tokens_batch(batch_rows)  # warm every shard
            for s in servers:
                s.reset_work_stats()
            client_stats.reset()
            barrier = _threading.Barrier(shard_threads + 1)

            def worker():
                barrier.wait()
                for _ in range(per_thread_batches):
                    client.request_tokens_batch(batch_rows)

            threads = [
                _threading.Thread(target=worker)
                for _ in range(shard_threads)
            ]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            plane = client.plane_snapshot()
            snap = client_stats.snapshot()
            if stance == "window":
                # Aggregate decision capacity = Σ per-shard standalone
                # saturation (decisions/busy_s from each server's own
                # work clock), measured one shard at a time: a 1-core
                # box serializes concurrent handlers through the GIL,
                # which would charge every shard's busy clock with the
                # others' contention — while each deployment shard is
                # its own machine. The PARALLEL run above (recorded as
                # wall ops/s + parallel_issue) is the honesty column
                # showing the client really issues shards concurrently.
                for s in servers:
                    s.reset_work_stats()
                for i in range(n_shards):
                    rows_i = [
                        r for r in batch_rows
                        if shard_of(r[0], n_shards) == i
                    ] or batch_rows[:8]
                    rows_i = (rows_i * (256 // len(rows_i) + 1))[:256]
                    for _ in range(6):
                        client.clients[i].request_tokens_batch(rows_i)
                capacity = sum(
                    w["decisions"] / w["busy_s"]
                    for w in (s.work_stats() for s in servers)
                    if w["busy_s"] > 0
                )
        finally:
            client.stop()
            for s in servers:
                s.stop()
        frames = snap["batch_frames"] or snap["rpc"]["count"]
        tag = f"cluster_shard{n_shards}_{stance}"
        out[f"{tag}_ops_per_sec"] = round(shard_ops / dt, 1)
        out[f"{tag}_frames_per_op"] = round(frames / shard_ops, 4)
        out[f"{tag}_fallbacks"] = snap["fallbacks"]
        if stance == "window":
            out[f"cluster_shard{n_shards}_capacity_per_sec"] = round(
                capacity, 1
            )
            issued = plane["parallel_batches"] + plane["single_batches"]
            out[f"cluster_shard{n_shards}_parallel_issue"] = round(
                plane["parallel_batches"] / max(1, issued), 4
            )
        else:
            out[f"{tag}_hit_rate"] = round(
                snap["lease_admits"] / max(1, snap["requests"]), 4
            )
        _log(
            f"cluster shard{n_shards}/{stance}: {shard_ops / dt:,.0f} "
            f"ops/s wall, capacity {capacity:,.0f}/s, "
            f"{frames / shard_ops:.4f} frames/op"
        )
        print(json.dumps(dict(out)), flush=True)

    for _n in (1, 2, 4):
        for _stance in ("window", "lease"):
            drive_shards(_n, _stance)
    cap1 = out.get("cluster_shard1_capacity_per_sec", 0.0)
    cap4 = out.get("cluster_shard4_capacity_per_sec", 0.0)
    out["cluster_shard_capacity_ratio_4x"] = round(cap4 / max(1e-9, cap1), 3)
    _log(
        f"shard sweep done: 4-shard aggregate capacity "
        f"{out['cluster_shard_capacity_ratio_4x']:.2f}x single-shard"
    )

    # ---- gossip merge cost: merge_remote + fleet-view query in
    # isolation (the wire is one small compressed frame; the cost that
    # scales with fleet size is the saturating vector add + the union
    # key query, so that is what gets a column).
    import numpy as _np

    from sentinel_tpu.runtime.sketch import SketchTier

    saved_g = {
        k: config.get(k)
        for k in (config.SKETCH_ENABLED, config.GOSSIP_ENABLED)
    }
    config.set(config.SKETCH_ENABLED, "true")
    config.set(config.GOSSIP_ENABLED, "true")
    try:
        class _Tele:
            enabled = False

        class _Eng:
            telemetry = _Tele()

        t_a, t_b = SketchTier(_Eng()), SketchTier(_Eng())
        t_b._host_cm[:] = 7
        for k in range(64):
            t_b.host_mirror.offer("\x01sr%d" % k, 50)
        wid, cm, cands = t_b.gossip_snapshot()
        reps = 50
        t0 = time.perf_counter()
        for i in range(reps):
            t_a.merge_remote("peer%d" % (i % 4), wid, cm, cands)
            t_a._fleet_by_key({})
        out["cluster_gossip_merge_ms"] = round(
            (time.perf_counter() - t0) / reps * 1e3, 3
        )
        _log(f"gossip merge cost: {out['cluster_gossip_merge_ms']:.2f} ms")
    finally:
        for k, v in saved_g.items():
            config.set(k, v if v is not None else config.DEFAULTS[k])
    out.update({
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "jax_version": jax.__version__,
        **_host_identity(),
    })
    return out


def _run_stage(n_rules: int, n_entries: int, iters: int) -> dict:
    """Child-process body: build state, compile, time. Prints one JSON
    line with the stage result (including the platform ACTUALLY used)."""
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.metrics.nodes import make_stats
    from sentinel_tpu.rules.degrade_table import DegradeIndex
    from sentinel_tpu.rules.flow_table import FlowRuleDynState, FlowTableDevice
    from sentinel_tpu.rules.param_table import make_param_state
    from sentinel_tpu.runtime.flush import SystemDevice, flush_step_jit
    from __graft_entry__ import _example_batch

    n_rows = n_rules
    k = 1
    _log(f"stage rules={n_rules} entries={n_entries}: building state")
    stats = make_stats(n_rows)
    dindex = DegradeIndex([])
    ddev, ddyn = dindex.device, dindex.make_dyn_state()
    inf = float("inf")
    sysdev = SystemDevice(
        qps=jnp.float32(inf),
        max_thread=jnp.float32(inf),
        max_rt=jnp.float32(inf),
        load_threshold=jnp.float32(-1.0),
        cpu_threshold=jnp.float32(-1.0),
        cur_load=jnp.float32(-1.0),
        cur_cpu=jnp.float32(-1.0),
    )
    # Build the device rule table directly (bypasses the Python bean
    # layer, which is not the hot path being measured).
    dev = FlowTableDevice(
        grade=jnp.ones(n_rules, dtype=jnp.int32),
        count=jnp.full(n_rules, 20.0, dtype=jnp.float32),
        behavior=jnp.zeros(n_rules, dtype=jnp.int32),
        max_queueing_time_ms=jnp.zeros(n_rules, dtype=jnp.int32),
        cost1_ms=jnp.full(n_rules, 50, dtype=jnp.int32),
        warmup_warning_token=jnp.zeros(n_rules, dtype=jnp.int32),
        warmup_max_token=jnp.zeros(n_rules, dtype=jnp.int32),
        warmup_slope=jnp.zeros(n_rules, dtype=jnp.float32),
        warmup_refill_threshold=jnp.zeros(n_rules, dtype=jnp.int32),
    )
    dyn = FlowRuleDynState(
        latest_passed_time=jnp.full(n_rules, -(10**9), dtype=jnp.int32),
        stored_tokens=jnp.zeros(n_rules, dtype=jnp.float32),
        last_filled_time=jnp.full(n_rules, -(10**9), dtype=jnp.int32),
    )
    batch = _example_batch(n_entries, n_rows, n_rules, k)
    pdyn = make_param_state(8)

    # The same host-known specialization the Engine picks for this
    # workload: no prioritized entries, no system/degrade rules, no
    # exits in the batch (runtime/engine._run_chunk `flags`).
    flags = dict(
        with_occupy=False, with_system=False, with_degrade=False, with_exits=False
    )
    _log("compiling + warm-up")
    t0 = time.perf_counter()
    stats, dyn, ddyn, pdyn, _sk, result = flush_step_jit(
        stats, dev, dyn, ddev, ddyn, pdyn, sysdev, batch, **flags
    )
    jax.block_until_ready(result.admitted)
    _log(f"compile+first-run {time.perf_counter() - t0:.1f}s; timing {iters} iters")

    t0 = time.perf_counter()
    for _ in range(iters):
        stats, dyn, ddyn, pdyn, _sk, result = flush_step_jit(
            stats, dev, dyn, ddev, ddyn, pdyn, sysdev, batch, **flags
        )
    jax.block_until_ready(result.admitted)
    dt = (time.perf_counter() - t0) / iters

    checks_per_sec = n_entries / dt
    vs = TARGET_S_PER_ENTRY / (dt / n_entries)
    _log(
        f"stage done: {dt * 1e3:.3f} ms/flush, {checks_per_sec:,.0f} entries/sec, "
        f"vs_baseline {vs:.3f}"
    )
    return {
        "metric": "batched_entry_checks_per_sec_per_chip",
        "value": round(checks_per_sec, 1),
        "unit": "entries/sec",
        "vs_baseline": round(vs, 4),
        "platform": jax.default_backend(),
        # Hardware-truth header: the BENCH trajectory must be able to
        # tell CPU liveness runs from real TPU numbers without reading
        # the log (round-3 lesson, hardened here). The host token
        # (_host_identity) extends it to same-silicon different-speed
        # boxes.
        "device_kind": jax.devices()[0].device_kind,
        "jax_version": jax.__version__,
        **_host_identity(),
        "n_rules": n_rules,
        "n_entries": n_entries,
        "flush_ms": round(dt * 1e3, 4),
    }


def _child_main(args) -> None:
    from sentinel_tpu.utils.backend import (
        enable_compile_cache,
        force_cpu,
        require_platform,
    )

    if args.child_platform == "cpu":
        force_cpu()
    require_platform(args.child_platform)
    enable_compile_cache()
    fn = {
        "kernel": _run_stage,
        "mixed": _run_mixed_stage,
        "engine": _run_engine_stage,
        "speculative": _run_speculative_stage,
        "sketch": _run_sketch_stage,
        "adapters": _run_adapters_stage,
        "autotune": _run_autotune_stage,
        "ipc": _run_ipc_stage,
        "cluster": _run_cluster_stage,
    }[args.kind]
    print(json.dumps(fn(args.rules, args.entries, args.iters)), flush=True)


def _last_json_line(out) -> dict | None:
    """Last parseable non-error JSON object in a child's stdout (str,
    bytes, or None) — the salvage contract for killed stages."""
    if not out:
        return None
    if isinstance(out, bytes):
        out = out.decode("utf-8", errors="replace")
    for line in reversed(out.strip().splitlines()):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "error" not in rec:
            return rec
    return None


def _spawn_stage(
    n_rules: int, n_entries: int, iters: int, platform: str, timeout_s: float,
    kind: str = "kernel",
) -> dict | None:
    cmd = [
        sys.executable,
        os.path.abspath(__file__),
        "--run-stage",
        "--kind", kind,
        "--rules", str(n_rules),
        "--entries", str(n_entries),
        "--iters", str(iters),
        "--child-platform", platform,
    ]
    try:
        r = subprocess.run(
            cmd, stdout=subprocess.PIPE, text=True, timeout=timeout_s
        )  # stderr passes through for live progress
    except subprocess.TimeoutExpired as exc:
        _log(f"stage rules={n_rules} timed out after {timeout_s:.0f}s")
        # Salvage any JSON the child printed before the kill: stages
        # emit completed sub-measurements incrementally for exactly
        # this case.
        rec = _last_json_line(exc.stdout)
        if rec is not None:
            _log(f"stage rules={n_rules}: salvaged partial results")
        return rec
    if r.returncode != 0:
        _log(f"stage rules={n_rules} failed rc={r.returncode}")
        return None
    rec = _last_json_line(r.stdout)
    if rec is None:
        # Distinguish "child reported an error record" from "no JSON
        # at all" in the log; either way the stage yields nothing.
        if '"error"' in (r.stdout or ""):
            _log(f"stage rules={n_rules} reported an error")
        else:
            _log(f"stage rules={n_rules} produced no JSON")
    return rec


def _env_budget() -> float:
    try:
        return float(os.environ.get("SENTINEL_BENCH_BUDGET_S", 1080))
    except ValueError:
        return 1080.0


PARTIAL_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_partial.jsonl")


def _stage_done(out: dict, label: str) -> None:
    """Append a completed stage's JSON to BENCH_partial.jsonl so a
    mid-run wedge still leaves every finished stage's hardware data on
    disk (round-3 lesson: the round's only TPU numbers died in a
    wedged process)."""
    rec = {"stage": label, "t": round(time.time(), 1), **out}
    try:
        with open(PARTIAL_PATH, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError as exc:  # never let bookkeeping kill the bench
        _log(f"could not append partial record: {exc}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget-s", type=float, default=_env_budget())
    ap.add_argument("--platform", default="tpu",
                    help="platform every stage must run on (cpu: pin the CPU)")
    ap.add_argument(
        "--gate", action="store_true",
        help="after the run, compare against the newest committed "
             "BENCH_*.json with the same device_kind+jax_version "
             "(tools/benchgate.py) and exit non-zero on regression",
    )
    ap.add_argument("--run-stage", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--kind", default="kernel", help=argparse.SUPPRESS)
    ap.add_argument("--rules", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--entries", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--iters", type=int, default=10, help=argparse.SUPPRESS)
    ap.add_argument("--child-platform", default="cpu", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.run_stage:
        _child_main(args)
        return

    platform = args.platform
    # Fresh partial file per run: interleaved records from different
    # runs are indistinguishable to consumers.
    try:
        open(PARTIAL_PATH, "w").close()
    except OSError:
        pass
    deadline = time.monotonic() + args.budget_s

    def spawn(n_rules, n_entries, iters, plat, timeout_s, kind="kernel"):
        out = _spawn_stage(n_rules, n_entries, iters, plat, timeout_s, kind=kind)
        if out is not None:
            _stage_done(out, f"{kind}:{n_rules}x{n_entries}")
        return out

    best: dict | None = None
    ladder = CPU_LADDER if platform == "cpu" else LADDER
    for n_rules, n_entries, iters in ladder:
        remaining = deadline - time.monotonic()
        if remaining < 30 or (best is not None and remaining < 90):
            _log(f"skipping rules={n_rules}: only {remaining:.0f}s of budget left")
            break
        # Cap per-stage time so one wedged stage can't eat the whole
        # budget.
        timeout_s = remaining if platform == "cpu" else min(remaining, 240.0)
        out = spawn(n_rules, n_entries, iters, platform, timeout_s)
        if out is None:
            break
        best = out

    # Secondary metrics (merged into the one JSON line): the mixed
    # slot-chain workload and the engine-level deferred path.
    if best is not None:
        # The mixed/engine kernels are the biggest compiles in the repo:
        # killing one mid-compile loses the stage. So on hardware each
        # stage is only attempted with enough headroom to finish, never
        # with a scrap of leftover budget.
        min_mixed = 90.0 if platform == "cpu" else 330.0
        min_engine = 45.0 if platform == "cpu" else 330.0
        remaining = deadline - time.monotonic()
        # Reserve the engine stage's floor when both still fit; when
        # they don't, the mixed chain (the headline verdict metric)
        # gets the room and the engine skip is logged. Either way a
        # stage's actual timeout is NEVER below its floor — a
        # sub-floor spawn is exactly the kill-mid-compile case.
        mixed_t = min(remaining - min_engine, 420.0)
        if mixed_t < min_mixed:
            mixed_t = min(remaining - 45, 420.0)
        if mixed_t >= min_mixed:
            mr, me = (
                ((1 << 20), (1 << 17)) if platform != "cpu" else ((1 << 14), (1 << 13))
            )
            mixed = spawn(mr, me, 5, platform, mixed_t, kind="mixed")
            if mixed:
                best.update(mixed)
        else:
            _log(f"skipping mixed stage: {remaining:.0f}s left gives timeout "
                 f"{mixed_t:.0f}s < {min_mixed:.0f}s floor")
        remaining = deadline - time.monotonic()
        # Reserve the speculative stage's floor the same way the mixed
        # stage reserves the engine's: it is small (one 64-op shape
        # compile) but it is the per-request latency headline.
        min_spec = 40.0 if platform == "cpu" else 240.0
        engine_t = min(remaining - 15 - min_spec, 420.0)
        if engine_t < min_engine:
            engine_t = min(remaining - 15, 420.0)
        if engine_t >= min_engine:
            engine = spawn(1024, 8192, 3, platform, engine_t, kind="engine")
            if engine:
                best.update(engine)
        else:
            _log(f"skipping engine stage: {remaining:.0f}s left gives timeout "
                 f"{engine_t:.0f}s < {min_engine:.0f}s floor")
        remaining = deadline - time.monotonic()
        # Reserve the sketch stage's floor like the engine stage
        # reserves the speculative's.
        min_sketch = 40.0 if platform == "cpu" else 240.0
        spec_t = min(remaining - 10 - min_sketch, 300.0)
        if spec_t < min_spec:
            spec_t = min(remaining - 10, 300.0)
        if spec_t >= min_spec:
            spec = spawn(64, 4096, 3, platform, spec_t, kind="speculative")
            if spec:
                best.update(spec)
        else:
            _log(f"skipping speculative stage: {remaining:.0f}s left gives "
                 f"timeout {spec_t:.0f}s < {min_spec:.0f}s floor")
        remaining = deadline - time.monotonic()
        # Reserve the adapters stage's floor like the speculative stage
        # reserves the sketch's.
        min_adapters = 90.0 if platform == "cpu" else 240.0
        sketch_t = min(remaining - 10 - min_adapters, 300.0)
        if sketch_t < min_sketch:
            sketch_t = min(remaining - 10, 300.0)
        if sketch_t >= min_sketch:
            sketch = spawn(64, 8192, 3, platform, sketch_t, kind="sketch")
            if sketch:
                best.update(sketch)
        else:
            _log(f"skipping sketch stage: {remaining:.0f}s left gives "
                 f"timeout {sketch_t:.0f}s < {min_sketch:.0f}s floor")
        remaining = deadline - time.monotonic()
        # Reserve the autotune stage's floor like the sketch stage
        # reserves the adapters'.
        min_autotune = 60.0 if platform == "cpu" else 240.0
        adapters_t = min(remaining - 10 - min_autotune, 300.0)
        if adapters_t < min_adapters:
            adapters_t = min(remaining - 10, 300.0)
        if adapters_t >= min_adapters:
            adapters = spawn(
                64, 2048, 3, platform, adapters_t, kind="adapters"
            )
            if adapters:
                best.update(adapters)
        else:
            _log(f"skipping adapters stage: {remaining:.0f}s left gives "
                 f"timeout {adapters_t:.0f}s < {min_adapters:.0f}s floor")
        remaining = deadline - time.monotonic()
        # Reserve the ipc stage's floor like the adapters stage
        # reserves the autotune's.
        min_ipc = 60.0 if platform == "cpu" else 240.0
        autotune_t = min(remaining - 10 - min_ipc, 300.0)
        if autotune_t < min_autotune:
            autotune_t = min(remaining - 10, 300.0)
        if autotune_t >= min_autotune:
            att = spawn(
                64, 8192, 3, platform, autotune_t, kind="autotune"
            )
            if att:
                best.update(att)
        else:
            _log(f"skipping autotune stage: {remaining:.0f}s left gives "
                 f"timeout {autotune_t:.0f}s < {min_autotune:.0f}s floor")
        remaining = deadline - time.monotonic()
        # Reserve the cluster stage's floor like the autotune stage
        # reserves the ipc's. The cluster stage is pure host TCP — no
        # device compile — so its floor is small even on hardware.
        min_cluster = 45.0
        ipc_t = min(remaining - 10 - min_cluster, 300.0)
        if ipc_t < min_ipc:
            ipc_t = min(remaining - 10, 300.0)
        if ipc_t >= min_ipc:
            ipc = spawn(8, 16384, 3, platform, ipc_t, kind="ipc")
            if ipc:
                best.update(ipc)
        else:
            _log(f"skipping ipc stage: {remaining:.0f}s left gives "
                 f"timeout {ipc_t:.0f}s < {min_ipc:.0f}s floor")
        remaining = deadline - time.monotonic()
        cluster_t = min(remaining - 10, 120.0)
        if cluster_t >= min_cluster:
            cl = spawn(1, 8192, 1, platform, cluster_t, kind="cluster")
            if cl:
                best.update(cl)
        else:
            _log(f"skipping cluster stage: {remaining:.0f}s left gives "
                 f"timeout {cluster_t:.0f}s < {min_cluster:.0f}s floor")

    if best is None:
        _emit(
            {
                "metric": "batched_entry_checks_per_sec_per_chip",
                "value": 0.0,
                "unit": "entries/sec",
                "vs_baseline": 0.0,
                "error": "no ladder stage completed (backend unavailable or budget exhausted)",
            }
        )
        sys.exit(1)  # nothing measured
    _emit(best)
    if args.gate:
        # Regression gate against the committed BENCH trajectory
        # (tools/benchgate.py): report on stderr — the one-JSON-line
        # stdout contract above must survive a gated run.
        sys.path.insert(
            0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools")
        )
        import contextlib

        import benchgate

        with contextlib.redirect_stdout(sys.stderr):
            rc = benchgate.gate(
                best, os.path.dirname(os.path.abspath(__file__))
            )
        if rc:
            sys.exit(rc)


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except BaseException as exc:
        if "--run-stage" in sys.argv:
            raise  # children must fail loudly (rc != 0) for the parent
        # Parent: the ONE-JSON-line contract holds even here.
        _emit(
            {
                "metric": "batched_entry_checks_per_sec_per_chip",
                "value": 0.0,
                "unit": "entries/sec",
                "vs_baseline": 0.0,
                "error": f"bench crashed: {type(exc).__name__}: {exc}",
            }
        )
        sys.exit(1)
