"""Bisect the k=2 slot-width cliff on TPU (PERF_NOTES.md round 3).

Round-3 measurement: the flags-off kernel stage at n=131072 entries ran
0.14 ms with k=1 slots but 392 ms with k=2 (second slot all -1) — a
2800x jump for doubling the flat [n*k] width, while CPU shows +8%. This
probe times each suspect in isolation so the cliff can be attributed:

  sortP   lax.sort with P operands over the [n*k] flat slots
  admis   flow_admission alone, k=1 vs k=2
  flush   flush_step_jit (flags off), k=1 vs k=2
  stats   the metric-array batched window update alone
  seg     the segment cumsum/cummax rank math alone
  sketch  the sketch-tier count-min/candidate fold alone (2 widths)

Run: python tools/k2probe.py [--platform cpu] [--n 131072]
Each stage prints one line; a final JSON summary goes to stdout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _time(fn, *args, iters=5, warmup=2, **kw):
    """Compile + ``warmup`` extra executions before timing: per-dispatch
    overhead can linger past the first execution and skew a comparison
    of two variants (PERF_NOTES 'probe-order warm-up')."""
    import jax

    for _ in range(1 + warmup):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--n", type=int, default=131072)
    ap.add_argument("--rules", type=int, default=1 << 20)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument(
        "--seed-out", default=None, metavar="FILE",
        help="measure per-shape closed-vs-scan param-path flush timings"
             " and write a sentinel.tpu.autotune.param.seed.file JSON"
             " (the ParamPathMemo then starts committed instead of"
             " exploring)",
    )
    args = ap.parse_args()
    from sentinel_tpu.utils.backend import (
        enable_compile_cache,
        force_cpu,
        require_platform,
    )

    if args.platform == "cpu":
        force_cpu()
    require_platform(args.platform)
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from __graft_entry__ import _example_batch
    from sentinel_tpu.metrics.nodes import make_stats
    from sentinel_tpu.rules.degrade_table import DegradeIndex
    from sentinel_tpu.rules.flow_table import FlowRuleDynState, FlowTableDevice
    from sentinel_tpu.rules.param_table import make_param_state
    from sentinel_tpu.runtime import flush as F
    from sentinel_tpu.runtime.flush import SystemDevice, flush_step_jit

    n, nr = args.n, args.rules
    results: dict[str, float] = {"platform": jax.default_backend(), "n": n}
    rng = np.random.default_rng(0)

    def report(name: str, dt: float) -> None:
        results[name] = round(dt * 1e3, 4)
        print(f"[k2probe] {name}: {dt * 1e3:.3f} ms", file=sys.stderr, flush=True)
        # Incremental partial line after every stage: a run cut short
        # still leaves every finished stage's number.
        print(json.dumps(results), file=sys.stderr, flush=True)

    # --- the headline cliff FIRST: full flags-off kernel + admission ---
    # (most valuable number if the run is cut short)
    dev = FlowTableDevice(
        grade=jnp.ones(nr, dtype=jnp.int32),
        count=jnp.full(nr, 20.0, dtype=jnp.float32),
        behavior=jnp.zeros(nr, dtype=jnp.int32),
        max_queueing_time_ms=jnp.zeros(nr, dtype=jnp.int32),
        cost1_ms=jnp.full(nr, 50, dtype=jnp.int32),
        warmup_warning_token=jnp.zeros(nr, dtype=jnp.int32),
        warmup_max_token=jnp.zeros(nr, dtype=jnp.int32),
        warmup_slope=jnp.zeros(nr, dtype=jnp.float32),
        warmup_refill_threshold=jnp.zeros(nr, dtype=jnp.int32),
    )
    dindex = DegradeIndex([])
    inf = float("inf")
    sysdev = SystemDevice(
        qps=jnp.float32(inf), max_thread=jnp.float32(inf), max_rt=jnp.float32(inf),
        load_threshold=jnp.float32(-1.0), cpu_threshold=jnp.float32(-1.0),
        cur_load=jnp.float32(-1.0), cur_cpu=jnp.float32(-1.0),
    )
    flags = dict(
        with_occupy=False, with_system=False, with_degrade=False, with_exits=False
    )
    stats = make_stats(nr)
    # Clean warmed A/B: compile + warm BOTH k's fully before timing
    # either, so neither absorbs cold-connection dispatch overhead (the
    # round-4 confound where the first-run k read slower).
    flush_states = {}
    for k in (1, 2):
        batch = _example_batch(n, nr, nr, k)
        s = {
            "batch": batch,
            "st": make_stats(nr),
            "dyn": FlowRuleDynState(
                latest_passed_time=jnp.full(nr, -(10**9), dtype=jnp.int32),
                stored_tokens=jnp.zeros(nr, dtype=jnp.float32),
                last_filled_time=jnp.full(nr, -(10**9), dtype=jnp.int32),
            ),
            "ddyn": dindex.make_dyn_state(),
            "pdyn": make_param_state(8),
        }
        flush_states[k] = s

    def _flush_once(s):
        out = flush_step_jit(
            s["st"], dev, s["dyn"], dindex.device, s["ddyn"], s["pdyn"],
            sysdev, s["batch"], **flags
        )
        s["st"], s["dyn"], s["ddyn"], s["pdyn"], _sk, res = out
        return res

    for k in (1, 2):
        t0 = time.perf_counter()
        jax.block_until_ready(_flush_once(flush_states[k]).admitted)
        dt = time.perf_counter() - t0
        # Into results, not just stderr: a wedge during the (long) k=2
        # compile must still leave a salvageable partial line.
        report(f"flush_k{k}_compile", dt)  # report() renders ms
        print(f"[k2probe] flush_k{k} compile+first {dt:.1f}s",
              file=sys.stderr, flush=True)
        for _ in range(2):  # extra warm executions per k
            jax.block_until_ready(_flush_once(flush_states[k]).admitted)
    print(json.dumps(results), flush=True)  # partial: warm phase done
    for k in (1, 2):
        t0 = time.perf_counter()
        for _ in range(args.iters):
            res = _flush_once(flush_states[k])
        jax.block_until_ready(res.admitted)
        report(f"flush_k{k}", (time.perf_counter() - t0) / args.iters)

    admis = jax.jit(
        lambda stats, dev, batch: F.flow_admission(
            stats, dev, batch, with_occupy=False
        )
    )
    admis_batches = {k: flush_states[k]["batch"] for k in (1, 2)}
    for k in (1, 2):  # warm both before timing either
        jax.block_until_ready(admis(stats, dev, admis_batches[k]))
        jax.block_until_ready(admis(stats, dev, admis_batches[k]))
    for k in (1, 2):
        report(
            f"admis_k{k}",
            _time(admis, stats, dev, admis_batches[k], iters=args.iters, warmup=0),
        )
    # Both k's device states are no longer needed; holding them through
    # the sort/seg/stats stages would pin ~2 extra StatsStates of HBM.
    del flush_states, admis_batches

    # --- sync vs pipelined engine flush (depth-K dispatch overlap) ----
    # The same bulk window through Engine.flush() at pipeline depth 0
    # (dispatch + fetch per flush) vs depth 2 (fetch deferred, one
    # coalesced device_get per drain): the gap is the per-flush fetch
    # round trip the pipeline hides. Warm both
    # depths fully before timing either (probe-order warm-up).
    try:
        from sentinel_tpu.models.rules import FlowRule
        from sentinel_tpu.runtime.engine import Engine

        eng = Engine(initial_rows=4096)
        eng.set_flow_rules([FlowRule(resource=f"p{i}", count=1e9)
                            for i in range(64)])
        pipe_n = min(n, 1 << 14)

        def _window(depth):
            eng.pipeline_depth = depth
            for i in range(8):
                eng.submit_bulk(f"p{i}", pipe_n // 8)
            eng.flush()
            eng.drain()

        for depth in (0, 2):  # warm both before timing either
            _window(depth)
            _window(depth)
        for depth in (0, 2):
            eng.pipeline_depth = depth
            t0 = time.perf_counter()
            for _ in range(args.iters):
                for i in range(8):
                    eng.submit_bulk(f"p{i}", pipe_n // 8)
                eng.flush()
            eng.drain()
            report(
                f"engine_flush_depth{depth}",
                (time.perf_counter() - t0) / args.iters,
            )
        eng.pipeline_depth = 0
    except Exception as exc:  # engine drift — report, keep probing
        print(f"[k2probe] engine pipeline stage skipped: {exc}",
              file=sys.stderr)

    # --- speculative single-entry admission (host fast tier) ----------
    # entry_sync with the speculative tier on: the verdict comes from
    # the host mirror, no device round-trip on the timed path (the
    # settle flush runs between timed batches). p50/p99 wall per entry
    # — the sub-100 µs per-request story vs engine_flush_depth0's
    # multi-ms device round-trip.
    try:
        from sentinel_tpu.models.rules import FlowRule
        from sentinel_tpu.runtime.engine import Engine
        from sentinel_tpu.utils.config import config as _cfg

        _cfg.set(_cfg.SPECULATIVE_ENABLED, "true")
        _cfg.set(_cfg.SPECULATIVE_FLUSH_BATCH, "100000")
        try:
            seng = Engine(initial_rows=1024)
            seng.set_flow_rules(
                [FlowRule(resource=f"s{i}", count=1e9) for i in range(8)]
            )
            for i in range(64):
                seng.entry_sync(f"s{i % 8}")
            seng.flush()  # warm settle shape
            lats = []
            for r in range(args.iters):
                for i in range(512):
                    t0 = time.perf_counter()
                    seng.entry_sync(f"s{i % 8}")
                    lats.append(time.perf_counter() - t0)
                seng.flush()  # settle + reconcile between timed batches
            seng.drain()
            lats.sort()
            report("spec_entry_p50", lats[len(lats) // 2])
            report("spec_entry_p99", lats[int(len(lats) * 0.99)])

            # System gate overhead (PR 7): the same host fast path with
            # a wide-open system rule configured — the delta vs
            # spec_entry_* is the gate's per-entry cost.
            from sentinel_tpu.models import constants as _C
            from sentinel_tpu.rules.system_manager import SystemConfig

            seng.set_system_config(SystemConfig(qps=1e12))
            lats = []
            for r in range(args.iters):
                for i in range(512):
                    t0 = time.perf_counter()
                    seng.entry_sync(f"s{i % 8}", entry_type=_C.EntryType.IN)
                    lats.append(time.perf_counter() - t0)
                seng.flush()
            seng.drain()
            seng.set_system_config(None)
            lats.sort()
            report("spec_entry_sys_p50", lats[len(lats) // 2])
            report("spec_entry_sys_p99", lats[int(len(lats) * 0.99)])

            # Ingest shed fast path (PR 7): verdict latency when the
            # valve sheds — the under-saturation floor.
            from sentinel_tpu.runtime.ingest import IngestValve

            _cfg.set(_cfg.INGEST_DEADLINE_MS, "1")
            seng.ingest = IngestValve(seng)
            seng.ingest.force_latency_ms(1000.0)
            lats = []
            for i in range(2048):
                t0 = time.perf_counter()
                seng.entry_sync(f"s{i % 8}")
                lats.append(time.perf_counter() - t0)
            _cfg.set(_cfg.INGEST_DEADLINE_MS, "0")
            seng.ingest = IngestValve(seng)
            lats.sort()
            report("shed_entry_p50", lats[len(lats) // 2])
            report("shed_entry_p99", lats[int(len(lats) * 0.99)])
        finally:
            _cfg.set(_cfg.SPECULATIVE_ENABLED, "false")
            _cfg.set(_cfg.INGEST_DEADLINE_MS, "0")
    except Exception as exc:
        print(f"[k2probe] speculative stage skipped: {exc}", file=sys.stderr)

    # --- param path closed-vs-scan shape sweep (--seed-out) ------------
    # Times the SAME closed-form-eligible param batch through both
    # arms of the autotuner's cost memo (engine.param_force_path pins
    # the pick) at the memo's own bucket axes — (pow2 rows, ts
    # segments) — and emits the seed file ParamPathMemo.seed() loads at
    # engine start (sentinel.tpu.autotune.param.seed.file).
    try:
        from sentinel_tpu.models.rules import ParamFlowRule
        from sentinel_tpu.runtime.autotune import ParamPathMemo
        from sentinel_tpu.runtime.engine import Engine

        peng = Engine(initial_rows=1024)
        peng.set_param_rules(
            {"pp": [ParamFlowRule(resource="pp", param_idx=0, count=1e9)]}
        )
        seed_buckets = []
        shapes = [(256, 1), (256, 2), (2048, 1), (2048, 2), (2048, 4)]

        def _param_flush(n_items: int, nseg: int) -> None:
            base = peng.clock.now_ms()
            ts_col = np.asarray(
                [base + (i % nseg) for i in range(n_items)], dtype=np.int64
            )
            peng.submit_bulk(
                "pp", n_items, ts=ts_col - base,
                args_column=[(f"v{i % 64}",) for i in range(n_items)],
            )
            peng.flush()
            peng.drain()

        for n_items, nseg in shapes:
            timings = {}
            for path in ("closed", "scan"):
                peng.param_force_path = path
                _param_flush(n_items, nseg)  # warm/compile this arm
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    _param_flush(n_items, nseg)
                timings[path] = (
                    (time.perf_counter() - t0) / args.iters * 1e3
                )
            peng.param_force_path = None
            bucket = ParamPathMemo.bucket_of(n_items, nseg)
            seed_buckets.append(
                {
                    "rows_bucket": bucket[0],
                    "segments": bucket[1],
                    "closed_ms": round(timings["closed"], 4),
                    "scan_ms": round(timings["scan"], 4),
                }
            )
            report(f"param_closed_n{n_items}_s{nseg}", timings["closed"] / 1e3)
            report(f"param_scan_n{n_items}_s{nseg}", timings["scan"] / 1e3)
        peng.close()
        if args.seed_out:
            seed = {
                "format": "sentinel-param-seed-v1",
                "platform": results["platform"],
                "jax_version": jax.__version__,
                "buckets": seed_buckets,
            }
            with open(args.seed_out, "w", encoding="utf-8") as f:
                json.dump(seed, f, indent=1)
            print(f"[k2probe] seed file written: {args.seed_out}",
                  file=sys.stderr, flush=True)
    except Exception as exc:
        print(f"[k2probe] param-path stage skipped: {exc}", file=sys.stderr)

    # --- ipc plane round trip (sentinel_tpu/ipc) -----------------------
    # One in-process worker client against a live plane: entry()
    # shared-memory round-trip latency (frame encode -> ring -> plane
    # decode -> columnar submit -> verdict frame back), the per-request
    # cost a GIL-bound front-end worker pays to ride the one engine.
    try:
        from sentinel_tpu.ipc.plane import IngestPlane
        from sentinel_tpu.ipc.worker import IngestClient
        from sentinel_tpu.models.rules import FlowRule
        from sentinel_tpu.runtime.engine import Engine
        from sentinel_tpu.utils.config import config as _cfg

        _cfg.set(_cfg.SPECULATIVE_ENABLED, "true")
        _cfg.set(_cfg.SPECULATIVE_FLUSH_BATCH, "100000")
        try:
            ieng = Engine(initial_rows=1024)
            ieng.set_flow_rules(
                [FlowRule(resource=f"i{i}", count=1e9) for i in range(8)]
            )
            plane = IngestPlane(ieng)
            cli = IngestClient(plane.channel(0), 0)
            for i in range(64):  # warm the settle shape + intern tables
                cli.entry(f"i{i % 8}")
            lats = []
            for _ in range(args.iters):
                for i in range(256):
                    t0 = time.perf_counter()
                    cli.entry(f"i{i % 8}")
                    lats.append(time.perf_counter() - t0)
                ieng.flush()
            lats.sort()
            report("ipc_entry_p50", lats[len(lats) // 2])
            report("ipc_entry_p99", lats[int(len(lats) * 0.99)])
            cli.close()
            plane.close()

            # Adaptive wakeups (spin-then-park ring waits) on the same
            # engine: the round-trip floor without the two sleep-poll
            # wake quanta. Fresh plane — doorbells exist only when it
            # is built under wakeup=adaptive.
            _cfg.set(_cfg.IPC_WAKEUP, "adaptive")
            try:
                plane = IngestPlane(ieng)
                cli = IngestClient(plane.channel(0), 0)
                for i in range(64):
                    cli.entry(f"i{i % 8}")
                lats = []
                for _ in range(args.iters):
                    for i in range(256):
                        t0 = time.perf_counter()
                        cli.entry(f"i{i % 8}")
                        lats.append(time.perf_counter() - t0)
                    ieng.flush()
                lats.sort()
                report("ipc_entry_adaptive_p50", lats[len(lats) // 2])
                report("ipc_entry_adaptive_p99",
                       lats[int(len(lats) * 0.99)])
                cli.close()
                plane.close()
            finally:
                _cfg.set(_cfg.IPC_WAKEUP, "sleep")
            ieng.close()
        finally:
            _cfg.set(_cfg.SPECULATIVE_ENABLED, "false")
    except Exception as exc:
        print(f"[k2probe] ipc stage skipped: {exc}", file=sys.stderr)

    # --- fleet span stamp cost (metrics/spans.py) ----------------------
    # One armed SpanJournal.record() as the admission call sites issue
    # it — two wall_ms reads bracketing the span plus the dict build +
    # locked ring append — and the disabled path's single bool read.
    # Reported in ns per stamp (not ms): these are the numbers the
    # ≤2% armed-overhead budget is built from, far below report()'s
    # ms resolution.
    try:
        from sentinel_tpu.metrics.spans import SpanJournal
        from sentinel_tpu.metrics.spans import wall_ms as _wms

        spj = SpanJournal(role="probe", enabled=True, ring=8192,
                          spill_every=0)
        n_st = 20000

        def _stamp(i: int) -> None:
            t0s = _wms()
            spj.record("probe", "worker", t0s, _wms() - t0s,
                       wid=0, seq=i, push_ms=0.01, v=t0s, win=1, adm=1)

        for i in range(2048):  # warm the deque + dict shapes
            _stamp(i)
        t0 = time.perf_counter()
        for i in range(n_st):
            _stamp(i)
        armed_ns = (time.perf_counter() - t0) / n_st * 1e9
        results["span_stamp_ns"] = round(armed_ns, 1)
        print(f"[k2probe] span_stamp_ns: {armed_ns:.0f} ns",
              file=sys.stderr, flush=True)

        spj.enabled = False
        t0 = time.perf_counter()
        for i in range(n_st):
            if spj.enabled:
                _stamp(i)
        off_ns = (time.perf_counter() - t0) / n_st * 1e9
        results["span_disabled_ns"] = round(off_ns, 2)
        print(f"[k2probe] span_disabled_ns: {off_ns:.1f} ns",
              file=sys.stderr, flush=True)
        print(json.dumps(results), file=sys.stderr, flush=True)
    except Exception as exc:
        print(f"[k2probe] span stage skipped: {exc}", file=sys.stderr)

    # --- flight-recorder hook cost (runtime/capture.py) ----------------
    # The two numbers the capture ≤2%-armed budget is built from: the
    # ARMED per-flush hook cost (note_chunk columnar spill + the
    # note_verdicts fill, measured by wrapping the real hooks inside a
    # real bulk flush loop — reported in ms/flush and ns/row) and the
    # DISABLED path, which is one attribute-is-None read per flush.
    try:
        import shutil
        import tempfile

        from sentinel_tpu.models.rules import FlowRule
        from sentinel_tpu.runtime.capture import CaptureJournal
        from sentinel_tpu.runtime.engine import Engine

        cap_tmp = tempfile.mkdtemp(prefix="k2probe-cap-")
        ceng = Engine()
        ceng.set_flow_rules(
            [FlowRule(f"cap{i}", count=1e9) for i in range(16)]
        )
        cap = CaptureJournal(ceng, directory=cap_tmp)
        cap.segment_bytes = 1 << 30
        ceng.capture = cap
        hook_s = [0.0]
        orig_chunk, orig_verd = cap.note_chunk, cap.note_verdicts

        def _timed_chunk(*a, **kw):
            t0 = time.perf_counter()
            try:
                return orig_chunk(*a, **kw)
            finally:
                hook_s[0] += time.perf_counter() - t0

        def _timed_verd(*a, **kw):
            t0 = time.perf_counter()
            try:
                return orig_verd(*a, **kw)
            finally:
                hook_s[0] += time.perf_counter() - t0

        cap.note_chunk = _timed_chunk
        cap.note_verdicts = _timed_verd
        cap_rows = 16 * 1024

        def _cap_win():
            for i in range(16):
                ceng.submit_bulk(f"cap{i}", 1024)
            ceng.flush()
            ceng.drain()

        _cap_win()  # warm: interning + kernel shape + first segment
        hook_s[0] = 0.0
        n_fl = 10
        for _ in range(n_fl):
            _cap_win()
        armed_ms = hook_s[0] / n_fl * 1e3
        results["capture_hook_ms_per_flush"] = round(armed_ms, 3)
        results["capture_hook_ns_per_row"] = round(
            hook_s[0] / (n_fl * cap_rows) * 1e9, 1
        )
        print(
            f"[k2probe] capture_hook_ms_per_flush: {armed_ms:.3f} ms"
            f" ({results['capture_hook_ns_per_row']:.0f} ns/row)",
            file=sys.stderr, flush=True,
        )
        cap.note_chunk, cap.note_verdicts = orig_chunk, orig_verd
        cap.close()
        ceng.capture = None
        n_ck = 200000
        t0 = time.perf_counter()
        for _ in range(n_ck):
            if ceng.capture is not None:
                _cap_win()  # never taken
        off_ns = (time.perf_counter() - t0) / n_ck * 1e9
        results["capture_disabled_ns"] = round(off_ns, 2)
        print(f"[k2probe] capture_disabled_ns: {off_ns:.1f} ns",
              file=sys.stderr, flush=True)
        print(json.dumps(results), file=sys.stderr, flush=True)
        ceng.close()
        shutil.rmtree(cap_tmp, ignore_errors=True)
    except Exception as exc:
        print(f"[k2probe] capture stage skipped: {exc}", file=sys.stderr)

    # --- cluster token plane round trips (sentinel_tpu/cluster) --------
    # One real TCP server on loopback: the three wire stances a token
    # decision can take — per-call frame, 8-row batch frame (cost shown
    # PER DECISION), and a local lease admit (zero frames). The spread
    # between the three is the whole argument for the batched plane.
    try:
        from sentinel_tpu.cluster import (
            cluster_flow_rule_manager as _cfrm,
            cluster_server_config_manager as _cscm,
        )
        from sentinel_tpu.cluster.client import ClusterTokenClient
        from sentinel_tpu.cluster.server import SentinelTokenServer
        from sentinel_tpu.cluster.token_service import DefaultTokenService
        from sentinel_tpu.models import constants as CC
        from sentinel_tpu.models.rules import ClusterFlowConfig, FlowRule
        from sentinel_tpu.utils.config import config as _ccfg

        _cfrm.clear()
        _cscm.load_global_flow_config(exceed_count=1.0, max_allowed_qps=1e12)
        _cfrm.load_rules(
            "default",
            [FlowRule(
                "k2c", count=1e9, cluster_mode=True,
                cluster_config=ClusterFlowConfig(
                    flow_id=77,
                    threshold_type=CC.FLOW_THRESHOLD_GLOBAL,
                ),
            )],
        )
        csrv = SentinelTokenServer(port=0, service=DefaultTokenService())
        csrv.start()
        try:
            ccli = ClusterTokenClient("127.0.0.1", csrv.port).start()
            try:
                for _ in range(32):  # warm the connection + service row
                    ccli.request_token(77, 1)
                n_rt = 256
                lats = []
                for _ in range(args.iters):
                    for _ in range(n_rt):
                        t0 = time.perf_counter()
                        ccli.request_token(77, 1)
                        lats.append(time.perf_counter() - t0)
                lats.sort()
                report("cluster_percall_p50", lats[len(lats) // 2])
                report("cluster_percall_p99", lats[int(len(lats) * 0.99)])

                rows8 = [(77, 1, 0)] * 8
                lats = []
                for _ in range(args.iters):
                    for _ in range(n_rt // 8):
                        t0 = time.perf_counter()
                        ccli.request_tokens_batch(rows8)
                        lats.append((time.perf_counter() - t0) / 8)
                lats.sort()
                report("cluster_batch8_per_decision_p50",
                       lats[len(lats) // 2])

                # Lease admit: plant a lease by hand (the client-side
                # admit path is what's being timed, not the grant).
                _ccfg.set(_ccfg.CLUSTER_LEASE_ENABLED, "true")
                try:
                    ccli._store_leases([(77, n_rt * args.iters + 64, 60_000)])
                    lats = []
                    for _ in range(args.iters):
                        for _ in range(n_rt):
                            t0 = time.perf_counter()
                            ccli.request_token(77, 1)
                            lats.append(time.perf_counter() - t0)
                    lats.sort()
                    report("cluster_lease_admit_p50",
                           lats[len(lats) // 2])
                finally:
                    _ccfg.set(_ccfg.CLUSTER_LEASE_ENABLED, "false")
            finally:
                ccli.stop()
        finally:
            csrv.stop()
            _cfrm.clear()
    except Exception as exc:
        print(f"[k2probe] cluster stage skipped: {exc}", file=sys.stderr)

    # --- sharded token plane round trips (cluster/shards.py) -----------
    # The fan-out tax in isolation: a 32-row batch through 1/2/4 real
    # loopback shards, cost shown PER DECISION (routing split + M
    # concurrent frames + verdict reassembly vs one frame), plus the
    # pure hash-route cost per row. The single-shard row doubles as
    # the shards=1-is-PR-16 baseline.
    try:
        from sentinel_tpu.cluster import (
            cluster_flow_rule_manager as _cfrm,
            cluster_server_config_manager as _cscm,
        )
        from sentinel_tpu.cluster.server import SentinelTokenServer
        from sentinel_tpu.cluster.shards import (
            ShardMap, ShardedTokenClient, shard_of,
        )
        from sentinel_tpu.cluster.token_service import DefaultTokenService
        from sentinel_tpu.models import constants as CC
        from sentinel_tpu.models.rules import ClusterFlowConfig, FlowRule

        _cfrm.clear()
        _cscm.load_global_flow_config(exceed_count=1.0, max_allowed_qps=1e12)
        sh_flows = list(range(900, 932))
        _cfrm.load_rules(
            "default",
            [FlowRule(
                "k2s%d" % f, count=1e9, cluster_mode=True,
                cluster_config=ClusterFlowConfig(
                    flow_id=f, threshold_type=CC.FLOW_THRESHOLD_GLOBAL,
                ),
            ) for f in sh_flows],
        )
        rows32 = [(sh_flows[i % len(sh_flows)], 1, False) for i in range(32)]
        for n_sh in (1, 2, 4):
            srvs = [
                SentinelTokenServer(
                    port=0, service=DefaultTokenService()
                ).start()
                for _ in range(n_sh)
            ]
            scli = ShardedTokenClient(
                ShardMap(0, [("127.0.0.1", s.port) for s in srvs])
            ).start()
            try:
                for _ in range(8):  # warm every shard connection
                    scli.request_tokens_batch(rows32)
                lats = []
                for _ in range(args.iters):
                    for _ in range(32):
                        t0 = time.perf_counter()
                        scli.request_tokens_batch(rows32)
                        lats.append((time.perf_counter() - t0) / 32)
                lats.sort()
                report(
                    f"cluster_shard{n_sh}_batch_per_decision_p50",
                    lats[len(lats) // 2],
                )
            finally:
                scli.stop()
                for s in srvs:
                    s.stop()
        # Pure routing cost: the crc32 hash-partition per row.
        t0 = time.perf_counter()
        for _ in range(args.iters):
            for f in sh_flows * 8:
                shard_of(f, 4)
        report(
            "cluster_shard_route_per_row",
            (time.perf_counter() - t0) / (args.iters * len(sh_flows) * 8),
        )
        _cfrm.clear()
    except Exception as exc:
        print(f"[k2probe] cluster_shard stage skipped: {exc}",
              file=sys.stderr)

    # --- sketch-tier fold in isolation (runtime/sketch.py) -------------
    # The count-min + candidate merge over a pow2 key batch, jitted
    # standalone at two widths — the marginal device cost one armed
    # flush pays on top of the main kernel.
    try:
        from sentinel_tpu.runtime.sketch import (
            SketchBatch, make_sketch_state, sketch_fold,
        )

        sk_n = min(8192, n)
        ids = jnp.asarray(
            rng.integers(0, 2**31 - 1, sk_n).astype(np.int32)
        )
        w = jnp.ones((sk_n,), dtype=jnp.int32)
        for width in (2048, 16384):
            st = make_sketch_state(4, width, 64)
            fold = jax.jit(lambda s, i, ww: sketch_fold(
                s, SketchBatch(ids=i, w=ww)
            ))
            report(
                f"sketch_fold_w{width}",
                _time(fold, st, ids, w, iters=args.iters),
            )
    except Exception as exc:
        print(f"[k2probe] sketch stage skipped: {exc}", file=sys.stderr)

    # --- isolated sorts over the flat slot array -----------------------
    for k in (1, 2):
        size = n * k
        row_key = jnp.asarray(rng.integers(0, nr, size).astype(np.int32))
        ts = jnp.asarray(rng.integers(0, 400, size).astype(np.int32))
        eidx = jnp.arange(size, dtype=jnp.int32) // k
        pos = jnp.arange(size, dtype=jnp.int32)

        s4 = jax.jit(lambda a, b, c, d: jax.lax.sort((a, b, c, d), num_keys=3))
        s3 = jax.jit(lambda a, b, c: jax.lax.sort((a, b, c), num_keys=2))
        s2 = jax.jit(lambda a, b: jax.lax.sort((a, b), num_keys=1))
        s1 = jax.jit(lambda a: jax.lax.sort((a,), num_keys=1))
        report(f"sort4_k{k}", _time(s4, row_key, ts, eidx, pos, iters=args.iters))
        report(f"sort3_k{k}", _time(s3, row_key, ts, pos, iters=args.iters))
        report(f"sort2_k{k}", _time(s2, row_key, pos, iters=args.iters))
        report(f"sort1_k{k}", _time(s1, row_key, iters=args.iters))

    # --- segment rank math alone ---------------------------------------
    for k in (1, 2):
        size = n * k
        rk_s = jnp.sort(jnp.asarray(rng.integers(0, nr, size).astype(np.int32)))
        acq = jnp.ones(size, dtype=jnp.int32)

        @jax.jit
        def seg(rk_s, acq):
            ones = jnp.ones((1,), dtype=bool)
            new_grp = jnp.concatenate([ones, rk_s[1:] != rk_s[:-1]])
            return F.segment_excl_cumsum(new_grp, acq)

        report(f"seg_k{k}", _time(seg, rk_s, acq, iters=args.iters))

    # --- stats window update alone -------------------------------------
    from sentinel_tpu.metrics import metric_array as ma
    from sentinel_tpu.metrics.nodes import SECOND_CFG

    stats = make_stats(nr)
    for k in (1, 2):
        size = n * k
        rows = jnp.asarray(rng.integers(0, nr, size).astype(np.int32))
        ts = jnp.asarray(rng.integers(0, 400, size).astype(np.int32))
        deltas = jnp.ones((size, 1), dtype=jnp.int32) * jnp.ones(
            (1, F.NUM_EVENTS), dtype=jnp.int32
        )

        @jax.jit
        def upd(second, rows, ts, deltas):
            return ma.update(SECOND_CFG, second, rows, ts, deltas)

        try:
            report(
                f"stats_k{k}",
                _time(upd, stats.second, rows, ts, deltas, iters=args.iters),
            )
        except Exception as exc:  # signature drift — report, keep going
            print(f"[k2probe] stats_k{k} skipped: {exc}", file=sys.stderr)
            break

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
