"""Merge per-process fleet span journals into ONE Perfetto timeline.

tools/tracedump.py shows one engine's flush pipeline; this tool shows
the FLEET: every process's span journal (metrics/spans.py) — ingest
workers, the engine, cluster token shards — merged into a single
Chrome trace-event JSON where one admission is a chain of flow arrows
across process boundaries:

    worker admit ──s──▶ engine frame          (matched on wid + seq)
    client rpc   ──s──▶ shard serve           (matched on port + xid)

Track layout: one Perfetto process per journal (named
``sentinel-<role>``, pid = the real OS pid), one thread per span
category inside it (worker / engine / client / shard) — so an engine
process that also hosts the cluster client shows both tracks. Each
journal's spans are shifted by its recorded ``ruler_off_ms`` (local
clock minus the ipc control header's wall-ms ruler at the last beat
observed), landing every process on the shared ruler timeline.

Usage::

    # Merge journals spilled by a real run (workers/engine/shards
    # spill on close; or hit the `spans` command with &spill=1):
    python tools/fleetdump.py --out fleet.json /path/*-spans-*.jsonl

    # Self-contained demo: spawn 2 ingest workers + 2 token shards
    # around this process's engine, spill all journals, merge:
    python tools/fleetdump.py --demo --out fleet.json [--platform cpu]

    # Demo + hard checks (ci_check.sh stage): all three process-type
    # track families present, flow arrows cross both boundaries:
    python tools/fleetdump.py --smoke --out fleet.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Stable thread ordering inside each process: request flow reads
# top-to-bottom (worker joins -> engine drains -> client RPCs out ->
# shard serves) even when categories share a journal.
_CAT_ORDER = ("worker", "engine", "client", "shard")


def _cat_tid(cat: str) -> int:
    try:
        return _CAT_ORDER.index(cat) + 1
    except ValueError:
        return len(_CAT_ORDER) + 1


def merge_journals(journals) -> dict:
    """[{"meta": ..., "spans": [...]}] -> Chrome trace-event object.

    Spans become ``X`` slices (ts/dur in µs); cross-process admissions
    and RPCs become ``s``/``f`` flow-arrow pairs. Event mechanics
    (emit-once metadata, the backwards-arrow clamp) come from
    metrics/perfetto.py's :class:`TraceBuilder`; this function owns
    only the fleet-specific span matching."""
    from sentinel_tpu.metrics.perfetto import TraceBuilder

    tb = TraceBuilder()
    admits = []   # (ts_us, pid, tid, wid, seq, trace_id)
    frames = []   # (ts_us, pid, tid, wid, seq_lo, seq_hi)
    rpcs = []     # (ts_us, pid, tid, port, xid)
    serves = {}   # (port, xid) -> (ts_us, pid, tid)

    for i, j in enumerate(journals):
        meta = j.get("meta") or {}
        spans = j.get("spans") or []
        role = str(meta.get("role", "proc"))
        pid = int(meta.get("pid", 0) or (100 + i))
        off_ms = float(meta.get("ruler_off_ms", 0.0) or 0.0)
        tb.process(f"sentinel-{role}", pid=pid)
        for sp in spans:
            cat = str(sp.get("cat", role))
            tid = tb.thread(pid, cat, tid=_cat_tid(cat))
            ts = int(round((float(sp["t0"]) - off_ms) * 1000.0))
            dur = max(1, int(round(float(sp.get("dur", 0.0)) * 1000.0)))
            args = {
                k: v for k, v in sp.items()
                if k not in ("name", "cat", "t0", "dur")
            }
            tb.slice(pid, tid, sp["name"], ts, dur, cat=cat, args=args)
            name = sp["name"]
            if cat == "worker" and name in ("admit", "admit.bulk"):
                if "wid" in sp and "seq" in sp:
                    admits.append((ts, pid, tid, int(sp["wid"]),
                                   int(sp["seq"]), sp.get("trace")))
            elif cat == "engine" and name == "frame":
                frames.append((ts, pid, tid, int(sp.get("wid", -1)),
                               int(sp.get("seq_lo", 0)),
                               int(sp.get("seq_hi", -1))))
            elif cat == "client" and name == "rpc":
                rpcs.append((ts, pid, tid,
                             int(sp.get("port", 0)), int(sp.get("xid", 0))))
            elif cat == "shard" and name == "serve":
                key = (int(sp.get("port", 0)), int(sp.get("xid", 0)))
                serves[key] = (ts, pid, tid)

    # Admission arrows: the worker's admit span into the engine frame
    # that carried its seq. seq is monotone per worker, so at most one
    # frame matches.
    for ts, pid, tid, wid, seq, trace_id in admits:
        for f_ts, f_pid, f_tid, f_wid, lo, hi in frames:
            if f_wid == wid and lo <= seq <= hi:
                fid = str(trace_id) if trace_id else f"adm-{wid}-{seq}"
                tb.flow(fid, "admission", (ts, pid, tid),
                        (f_ts, f_pid, f_tid), cat="fleet")
                break
    # RPC arrows: the client frame into the shard that served its xid
    # (xids count per client connection; the port disambiguates).
    for ts, pid, tid, port, xid in rpcs:
        hit = serves.get((port, xid))
        if hit is not None:
            tb.flow(f"rpc-{port}-{xid}", "rpc", (ts, pid, tid), hit,
                    cat="fleet")

    return tb.build()


def merge_files(paths) -> dict:
    from sentinel_tpu.metrics.spans import load_journal

    return merge_journals([load_journal(p) for p in sorted(paths)])


# ---- demo: a real spawned fleet -----------------------------------------
#
# multiprocessing spawn children import these by module name, so they
# must stay top-level (same contract as tests/ipc_procs.py).

DEMO_FLOWS = (9101, 9102, 9103, 9104)


def _demo_cfg(spans_dir: str) -> dict:
    from sentinel_tpu.utils.config import SentinelConfig

    return {
        SentinelConfig.SPANS_ENABLED: "true",
        SentinelConfig.SPANS_DIR: spans_dir,
    }


def _worker_child(channel, wid, cfg, n, q):
    """Spawned ingest worker: n entries + one bulk against the shared
    rings; the journal spills on close."""
    from sentinel_tpu.utils.backend import force_cpu
    from sentinel_tpu.utils.config import config

    force_cpu()  # the parent's engine holds the chip

    for k, v in cfg.items():
        config.set(k, v)
    from sentinel_tpu.ipc.worker import IngestClient

    cli = IngestClient(channel, wid)
    try:
        admitted = 0
        for _ in range(n):
            v = cli.entry("fleet-res", timeout_ms=60000)
            admitted += int(v.admitted)
        a, _r, _w, _f = cli.bulk("fleet-res", 8)
        q.put(("done", wid, admitted + int(a.sum())))
    finally:
        cli.close()


def _shard_child(cfg, flow_ids, q, stop_evt):
    """Spawned token shard: a real TCP SentinelTokenServer with the
    demo's cluster flow rules loaded; journal spills on stop."""
    from sentinel_tpu.utils.backend import force_cpu
    from sentinel_tpu.utils.config import config

    force_cpu()  # the parent's engine holds the chip

    for k, v in cfg.items():
        config.set(k, v)
    from sentinel_tpu.cluster import (
        cluster_flow_rule_manager,
        cluster_server_config_manager,
    )
    from sentinel_tpu.cluster.server import SentinelTokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.models import constants as C
    from sentinel_tpu.models.rules import ClusterFlowConfig, FlowRule

    cluster_server_config_manager.load_global_flow_config(
        exceed_count=1.0, max_allowed_qps=1e12
    )
    cluster_flow_rule_manager.load_rules(
        "default",
        [FlowRule(
            "fleet%d" % f, count=1e9, cluster_mode=True,
            cluster_config=ClusterFlowConfig(
                flow_id=f, threshold_type=C.FLOW_THRESHOLD_GLOBAL,
            ),
        ) for f in flow_ids],
    )
    srv = SentinelTokenServer(port=0, service=DefaultTokenService()).start()
    q.put(srv.port)
    stop_evt.wait(timeout=120)
    srv.stop()


def run_demo(out_path: str, spans_dir=None, entries: int = 12) -> dict:
    """2 spawned workers + this process's engine + 2 spawned token
    shards, spans armed everywhere; every journal spilled and merged
    to ``out_path``. Returns the trace object."""
    from sentinel_tpu.cluster.client import ClusterTokenClient
    from sentinel_tpu.ipc.plane import IngestPlane
    from sentinel_tpu.metrics import spans as spans_mod
    from sentinel_tpu.models.rules import FlowRule
    from sentinel_tpu.runtime.engine import Engine
    from sentinel_tpu.utils.config import config

    own_dir = spans_dir is None
    if own_dir:
        spans_dir = tempfile.mkdtemp(prefix="fleetdump-")
    cfg = _demo_cfg(spans_dir)
    saved = {k: config.get(k) for k in cfg}
    for k, v in cfg.items():
        config.set(k, v)
    spans_mod.reset_journal()  # re-arm this process's journal

    eng = Engine(initial_rows=1024)
    eng.set_flow_rules([FlowRule(resource="fleet-res", count=1e9)])
    plane = IngestPlane(eng)
    ctx = plane.spawn_context()
    procs, shard_stops, shard_ports = [], [], []
    try:
        for _ in range(2):
            q, stop = ctx.Queue(), ctx.Event()
            p = ctx.Process(
                target=_shard_child,
                args=(cfg, list(DEMO_FLOWS), q, stop), daemon=True,
            )
            p.start()
            procs.append(p)
            shard_stops.append(stop)
            shard_ports.append(q.get(timeout=60))

        worker_qs = []
        for wid in range(2):
            q = ctx.Queue()
            p = ctx.Process(
                target=_worker_child,
                args=(plane.channel(wid), wid, cfg, entries, q),
                daemon=True,
            )
            p.start()
            procs.append(p)
            worker_qs.append(q)
        for q in worker_qs:
            tag, _wid, _n = q.get(timeout=120)
            assert tag == "done"

        # The cluster-client leg lives in THIS (engine) process — its
        # rpc spans land on the engine journal's "client" track.
        for port in shard_ports:
            cli = ClusterTokenClient(
                port=port, request_timeout_sec=5.0,
                reconnect_interval_sec=0.2,
            ).start()
            try:
                for _ in range(3):
                    cli.request_tokens_batch(
                        [(f, 1, False) for f in DEMO_FLOWS]
                    )
            finally:
                cli.stop()
    finally:
        for stop in shard_stops:
            stop.set()
        deadline = time.monotonic() + 15
        for p in procs:
            p.join(timeout=max(0.1, deadline - time.monotonic()))
            if p.is_alive():
                p.terminate()
        plane.close()  # spills the engine/client journal
        eng.close()
        for k, v in saved.items():
            config.set(k, v if v is not None else config.DEFAULTS.get(k, ""))
        spans_mod.reset_journal()

    paths = glob.glob(os.path.join(spans_dir, "*-spans-*.jsonl"))
    trace = merge_files(paths)
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(trace, f)
    return trace


def smoke_checks(trace: dict) -> list:
    """The ci_check.sh assertions; returns failure strings (empty =
    green)."""
    evs = trace.get("traceEvents", [])
    cats = {e.get("cat") for e in evs if e.get("ph") == "X"}
    fails = []
    for want in ("worker", "engine", "shard"):
        if want not in cats:
            fails.append(f"no '{want}' track family in merged trace")
    procs = {e["pid"] for e in evs if e.get("ph") == "M"
             and e.get("name") == "process_name"}
    if len(procs) < 5:  # 2 workers + engine + 2 shards
        fails.append(f"expected >=5 processes, merged {len(procs)}")
    adm = sum(1 for e in evs if e.get("ph") == "s"
              and e.get("name") == "admission")
    rpc = sum(1 for e in evs if e.get("ph") == "s" and e.get("name") == "rpc")
    if adm == 0:
        fails.append("no worker->engine admission flow arrows")
    if rpc == 0:
        fails.append("no client->shard rpc flow arrows")
    n_s = sum(1 for e in evs if e.get("ph") == "s")
    n_f = sum(1 for e in evs if e.get("ph") == "f")
    if n_s != n_f:
        fails.append(f"unbalanced flow arrows: {n_s} starts, {n_f} finishes")
    return fails


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("journals", nargs="*",
                    help="spilled *-spans-*.jsonl files to merge")
    ap.add_argument("--out", default="fleet.json")
    ap.add_argument("--demo", action="store_true",
                    help="spawn a 2-worker/1-engine/2-shard fleet")
    ap.add_argument("--smoke", action="store_true",
                    help="demo + hard checks (nonzero exit on failure)")
    ap.add_argument("--entries", type=int, default=12)
    ap.add_argument("--platform", default=None,
                    help="JAX platform override (e.g. cpu)")
    args = ap.parse_args()
    if args.platform:
        os.environ.setdefault("JAX_PLATFORMS", args.platform)
    if args.journals:
        trace = merge_files(args.journals)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(trace, f)
    elif args.demo or args.smoke:
        trace = run_demo(args.out, entries=args.entries)
    else:
        ap.error("give journal files or --demo/--smoke")
        return 2
    evs = trace["traceEvents"]
    n_x = sum(1 for e in evs if e.get("ph") == "X")
    n_s = sum(1 for e in evs if e.get("ph") == "s")
    procs = {e["pid"] for e in evs if e.get("name") == "process_name"}
    print(f"[fleetdump] wrote {args.out}: {len(procs)} processes, "
          f"{n_x} spans, {n_s} flow arrows — load at "
          "https://ui.perfetto.dev")
    if args.smoke:
        fails = smoke_checks(trace)
        for f in fails:
            print(f"[fleetdump] FAIL {f}")
        if fails:
            return 1
        print("[fleetdump] smoke all green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
