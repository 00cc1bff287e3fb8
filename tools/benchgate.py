"""Bench regression gate: compare a fresh bench.py JSON against the
latest committed ``BENCH_*.json`` trajectory point.

ROADMAP's "hardware truth" item: the committed ``BENCH_*`` files are a
perf trajectory, and a trajectory without a gate is a scrapbook — a
regression lands silently and the next session inherits it as the new
normal. This tool turns the trajectory into a gate:

* the **baseline** is the newest committed ``BENCH_*.json`` whose
  ``device_kind`` AND ``jax_version`` match the fresh run's (PR-7's
  hardware-truth header). No comparable baseline — different silicon,
  different jax, or a pre-header record — is a **SKIP with a reason**,
  never a fake pass/fail: comparing a TPU run against CPU liveness
  numbers is exactly the mistake the header exists to prevent;
* each stage metric is compared only when its stage **context**
  (ladder rung sizes: n_rules/n_entries etc.) matches — a budget-
  truncated ladder must not read as a slowdown;
* every metric carries its own **tolerance band** (throughput is a lot
  steadier than a p99 on a busy 1-core box), scaled globally by
  ``--tolerance-scale``. A metric worse than baseline by more than its
  band is a regression → exit 1 with a per-metric report.

Committed baselines may be the raw bench JSON or the driver wrapper
``{"parsed": {...}}`` shape — both load.

Usage::

    python bench.py --gate                    # bench + gate in one go
    python bench.py > fresh.json
    python tools/benchgate.py --fresh fresh.json [--repo-root .]
                              [--baseline BENCH_r11.json]
                              [--tolerance-scale 1.0]

Exit status: 0 pass or skip-with-reason, 1 regression (or a fresh
record that is itself an error), 2 usage error. The programmatic
surface (``load_record`` / ``find_baseline`` / ``compare`` / ``gate``)
is what tests/test_benchgate.py asserts on.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# Direction per metric: "higher" = higher is better (throughput),
# "lower" = lower is better (latency). The band is the tolerated
# RELATIVE regression (0.60 = 60% worse than baseline still passes).
#
# Band sizing is empirical, from back-to-back CPU runs on the
# timeshared 1-core dev box (PR 8): throughputs swung up to 1.8x,
# mean sync latency 2.7x, percentile latencies 5x — pure tenancy
# noise, zero code change between the runs. The bands therefore catch
# ORDER-OF-MAGNITUDE regressions, which is the only gating a CPU
# liveness box honestly supports; on steady hardware (a real TPU run)
# tighten with ``--tolerance-scale 0.2``-ish. Too-loose-but-honest
# beats tight-but-flaky: a gate that cries wolf gets deleted.
STAGE_METRICS: Dict[str, Tuple[str, float]] = {
    "value": ("higher", 0.60),
    "flush_ms": ("lower", 2.00),
    "mixed_checks_per_sec": ("higher", 0.60),
    "mixed_flush_ms": ("lower", 2.00),
    "engine_ops_per_sec": ("higher", 0.60),
    "engine_bulk_ops_per_sec": ("higher", 0.60),
    "engine_adapter_ops_per_sec": ("higher", 0.60),
    "engine_pipelined_ops_per_sec": ("higher", 0.60),
    "engine_sync_latency_ms": ("lower", 2.00),
    # Flight-recorder arming cost (PR 19): same-run on/off median
    # ratios on the bulk loop — box noise cancels, so they get the
    # tight ratio band next to ipc_span_overhead.
    "engine_capture_overhead_d0": ("lower", 0.30),
    "engine_capture_overhead_d2": ("lower", 0.30),
    "spec_ops_per_sec": ("higher", 0.60),
    "spec_entry_p50_us": ("lower", 2.00),
    "spec_entry_p99_us": ("lower", 5.00),
    "spec_entry_sys_p50_us": ("lower", 2.00),
    "spec_entry_sys_p99_us": ("lower", 5.00),
    "shed_entry_p50_us": ("lower", 2.00),
    "shed_entry_p99_us": ("lower", 5.00),
    "sketch_ops_per_sec_on": ("higher", 0.60),
    "sketch_ops_per_sec_off": ("higher", 0.60),
    # Storm latency includes real decay-window waits, so box noise is
    # a smaller share — but keep the same latency-class band.
    "sketch_promote_storm_ms": ("lower", 2.00),
    # Adapter batch-window matrix (bench `adapters` stage). The spine
    # ratio is a RATIO of two same-run numbers, so box noise largely
    # cancels — it gets a tighter band than raw throughputs.
    "adapters_gateway_bulk_ops_per_sec": ("higher", 0.60),
    "adapters_spine_on_ops_per_sec": ("higher", 0.60),
    "adapters_spine_vs_bulk": ("higher", 0.30),
    "adapters_wsgi_on_ops_per_sec": ("higher", 0.60),
    "adapters_wsgi_off_ops_per_sec": ("higher", 0.60),
    "adapters_wsgi_on_p50_us": ("lower", 2.00),
    "adapters_wsgi_on_p99_us": ("lower", 5.00),
    "adapters_asgi_on_ops_per_sec": ("higher", 0.60),
    "adapters_asgi_off_ops_per_sec": ("higher", 0.60),
    "adapters_asgi_on_p50_us": ("lower", 2.00),
    "adapters_asgi_on_p99_us": ("lower", 5.00),
    "adapters_aiohttp_on_ops_per_sec": ("higher", 0.60),
    "adapters_aiohttp_off_ops_per_sec": ("higher", 0.60),
    "adapters_aiohttp_on_p50_us": ("lower", 2.00),
    "adapters_aiohttp_on_p99_us": ("lower", 5.00),
    "adapters_grpc_on_ops_per_sec": ("higher", 0.60),
    "adapters_grpc_off_ops_per_sec": ("higher", 0.60),
    "adapters_grpc_on_p50_us": ("lower", 2.00),
    "adapters_grpc_on_p99_us": ("lower", 5.00),
    "adapters_flask_on_ops_per_sec": ("higher", 0.60),
    "adapters_flask_off_ops_per_sec": ("higher", 0.60),
    "adapters_flask_on_p50_us": ("lower", 2.00),
    "adapters_flask_on_p99_us": ("lower", 5.00),
    "adapters_fastapi_on_ops_per_sec": ("higher", 0.60),
    "adapters_fastapi_off_ops_per_sec": ("higher", 0.60),
    "adapters_fastapi_on_p50_us": ("lower", 2.00),
    "adapters_fastapi_on_p99_us": ("lower", 5.00),
    # Self-tuning stage (bench `autotune`). The vs-static ratio is a
    # RATIO of two same-run numbers (box noise largely cancels), so it
    # gets the tighter ratio-class band like adapters_spine_vs_bulk.
    "autotune_static_best_ops_per_sec": ("higher", 0.60),
    "autotune_steady_ops_per_sec": ("higher", 0.60),
    "autotune_vs_static_best": ("higher", 0.30),
    # Multi-process ingest plane (bench `ipc` stage). The vs-inproc
    # ratio is a RATIO of two same-run numbers (box noise largely
    # cancels) — tighter band like the other ratio metrics; on the
    # 1-core box it measures transport overhead (3 processes share one
    # CPU), on real hardware it is the scale-out headline.
    "ipc_workers_ops_per_sec": ("higher", 0.60),
    "ipc_inproc_ops_per_sec": ("higher", 0.60),
    "ipc_vs_inproc": ("higher", 0.30),
    "ipc_entry_p50_us": ("lower", 2.00),
    "ipc_entry_p99_us": ("lower", 5.00),
    # IPC fast path (PR 14): the adaptive-wakeup A/B and the worker
    # concurrency sweep. Speedup/amortization are same-run RATIOS
    # (box noise cancels) — tighter bands; frames-per-entry is a pure
    # protocol count, the steadiest metric in the file.
    "ipc_entry_adaptive_p50_us": ("lower", 2.00),
    "ipc_entry_adaptive_p99_us": ("lower", 5.00),
    "ipc_wakeup_speedup": ("higher", 0.30),
    # Engine hot-restart outage (supervised kill -9 → device-served
    # again): dominated by process cold boot (JAX import + first
    # compile) + dead-ms detection + restart backoff, so it gets the
    # widest band the gate allows — its job is catching a recovery
    # that stops converging, not a ±second of import time.
    "ipc_restart_outage_ms": ("lower", 5.00),
    # Warm-standby takeover + planned handoff (PR 20). The standby
    # outage is detection + attach (cold boot is off the outage path)
    # but still rides process scheduling on a shared box; the handoff
    # gap includes the old world's drain + final durable spill; the
    # warm-boot column is a JAX import + first compile — all wall-clock
    # process-lifecycle numbers, so they keep the widest band. Their
    # job is catching a takeover that regresses to cold-boot-dominated,
    # not a ±second of import time.
    "ipc_standby_outage_ms": ("lower", 5.00),
    "ipc_handoff_outage_ms": ("lower", 5.00),
    "ipc_standby_warm_boot_ms": ("lower", 5.00),
    "ipc_percall_w1_ops_per_sec": ("higher", 0.60),
    "ipc_percall_w2_ops_per_sec": ("higher", 0.60),
    "ipc_percall_w4_ops_per_sec": ("higher", 0.60),
    "ipc_window_w1_ops_per_sec": ("higher", 0.60),
    "ipc_window_w2_ops_per_sec": ("higher", 0.60),
    "ipc_window_w4_ops_per_sec": ("higher", 0.60),
    "ipc_frames_per_entry_window": ("lower", 0.50),
    "ipc_window_amortization": ("higher", 0.30),
    # Batched cluster token plane (PR 16, bench `cluster` stage).
    # Frames-per-op and lease hit rate are protocol COUNTS (steadiest
    # class in the file); amortization is a same-run ratio. The lease
    # frames/op band is wide in relative terms because the absolute
    # number is tiny (~0.004) and one extra renewal frame doubles it.
    "cluster_percall_ops_per_sec": ("higher", 0.60),
    "cluster_window_ops_per_sec": ("higher", 0.60),
    "cluster_lease_ops_per_sec": ("higher", 0.60),
    "cluster_frames_per_op_window": ("lower", 0.50),
    "cluster_frames_per_op_lease": ("lower", 2.00),
    "cluster_lease_hit_rate": ("higher", 0.30),
    "cluster_window_amortization": ("higher", 0.30),
    # Sharded token plane (PR 17, bench `cluster` shard sweep).
    # Frames-per-op and lease hit rates are protocol COUNTS; the
    # capacity ratio and parallel-issue fraction are same-run ratios
    # (box noise cancels) — ratio-class bands. Per-shard capacity is
    # a busy-clock rate, steadier than wall throughput but still on a
    # shared box, so it keeps the throughput band.
    "cluster_shard1_window_ops_per_sec": ("higher", 0.60),
    "cluster_shard2_window_ops_per_sec": ("higher", 0.60),
    "cluster_shard4_window_ops_per_sec": ("higher", 0.60),
    "cluster_shard1_lease_ops_per_sec": ("higher", 0.60),
    "cluster_shard2_lease_ops_per_sec": ("higher", 0.60),
    "cluster_shard4_lease_ops_per_sec": ("higher", 0.60),
    "cluster_shard1_window_frames_per_op": ("lower", 0.50),
    "cluster_shard2_window_frames_per_op": ("lower", 0.50),
    "cluster_shard4_window_frames_per_op": ("lower", 0.50),
    "cluster_shard1_lease_hit_rate": ("higher", 0.30),
    "cluster_shard2_lease_hit_rate": ("higher", 0.30),
    "cluster_shard4_lease_hit_rate": ("higher", 0.30),
    "cluster_shard1_capacity_per_sec": ("higher", 0.60),
    "cluster_shard2_capacity_per_sec": ("higher", 0.60),
    "cluster_shard4_capacity_per_sec": ("higher", 0.60),
    "cluster_shard4_parallel_issue": ("higher", 0.30),
    "cluster_shard_capacity_ratio_4x": ("higher", 0.30),
    # Gossip merge cost: one merge_remote + fleet-view query, pure
    # numpy in-process — latency-class band.
    "cluster_gossip_merge_ms": ("lower", 2.00),
    # Fleet span decomposition (PR 18). The span-derived percentiles
    # get the latency-class bands; the armed/unarmed p50 ratio and the
    # wire share are same-run RATIOS (box noise cancels) — overhead
    # must stay near 1.0, so it gets the tight ratio band.
    "ipc_span_e2e_p50_us": ("lower", 2.00),
    "ipc_span_e2e_p99_us": ("lower", 5.00),
    "ipc_span_drain_p50_us": ("lower", 2.00),
    "ipc_span_overhead": ("lower", 0.30),
    "cluster_rpc_p50_ms": ("lower", 2.00),
    "cluster_rpc_p99_ms": ("lower", 5.00),
    "cluster_serve_p50_ms": ("lower", 2.00),
}

# Host-identity token (PR 14): device_kind + jax_version cannot tell
# two different-speed VMs apart (the r09→r10 re-anchor hole). When
# BOTH records carry the measured host token (bench._host_identity),
# the cpu count must match and the spin calibration must agree within
# this ratio band for the baseline to be comparable; records predating
# the token keep matching on the hardware header alone.
HOST_SPIN_BAND = 2.5

# Stage-context keys: a group's metrics are comparable only when every
# context key present in EITHER record matches (a missing stage on one
# side skips the group, a different rung size skips it too).
STAGE_CONTEXT: List[Tuple[Tuple[str, ...], Tuple[str, ...]]] = [
    (("n_rules", "n_entries"), ("value", "flush_ms")),
    (("mixed_n_rules", "mixed_n_entries"),
     ("mixed_checks_per_sec", "mixed_flush_ms")),
    (("engine_n_rules", "engine_n_ops"),
     ("engine_ops_per_sec", "engine_bulk_ops_per_sec",
      "engine_adapter_ops_per_sec", "engine_pipelined_ops_per_sec",
      "engine_sync_latency_ms",
      "engine_capture_overhead_d0", "engine_capture_overhead_d2")),
    ((), ("spec_ops_per_sec", "spec_entry_p50_us", "spec_entry_p99_us",
          "spec_entry_sys_p50_us", "spec_entry_sys_p99_us",
          "shed_entry_p50_us", "shed_entry_p99_us")),
    (("sketch_n_ops",),
     ("sketch_ops_per_sec_on", "sketch_ops_per_sec_off",
      "sketch_promote_storm_ms")),
    (("adapters_n_ops",),
     tuple(
         m for m in STAGE_METRICS if m.startswith("adapters_")
     )),
    (("autotune_n_ops",),
     ("autotune_static_best_ops_per_sec", "autotune_steady_ops_per_sec",
      "autotune_vs_static_best")),
    (("ipc_n_ops", "ipc_n_workers"),
     ("ipc_workers_ops_per_sec", "ipc_inproc_ops_per_sec",
      "ipc_vs_inproc", "ipc_entry_p50_us", "ipc_entry_p99_us",
      "ipc_entry_adaptive_p50_us", "ipc_entry_adaptive_p99_us",
      "ipc_wakeup_speedup", "ipc_restart_outage_ms",
      "ipc_standby_outage_ms", "ipc_handoff_outage_ms",
      "ipc_standby_warm_boot_ms",
      "ipc_span_e2e_p50_us", "ipc_span_e2e_p99_us",
      "ipc_span_drain_p50_us", "ipc_span_overhead")),
    # The sweep carries its own rung key so a truncated/smoke run
    # never reads as a slowdown (and pre-PR-14 baselines, which lack
    # both the key and the metrics, simply don't compare here).
    (("ipc_sweep_quota",),
     ("ipc_percall_w1_ops_per_sec", "ipc_percall_w2_ops_per_sec",
      "ipc_percall_w4_ops_per_sec", "ipc_window_w1_ops_per_sec",
      "ipc_window_w2_ops_per_sec", "ipc_window_w4_ops_per_sec",
      "ipc_frames_per_entry_window", "ipc_window_amortization")),
    # Batched cluster token plane (PR 16): keyed on its own op count
    # so smoke runs and pre-PR-16 baselines never compare here.
    (("cluster_n_ops",),
     ("cluster_percall_ops_per_sec", "cluster_window_ops_per_sec",
      "cluster_lease_ops_per_sec", "cluster_frames_per_op_window",
      "cluster_frames_per_op_lease", "cluster_lease_hit_rate",
      "cluster_window_amortization",
      "cluster_rpc_p50_ms", "cluster_rpc_p99_ms",
      "cluster_serve_p50_ms")),
    # Shard sweep (PR 17): keyed on its own rung size so truncated
    # runs and pre-PR-17 baselines never compare here.
    (("cluster_shard_ops",),
     tuple(
         m for m in STAGE_METRICS
         if m.startswith("cluster_shard") or m == "cluster_gossip_merge_ms"
     )),
]


def load_record(path_or_obj) -> Optional[dict]:
    """A bench record from a path (or an already-loaded object):
    unwraps the driver's ``{"parsed": {...}}`` wrapper shape; None when
    unreadable/not a dict."""
    obj = path_or_obj
    if isinstance(obj, str):
        try:
            with open(obj, "r", encoding="utf-8") as f:
                obj = json.load(f)
        except (OSError, ValueError):
            return None
    if not isinstance(obj, dict):
        return None
    parsed = obj.get("parsed")
    if isinstance(parsed, dict):
        obj = parsed
    return obj


def host_mismatch(fresh: dict, baseline: dict) -> Optional[str]:
    """A reason string when the two records' measured host-identity
    tokens (``host_cpu_count`` + ``host_spin_ms``) say DIFFERENT
    boxes, else None. Records missing the token (pre-PR-14) are never
    mismatched — the hardware header is then the only identity we
    have, which is exactly the r09→r10 hole this closes going
    forward."""
    f_cpu, b_cpu = fresh.get("host_cpu_count"), baseline.get("host_cpu_count")
    f_spin, b_spin = fresh.get("host_spin_ms"), baseline.get("host_spin_ms")
    if not isinstance(f_spin, (int, float)) or not isinstance(
        b_spin, (int, float)
    ) or f_spin <= 0 or b_spin <= 0:
        return None
    if (
        isinstance(f_cpu, int) and isinstance(b_cpu, int)
        and f_cpu > 0 and b_cpu > 0 and f_cpu != b_cpu
    ):
        return f"host cpu count differs ({b_cpu} vs {f_cpu})"
    ratio = f_spin / b_spin
    if ratio > HOST_SPIN_BAND or ratio < 1.0 / HOST_SPIN_BAND:
        return (
            f"host speed token differs ({b_spin:g} ms vs {f_spin:g} ms "
            f"spin calibration, {ratio:.2f}x, band {HOST_SPIN_BAND:g}x)"
        )
    return None


def find_baseline(
    repo_root: str, device_kind, jax_version, fresh: Optional[dict] = None
) -> Tuple[Optional[str], Optional[dict], str]:
    """Newest committed BENCH_*.json matching the fresh run's hardware
    header AND host-identity token: ``(path, record, reason)`` —
    path/record None when nothing comparable exists, with the reason
    spelled out."""
    if not device_kind or not jax_version:
        return None, None, "fresh record lacks device_kind/jax_version"
    paths = sorted(glob.glob(os.path.join(repo_root, "BENCH_*.json")))
    seen = 0
    host_skipped: List[str] = []
    for path in reversed(paths):
        rec = load_record(path)
        if rec is None or "error" in rec:
            continue
        seen += 1
        if (
            rec.get("device_kind") == device_kind
            and rec.get("jax_version") == jax_version
        ):
            why = host_mismatch(fresh or {}, rec)
            if why is not None:
                host_skipped.append(f"{os.path.basename(path)}: {why}")
                continue
            if host_skipped and not isinstance(
                rec.get("host_spin_ms"), (int, float)
            ):
                # A NEWER same-header baseline's token already said
                # "different box" — falling back to an older token-less
                # record would re-open exactly the cross-box comparison
                # the token refuses (the pre-token record carries no
                # evidence it came from this box either).
                host_skipped.append(
                    f"{os.path.basename(path)}: pre-token record behind "
                    "a token mismatch"
                )
                continue
            return path, rec, ""
    if not paths:
        return None, None, f"no BENCH_*.json baselines under {repo_root}"
    if host_skipped:
        return (
            None, None,
            "hardware header matches but the measured host-identity "
            "token does not — cross-box comparison refused ("
            + "; ".join(host_skipped) + ")",
        )
    return (
        None, None,
        f"no baseline among {seen} readable BENCH_*.json matches "
        f"device_kind={device_kind!r} jax_version={jax_version!r} "
        "(pre-header records never match)",
    )


def compare(
    fresh: dict, baseline: dict, tolerance_scale: float = 1.0
) -> Tuple[List[str], List[str], List[str]]:
    """``(regressions, compared, skipped)`` message lists. A metric is
    compared when both records carry it numerically and its stage
    context matches; regression means worse than baseline by more than
    ``band × tolerance_scale``."""
    regressions: List[str] = []
    compared: List[str] = []
    skipped: List[str] = []
    for ctx_keys, metrics in STAGE_CONTEXT:
        ctx_mismatch = None
        for k in ctx_keys:
            if k in fresh or k in baseline:
                if fresh.get(k) != baseline.get(k):
                    ctx_mismatch = (
                        f"{k}: fresh={fresh.get(k)} vs "
                        f"baseline={baseline.get(k)}"
                    )
                    break
        for m in metrics:
            f, b = fresh.get(m), baseline.get(m)
            if not isinstance(f, (int, float)) or not isinstance(b, (int, float)):
                continue  # stage absent on one side: silently not comparable
            if ctx_mismatch is not None:
                skipped.append(f"{m}: stage context differs ({ctx_mismatch})")
                continue
            if b <= 0:
                skipped.append(f"{m}: baseline value {b} not comparable")
                continue
            direction, band = STAGE_METRICS[m]
            band = band * tolerance_scale
            ratio = f / b
            if direction == "higher":
                bad = ratio < 1.0 - band
                word = "dropped"
            else:
                bad = ratio > 1.0 + band
                word = "rose"
            line = (
                f"{m}: {word if bad else 'ok'} {b:g} -> {f:g} "
                f"({ratio:.3f}x, band ±{band:.0%})"
            )
            (regressions if bad else compared).append(line)
    return regressions, compared, skipped


def gate(
    fresh: dict,
    repo_root: str,
    baseline_path: Optional[str] = None,
    tolerance_scale: float = 1.0,
) -> int:
    """Run the gate and print the report; returns the exit status."""
    if not isinstance(fresh, dict) or "error" in fresh:
        print(f"benchgate FAILED: fresh record is an error record: "
              f"{fresh.get('error') if isinstance(fresh, dict) else fresh!r}")
        return 1
    if baseline_path is not None:
        baseline = load_record(baseline_path)
        if baseline is None:
            print(f"benchgate usage error: cannot load {baseline_path}")
            return 2
        # An explicit baseline still honors the hardware-truth header
        # and the measured host-identity token.
        if (
            baseline.get("device_kind") != fresh.get("device_kind")
            or baseline.get("jax_version") != fresh.get("jax_version")
        ):
            print(
                "benchgate SKIP: explicit baseline "
                f"{os.path.basename(baseline_path)} has device_kind="
                f"{baseline.get('device_kind')!r}/jax="
                f"{baseline.get('jax_version')!r}, fresh has "
                f"{fresh.get('device_kind')!r}/{fresh.get('jax_version')!r}"
            )
            return 0
        host_why = host_mismatch(fresh, baseline)
        if host_why is not None:
            print(
                "benchgate SKIP: explicit baseline "
                f"{os.path.basename(baseline_path)} is a different box — "
                f"{host_why}"
            )
            return 0
    else:
        baseline_path, baseline, reason = find_baseline(
            repo_root, fresh.get("device_kind"), fresh.get("jax_version"),
            fresh=fresh,
        )
        if baseline is None:
            print(f"benchgate SKIP: {reason}")
            return 0
    regressions, compared, skipped = compare(fresh, baseline, tolerance_scale)
    base_name = os.path.basename(baseline_path)
    for line in skipped:
        print(f"  skip {line}")
    for line in compared:
        print(f"  {line}")
    if regressions:
        print(f"benchgate FAILED vs {base_name}:")
        for line in regressions:
            print(f"  REGRESSION {line}")
        return 1
    if not compared:
        print(f"benchgate SKIP: no comparable stage metrics vs {base_name}")
        return 0
    print(
        f"benchgate OK vs {base_name}: {len(compared)} metrics within "
        f"band ({len(skipped)} skipped)"
    )
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fresh", required=True,
                    help="fresh bench JSON path, or - for stdin")
    ap.add_argument("--repo-root",
                    default=os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))))
    ap.add_argument("--baseline", default=None,
                    help="explicit baseline path (default: newest "
                         "matching BENCH_*.json)")
    ap.add_argument("--tolerance-scale", type=float, default=1.0)
    args = ap.parse_args()
    if args.fresh == "-":
        try:
            fresh = load_record(json.load(sys.stdin))
        except ValueError:
            fresh = None
    else:
        fresh = load_record(args.fresh)
    if fresh is None:
        print(f"benchgate usage error: cannot load fresh record "
              f"{args.fresh}")
        return 2
    return gate(fresh, args.repo_root, args.baseline, args.tolerance_scale)


if __name__ == "__main__":
    sys.exit(main())
