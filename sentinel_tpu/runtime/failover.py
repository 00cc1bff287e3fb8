"""Device-failure domain: health state machine, host-fallback admission,
flush watchdog, checkpoint/restore.

Sentinel's whole point is that the protected system keeps answering when
a dependency misbehaves — and the reference already encodes the pattern
one layer up: cluster-mode rules fall back to local checking when the
token server fails (``cc.fallback_to_local_when_fail``, mirrored in
engine.py's ``_apply_cluster_checks``). This module is the same stance
applied to the engine's own most critical dependency, the device.
Keeping admission on-device is the perf thesis (data-plane admission à
la *Heavy-Hitter Detection Entirely in the Data Plane*, arXiv
1611.04825) — so losing the device must degrade admission QUALITY,
never availability.

Four pieces:

* a **health state machine** ``HEALTHY → DEGRADED → RECOVERING →
  HEALTHY``: any dispatch fault, fetch fault or watchdog timeout trips
  the engine DEGRADED, quarantines the in-flight flush queue (every
  affected op gets a policy verdict instead of a re-raised device
  exception), and routes subsequent flushes to the host fallback;
* a **flush watchdog**: with failover armed, kernel dispatch and the
  device→host fetch run on a waiter thread bounded by
  ``sentinel.tpu.failover.fetch.timeout.ms`` — a wedged
  ``jax.device_get`` times out and trips failover instead of stranding
  the pipeline (and every submitter behind the flush lock) forever.
  Time the waiter spends in JAX's first-use trace/lower/compile does
  not count against that bound (a cold compile is not a device fault);
  it has its own, ``COMPILE_TIMEOUT_MS``;
* a :class:`HostFallbackAdmitter` serving admission while DEGRADED from
  the already-compiled rule tables: host token buckets for QPS flow
  rules, live concurrency counters for THREAD rules, last-known breaker
  states (the engine's host mirror) for degrade rules, per-value token
  buckets for QPS hot-param rules — under a per-resource
  fail-open/fail-closed policy (``sentinel.tpu.failover.policy``,
  default fail-open like the reference's pass-on-fallback). Degraded
  verdicts carry distinct provenance (``Verdict.degraded``, reason
  ``BLOCK_FAILOVER`` for policy sheds, ``degraded`` marks on admission
  -trace records) so tracing and metrics can tell degraded admits from
  device admits;
* **checkpoint/restore**: every N flushes
  (``sentinel.tpu.failover.checkpoint.every``) the engine's device
  states ride the existing coalesced result fetch to the host as the
  last-good checkpoint; RECOVERING re-entry restores it (re-based
  through the same ``shift_ws`` timestamp machinery the ~22-day epoch
  rebase uses) and requires K consecutive successful probe flushes
  (``sentinel.tpu.failover.probe.flushes``) before going HEALTHY.

Everything is deterministic under ``testing/faults.FaultInjector``:
each transition above is unit-testable without a flaky device.

Config keys (all declared in utils/config.py)::

    sentinel.tpu.failover.enabled            default false (opt-in)
    sentinel.tpu.failover.fetch.timeout.ms   watchdog bound, default 5000
    sentinel.tpu.failover.policy             "open" | "closed" |
                                             "open,resA=closed,..."
    sentinel.tpu.failover.checkpoint.every   flushes per checkpoint (0 off)
    sentinel.tpu.failover.probe.flushes      K successes before HEALTHY
    sentinel.tpu.failover.retry.ms           min gap between auto recovery
                                             attempts (engine clock)

What the fallback approximates vs the device path: QPS windows restart
full (burst of one window allowed at degrade entry), THREAD gauges
restart at zero (pre-fault in-flight entries are not visible), breaker
states are frozen at the last observed mirror, shaping/occupy/system
checks and per-origin rows are not enforced, and statistics for the
degraded window are lost. Documented in ARCHITECTURE.md §"Failure
domains & degraded admission".
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sentinel_tpu.core import errors as E
from sentinel_tpu.metrics import nodes as _ncfg
from sentinel_tpu.models import constants as C
from sentinel_tpu.rules.degrade_table import OPEN as _BREAKER_OPEN
from sentinel_tpu.utils.config import config
from sentinel_tpu.utils.record_log import record_log

HEALTHY = "HEALTHY"
DEGRADED = "DEGRADED"
RECOVERING = "RECOVERING"

# Prometheus gauge encoding of the state (transport/prometheus.py).
HEALTH_GAUGE = {HEALTHY: 0, DEGRADED: 1, RECOVERING: 2}


def parse_policy(raw: str) -> Tuple[str, Dict[str, str]]:
    """``"open"`` / ``"closed"`` / ``"open,resA=closed,resB=open"`` —
    the first ``=``-less segment is the default; unknown modes fall
    back to open (never make a config typo an outage). The ONE home of
    the ``sentinel.tpu.failover.policy`` format, shared by the host
    fallback admitter and the ipc plane's control-header snapshot."""
    default = "open"
    by_res: Dict[str, str] = {}
    for seg in str(raw).split(","):
        seg = seg.strip()
        if not seg:
            continue
        if "=" in seg:
            res, _, mode = seg.partition("=")
            by_res[res.strip()] = (
                "closed" if mode.strip().lower() == "closed" else "open"
            )
        else:
            default = "closed" if seg.lower() == "closed" else "open"
    return default, by_res


def _dead_ref():
    """A weakref whose referent is already gone — marks a checkpoint
    component as not-restorable (the durable loader's analog of an
    index swap killing the live checkpoint's ref)."""

    class _T:
        pass

    o = _T()
    r = weakref.ref(o)
    del o
    return r


class DeviceFetchTimeout(RuntimeError):
    """The flush watchdog's verdict: a dispatch or device→host fetch
    exceeded ``sentinel.tpu.failover.fetch.timeout.ms``."""


@dataclass(slots=True)
class HealthEvent:
    """One state transition, for the ``health`` command / telemetry."""

    now_ms: int
    frm: str
    to: str
    reason: str

    def as_dict(self) -> dict:
        return {
            "now_ms": self.now_ms, "from": self.frm, "to": self.to,
            "reason": self.reason,
        }


@dataclass(slots=True)
class Checkpoint:
    """One host-resident snapshot of the engine's device states.

    ``states`` is the fetched host pytree ``(stats, flow_dyn,
    degrade_dyn, param_dyn, sketch)`` — ``sketch`` is the device
    SketchState or None when the tier is disarmed; the index weakrefs
    gate which components are still restorable — a rule reload swaps an
    index AND its dyn state shape, so a stale component restores as a
    fresh dyn state instead (the reference rebuilds fresh breakers per
    load anyway)."""

    seq: int
    now_ms: int
    epoch_wall_ms: int
    win_key: object  # SECOND_CFG at capture (a retune invalidates stats)
    findex_ref: object
    dindex_ref: object
    pindex_ref: object
    states: Optional[tuple] = None  # filled at fetch time
    # ParamIndex.values_snapshot() captured when the states materialize
    # (durable spills only): the value→row maps that give the spilled
    # param_dyn rows their meaning in a fresh process.
    param_values: Optional[dict] = None


class _TokenBucket:
    """Host token bucket approximating one QPS window: capacity =
    threshold per window, continuous refill at threshold/window. Starts
    full — degrade entry grants one window's burst, the same stance as
    a restarted reference node."""

    __slots__ = ("cap", "rate_ms", "tokens", "last_ms")

    def __init__(self, cap: float, window_ms: float, now_ms: int) -> None:
        self.cap = float(cap)
        self.rate_ms = self.cap / max(window_ms, 1.0)
        self.tokens = self.cap
        self.last_ms = now_ms

    def _refill(self, now_ms: int) -> None:
        if now_ms > self.last_ms:
            self.tokens = min(
                self.cap, self.tokens + (now_ms - self.last_ms) * self.rate_ms
            )
            self.last_ms = now_ms

    def try_take(self, now_ms: int, n: float) -> bool:
        self._refill(now_ms)
        if self.tokens >= n:
            self.tokens -= n
            return True
        return False

    def available(self, now_ms: int) -> float:
        self._refill(now_ms)
        return self.tokens

    def consume(self, n: float) -> None:
        self.tokens = max(0.0, self.tokens - n)


class _HostShaping:
    """Host mirror of ONE shaping-governed rule's controller state —
    the mutable record rules/shaping.mirror_shaping_decide evolves
    (``latest`` ≙ latestPassedTime, ``stored``/``lastfill`` ≙ the
    warm-up ramp) plus pass counters approximating the check node's
    windowed pass: ``passq(ts)`` is a true LeapArray-style rolling
    window at the live SECOND_CFG bucket width (a bucket is valid while
    ``ts - ws <= interval``, exactly metric_array._valid_mask), and
    ``pass_prev`` is the ALIGNED previous-1s bucket (the minute-array
    read previousPassQps consumes). The device counts the whole node,
    the mirror counts its own admits through this rule —
    reconciliation adopts the settled device columns at every drain."""

    __slots__ = (
        "rule", "info", "latest", "stored", "lastfill",
        "win", "pass_sec", "pass_cur", "pass_prev",
    )

    def __init__(self, rule, info) -> None:
        self.rule = rule
        self.info = info  # FlowIndex.mirror_shaping_info tuple
        # Same inits as FlowIndex.make_dyn_state: "infinitely past".
        self.latest = -(10**9)
        self.stored = 0.0
        self.lastfill = -(10**9)
        self.win: "deque[list]" = deque()  # [bucket_ws, count] rolling
        self.pass_sec: Optional[int] = None
        self.pass_cur = 0
        self.pass_prev = 0

    def roll_pass(self, ts: int) -> None:
        """Advance the aligned per-second pass buckets to ``ts``'s
        second — ``pass_prev`` mirrors previousPassQps (the exact
        previous 1s bucket; a gap leaves it 0, like the minute-array
        read)."""
        sec = ts - ts % 1000
        if self.pass_sec is None:
            self.pass_sec = sec
            return
        if sec > self.pass_sec:
            self.pass_prev = self.pass_cur if sec - self.pass_sec == 1000 else 0
            self.pass_cur = 0
            self.pass_sec = sec

    def note_pass(self, ts: int, n: int) -> None:
        self.roll_pass(ts)
        self.pass_cur += n
        # The rolling window feeds only the warm-up line's passQps;
        # pacer-only rules never read it, so never grow it (it would
        # otherwise accumulate one bucket per window_len forever).
        if self.info[1] == C.CONTROL_BEHAVIOR_RATE_LIMITER:
            return
        self._trim_win(ts)
        wlen = _ncfg.SECOND_CFG.window_len_ms
        ws = ts - ts % wlen
        if self.win and self.win[-1][0] == ws:
            self.win[-1][1] += n
        else:
            self.win.append([ws, n])

    def _trim_win(self, ts: int) -> None:
        interval = _ncfg.SECOND_CFG.interval_ms
        while self.win and ts - self.win[0][0] > interval:
            self.win.popleft()

    def passq(self, ts: int) -> int:
        """Windowed pass sum at ``ts`` — LeapArray validity (strict
        ``ts - ws > interval`` deprecates a bucket)."""
        self._trim_win(ts)
        return sum(c for _ws, c in self.win)


class _HostSystem:
    """Host mirror of the global system-protection inputs
    (SystemRuleManager.checkSystem against Constants.ENTRY_NODE): a
    token bucket for the global inbound QPS threshold, a live inbound
    concurrency counter, and per-second success/RT windows feeding the
    avg-RT and BBR checks. Load/CPU read the same
    utils/system_status.sampler the device path samples. All
    approximations are the PR-5 bucket stance (windows restart at the
    gate's first use; reconciliation clamps the QPS bucket on observed
    over-admits)."""

    __slots__ = (
        "bucket", "qps_cap", "threads", "sec",
        "succ_cur", "succ_prev", "rt_cur", "rt_prev",
        "minrt_cur", "minrt_prev",
    )

    def __init__(self) -> None:
        self.bucket: Optional[_TokenBucket] = None
        self.qps_cap = -1.0
        self.threads = 0
        self.sec: Optional[int] = None
        self.succ_cur = 0
        self.succ_prev = 0
        self.rt_cur = 0
        self.rt_prev = 0
        self.minrt_cur = _ncfg.SECOND_CFG.max_rt
        self.minrt_prev = _ncfg.SECOND_CFG.max_rt

    def roll(self, now_ms: int) -> None:
        sec = now_ms - now_ms % 1000
        if self.sec is None:
            self.sec = sec
            return
        if sec > self.sec:
            gap1 = sec - self.sec == 1000
            self.succ_prev = self.succ_cur if gap1 else 0
            self.rt_prev = self.rt_cur if gap1 else 0
            self.minrt_prev = (
                self.minrt_cur if gap1 else _ncfg.SECOND_CFG.max_rt
            )
            self.succ_cur = 0
            self.rt_cur = 0
            self.minrt_cur = _ncfg.SECOND_CFG.max_rt
            self.sec = sec

    def note_complete(
        self, now_ms: int, rt: int, count: int,
        min_rt: Optional[int] = None,
    ) -> None:
        """``rt`` is the group's RT SUM (the avg-RT window input);
        ``min_rt`` the group's per-exit minimum — a bulk group's sum
        must not pose as one sample or the BBR minRt inflates by the
        group size."""
        self.roll(now_ms)
        self.succ_cur += count
        self.rt_cur += rt
        sample = rt if min_rt is None else min_rt
        if count > 0 and sample < self.minrt_cur:
            self.minrt_cur = sample

    def release(self, n: int) -> None:
        self.threads = max(0, self.threads - n)


class HostFallbackAdmitter:
    """Serves admission from host state while the engine is DEGRADED.

    Stage order matches the device path's ATTRIBUTION order (custom
    veto → authority → param → flow → degrade — ``_fill_results`` also
    reports a custom veto ahead of the shared authority channel); an op
    blocked by an earlier stage does not consume later stages' tokens.

    Two lifecycles (PR 6): in the original, non-``persistent`` mode all
    state is scoped to ONE degraded window — ``begin()`` resets it, so
    recovery retires every approximation along with the window. In
    ``persistent`` mode (the mirror core of the speculative tier,
    runtime/speculative.py) the buckets/counters run continuously under
    HEALTHY and are reconciled against device truth at every drain;
    ``begin()`` then keeps them — a device trip is a zero-transition
    event — and only resets the degraded-window delta ledgers + re-reads
    the policy. The gauge-delta ledgers (``_exit_rows`` &c.) track ONLY
    ops the device never saw, so recording is gated on
    ``_track_deltas`` — true exactly between a trip and a successful
    recovery."""

    def __init__(self, engine, persistent: bool = False) -> None:
        self._engine = engine
        self.persistent = persistent
        # Delta recording is scoped to degraded windows: a persistent
        # mirror serves admits the device WILL settle while HEALTHY —
        # those must not be replayed into a restored checkpoint.
        self._track_deltas = not persistent
        self._lock = threading.Lock()
        # id(rule) -> (rule, bucket): the rule ref pins the object so a
        # freed rule's id cannot be reused under the same key.
        self._buckets: Dict[int, Tuple[object, _TokenBucket]] = {}
        # prow -> (slot rule, bucket) for QPS hot-param values.
        self._pbuckets: Dict[int, Tuple[object, _TokenBucket]] = {}
        # resource -> live concurrency admitted by THIS fallback window.
        self._threads: Dict[str, int] = {}
        # gid -> host shaping-controller mirror (rules/shaping.py
        # mirror_shaping_decide state); _shaping_src pins the FlowIndex
        # the gids belong to, so a drain's reconcile against a
        # different (reloaded) index snapshot is a no-op instead of
        # adopting another rule's columns.
        self.shaping_enabled = config.get_bool(config.SPECULATIVE_SHAPING, True)
        self._shaping: Dict[int, _HostShaping] = {}
        self._shaping_src: Optional[object] = None
        # Host system-protection gate (consulted when
        # engine.system_config is set; lazily built).
        self._sys: Optional[_HostSystem] = None
        # Device-gauge deltas observed while DEGRADED: node row →
        # count. ``_exit_rows`` are releases the device never saw (a
        # restored gauge would stay pinned without replaying them);
        # ``_admit_rows`` are THREAD admissions the fallback made (in
        # flight through recovery — their post-recovery exits would
        # drive an unseeded gauge negative, permanently under-enforcing
        # the limit). Recovery applies the NET per row: exits of
        # fallback-admitted entries cancel their own admits exactly.
        self._exit_rows: Dict[int, int] = {}
        self._exit_prows: Dict[int, int] = {}
        self._admit_rows: Dict[int, int] = {}
        self._admit_prows: Dict[int, int] = {}
        self._policy_default = "open"
        self._policy_by_resource: Dict[str, str] = {}

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def begin(self, now_ms: int) -> None:
        """Enter a degraded window: fresh buckets/counters (UNLESS
        persistent — the speculative mirror carries its continuously-
        reconciled state straight into the degraded window, the
        zero-transition contract), fresh delta ledgers, re-read the
        policy (it is runtime-settable)."""
        with self._lock:
            if not self.persistent:
                self._buckets.clear()
                self._pbuckets.clear()
                self._threads.clear()
                self._shaping.clear()
                self._sys = None
            self._exit_rows.clear()
            self._exit_prows.clear()
            self._admit_rows.clear()
            self._admit_prows.clear()
            self._track_deltas = True
            self._parse_policy(config.get(config.FAILOVER_POLICY) or "open")

    def end_degraded(self) -> None:
        """Recovery succeeded: stop delta tracking (persistent mirrors
        keep serving the speculative tier; non-persistent admitters
        simply go idle until the next ``begin``)."""
        with self._lock:
            if self.persistent:
                self._track_deltas = False

    def assert_live(self, resource: str, n: int) -> None:
        """Worker-reconnect re-assertion (ipc/plane.py): charge ``n``
        live admissions to the mirror's THREAD counter. A restarted
        engine's mirror starts empty, so the workers' re-asserted live
        sets are what makes the fast tier's concurrency headroom exact
        in the new world — their eventual exits release through the
        normal on_exit path."""
        if n <= 0:
            return
        with self._lock:
            self._threads[resource] = self._threads.get(resource, 0) + n

    def reset_world(self) -> None:
        """Fresh mirror world: buckets, counters, and delta ledgers all
        cleared, delta tracking back to its construction-time stance.
        The full-reset analog of a non-persistent ``begin()`` — owned
        here so 'what constitutes a fresh world' has one home."""
        with self._lock:
            self._buckets.clear()
            self._pbuckets.clear()
            self._threads.clear()
            self._shaping.clear()
            self._sys = None
            self._exit_rows.clear()
            self._exit_prows.clear()
            self._admit_rows.clear()
            self._admit_prows.clear()
            self._track_deltas = not self.persistent

    def _parse_policy(self, raw: str) -> None:
        self._policy_default, self._policy_by_resource = parse_policy(raw)

    def policy_for(self, resource: str) -> str:
        with self._lock:
            return self._policy_by_resource.get(resource, self._policy_default)

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    def _bucket_for(
        self, rule, now_ms: int, cap: Optional[float] = None,
        window_ms: float = 1000.0,
    ) -> _TokenBucket:
        key = id(rule)
        ent = self._buckets.get(key)
        if ent is None or ent[0] is not rule:
            ent = (
                rule,
                _TokenBucket(
                    float(rule.count) if cap is None else cap,
                    window_ms, now_ms,
                ),
            )
            self._buckets[key] = ent
        return ent[1]

    def _pbucket_for(self, ps, now_ms: int) -> _TokenBucket:
        ent = self._pbuckets.get(ps.prow)
        if ent is None:
            cap, window = ps.mirror_bucket()
            ent = (ps.rule, _TokenBucket(cap, window, now_ms))
            self._pbuckets[ps.prow] = ent
        return ent[1]

    def _shaping_for(self, findex, gid: int) -> Optional[_HostShaping]:
        """The host shaping-controller mirror for one gid; caller
        holds ``self._lock``. Keyed per FlowIndex — a different index's
        gids name different rules, so the table resets on first touch
        after a swap (invalidate_rule_mirrors also clears it)."""
        if self._shaping_src is not findex:
            self._shaping.clear()
            self._shaping_src = findex
        st = self._shaping.get(gid)
        if st is None:
            info = findex.mirror_shaping_info(gid)
            if info is None:
                return None
            st = self._shaping[gid] = _HostShaping(info[0], info)
        return st

    def _shaping_admit_locked(self, findex, op) -> Tuple[bool, int, object]:
        """Decide the op's shaping-governed slots on the host mirror:
        ``(ok, wait_ms, blocking_rule)``. Every shaping slot's state
        advances like the kernel's would (no early exit — the device
        advances each pacer independently, and a grant sticks even when
        a sibling slot later vetoes the entry)."""
        from sentinel_tpu.rules.shaping import mirror_shaping_decide

        sg = findex.shaping_gids
        ok_all, wait_all, bad_rule = True, 0, None
        for gid, _crow in op.slots:
            if gid not in sg:
                continue
            st = self._shaping_for(findex, gid)
            if st is None:
                continue
            st.roll_pass(op.ts)
            ok, wait = mirror_shaping_decide(st, st.info, op.ts, op.acquire)
            if not ok and ok_all:
                ok_all, bad_rule = False, st.rule
            if wait > wait_all:
                wait_all = wait
        return ok_all, wait_all, bad_rule

    def _shaping_note_pass_locked(self, findex, op) -> None:
        """Count one ADMITTED entry's acquire into its shaping rules'
        per-second pass mirrors (the warm-up line's passQps input —
        only finally-admitted traffic counts toward the node's pass
        window on the device)."""
        sg = findex.shaping_gids
        for gid, _crow in op.slots:
            if gid in sg:
                st = self._shaping.get(gid)
                if st is not None:
                    st.note_pass(op.ts, op.acquire)

    def reconcile_shaping(self, findex, latest, stored, lastfill) -> None:
        """Adopt the settled device shaping columns at a drain
        (runtime/speculative.py rides them on the coalesced fetch):
        ``latestPassedTime`` advances monotonically (the mirror may be
        legitimately AHEAD by its in-flight speculative grants — those
        ops are still riding toward the device, so regressing to the
        device value would re-grant their pacing slots); the warm-up
        ramp adopts the device pair whenever the device's sync is at
        least as recent. A reconcile against a superseded index
        snapshot is a no-op (gids would name the wrong rules)."""
        with self._lock:
            if self._shaping_src is not findex:
                return
            n = latest.shape[0]
            for gid, st in self._shaping.items():
                if gid >= n:
                    continue
                dl = int(latest[gid])
                if dl > st.latest:
                    st.latest = dl
                df = int(lastfill[gid])
                if df >= st.lastfill:
                    st.lastfill = df
                    st.stored = float(stored[gid])

    def _sys_state_locked(self, cfg, now_ms: int) -> _HostSystem:
        """The host system gate's state, (re)built lazily; caller holds
        ``self._lock``. The QPS bucket rebuilds when the effective
        threshold changes (a reload narrowed/widened the rule)."""
        s = self._sys
        if s is None:
            s = self._sys = _HostSystem()
        if cfg.qps >= 0 and (s.bucket is None or s.qps_cap != cfg.qps):
            # qps is a PER-SECOND rate on both planes (the kernel
            # divides its interval pass sum by interval_sec before
            # comparing) — so the bucket refills per 1000 ms even when
            # the window geometry is retuned to another interval.
            s.bucket = _TokenBucket(float(cfg.qps), 1000.0, now_ms)
            s.qps_cap = cfg.qps
        return s

    def _sys_check_locked(
        self, s: _HostSystem, cfg, now_ms: int, acquire: int
    ) -> Optional[str]:
        """First violated system dimension ("qps"/"thread"/"rt"/
        "load"/"cpu") or None — the reference's checkSystem order
        (SystemRuleManager.java:298-353), which the kernel's
        reverse-iteration sys_type assignment reproduces. Nothing is
        consumed here; the QPS charge and thread acquire land only on
        the op's FINAL admit (the device's pass stats count admitted
        entries only)."""
        if cfg.qps >= 0 and s.bucket is not None:
            if s.bucket.available(now_ms) < acquire:
                return "qps"
        if cfg.max_thread >= 0 and s.threads > cfg.max_thread:
            return "thread"
        s.roll(now_ms)
        return self._sys_check_scalar_locked(s, cfg)

    def _sys_check_scalar_locked(self, s: _HostSystem, cfg) -> Optional[str]:
        """The snapshot dimensions (rt / load / cpu) shared by the
        singles and bulk gates; caller holds ``self._lock`` and has
        rolled ``s`` to the current second."""
        from sentinel_tpu.utils.system_status import sampler

        if cfg.max_rt >= 0:
            succ = s.succ_cur + s.succ_prev
            if succ > 0 and (s.rt_cur + s.rt_prev) / succ > cfg.max_rt:
                return "rt"
        cur_load, cur_cpu = sampler.read()
        if cfg.highest_system_load >= 0 and cur_load > cfg.highest_system_load:
            # BBR (checkBbr): under high load, block unless
            # curThread <= maxSuccessQps * minRt / 1000 (or <= 1).
            max_sq = float(max(s.succ_cur, s.succ_prev))
            min_rt = float(min(s.minrt_cur, s.minrt_prev))
            if s.threads > 1 and s.threads > max_sq * min_rt / 1000.0:
                return "load"
        if cfg.highest_cpu_usage >= 0 and cur_cpu > cfg.highest_cpu_usage:
            return "cpu"
        return None

    def drain_sys_bucket(self) -> bool:
        """Settlement observed a system-QPS over-admit: empty the
        gate's bucket (the clamp contract of :meth:`drain_bucket`)."""
        with self._lock:
            s = self._sys
            if s is not None and s.bucket is not None:
                s.bucket.consume(s.bucket.tokens)
                return True
        return False

    def _breaker_open(self, d_gids: Sequence[int]) -> bool:
        """Last-known breaker verdict from the engine's host mirror
        (kept by the breaker-event machinery). An invalid mirror —
        never observed, or shape-stale after a reload — fails open."""
        if not d_gids:
            return False
        from sentinel_tpu.rules.degrade_table import mirror_any_open

        eng = self._engine
        with eng._breaker_mirror_lock:
            if not eng._breaker_mirror_valid:
                return False
            return mirror_any_open(eng._breaker_state_host, d_gids)

    @staticmethod
    def _rule_of(src_index, gid: int):
        try:
            return src_index.rule_of_gid(gid)
        except Exception:
            return None

    # ------------------------------------------------------------------
    # single-op admission
    # ------------------------------------------------------------------
    def admit(self, op, now_ms: int, apply_policy: bool = True,
              degraded: bool = True, speculative: bool = False):
        """Host verdict for one op; never raises. Provenance is the
        caller's: the degraded fill marks ``degraded=True`` (the PR 5
        contract), the speculative tier marks ``speculative=True`` and
        ``degraded`` only while the engine actually is. The fail-open/
        closed policy is a DEGRADED concept — the healthy speculative
        tier passes ``apply_policy=False``."""
        from sentinel_tpu.runtime.engine import Verdict

        def blocked(reason, rule=None, slot_name="", limit_type=""):
            return Verdict(
                admitted=False, reason=reason, wait_ms=0, blocked_rule=rule,
                limit_type=limit_type, slot_name=slot_name,
                degraded=degraded, speculative=speculative,
            )

        if apply_policy and self.policy_for(op.resource) == "closed":
            return blocked(E.BLOCK_FAILOVER)
        if op.custom_veto is not None:
            slot, veto = op.custom_veto
            return blocked(
                E.BLOCK_CUSTOM,
                veto if veto is not True else None,
                getattr(slot, "name", "") or type(slot).__name__,
            )
        if not op.auth_ok:
            return blocked(
                E.BLOCK_AUTHORITY,
                self._engine.authority_rules.get(op.resource),
            )
        if op.cluster_blocked_rule is not None:
            # The token server's verdict predates the device fault and
            # stays binding (same attribution as the device fill).
            rule = op.cluster_blocked_rule
            reason = (
                E.BLOCK_PARAM
                if type(rule).__name__ == "ParamFlowRule"
                else E.BLOCK_FLOW
            )
            return blocked(reason, rule)
        findex = op.src[0] if op.src is not None else self._engine.flow_index
        sys_cfg = self._engine.system_config
        is_in = op.rows is not None and op.rows[3] >= 0
        with self._lock:
            # --- system protection (SystemSlot order: after authority,
            # before param/flow — only inbound entries are checked) ---
            sys_state = None
            if sys_cfg is not None and is_in:
                sys_state = self._sys_state_locked(sys_cfg, now_ms)
                dim = self._sys_check_locked(
                    sys_state, sys_cfg, now_ms, op.acquire
                )
                if dim is not None:
                    return blocked(E.BLOCK_SYSTEM, limit_type=dim)
            thr_prows = []
            for ps in op.p_slots:
                if ps.grade != C.FLOW_GRADE_QPS:
                    # THREAD-grade param gauges: not approximated (the
                    # value passes), but the device gauge would have
                    # counted +1 per admitted entry — remember the row
                    # for the recovery seed, exactly like _admit_rows
                    # (this entry's on-device exit may land after the
                    # gauge is restored).
                    if ps.prow >= 0:
                        thr_prows.append(ps.prow)
                    continue
                if ps.rule is None:
                    continue
                if not self._pbucket_for(ps, now_ms).try_take(
                    now_ms, op.acquire
                ):
                    return blocked(E.BLOCK_PARAM, ps.rule)
            # --- shaping controllers (pacer / warm-up ramp) on the
            # host mirror, BEFORE the plain buckets: a shaping block
            # must not consume bucket tokens (the device's blocked
            # entries never count toward window pass), while shaping
            # state itself advances regardless of sibling-slot
            # verdicts, exactly like the kernel's scan ---
            sg = findex.shaping_gids
            has_shaping = bool(sg) and any(g in sg for g, _ in op.slots)
            wait_ms = 0
            if has_shaping and self.shaping_enabled:
                sh_ok, wait_ms, sh_rule = self._shaping_admit_locked(
                    findex, op
                )
                if not sh_ok:
                    return blocked(E.BLOCK_FLOW, sh_rule)
            thread_rules = []
            for gid, _crow in op.slots:
                if sg and gid in sg and self.shaping_enabled:
                    continue  # decided by the shaping mirror above
                info = findex.mirror_info(gid)
                if info is None:
                    continue
                rule, grade, cap, window_ms = info
                if grade == C.FLOW_GRADE_THREAD:
                    thread_rules.append(rule)
                    cur = self._threads.get(op.resource, 0)
                    if cur + 1 > int(cap):
                        return blocked(E.BLOCK_FLOW, rule)
                else:
                    if not self._bucket_for(
                        rule, now_ms, cap, window_ms
                    ).try_take(now_ms, op.acquire):
                        return blocked(E.BLOCK_FLOW, rule)
            if self._breaker_open(op.d_gids):
                dindex = (
                    op.src[1] if op.src is not None else self._engine.degrade_index
                )
                rule = self._rule_of(dindex, op.d_gids[0]) if op.d_gids else None
                return blocked(E.BLOCK_DEGRADE, rule)
            if thread_rules:
                # The device gauge counts +1 per admitted entry
                # (acquire weights QPS only) — mirror that exactly,
                # and remember the rows for the recovery seed (this
                # entry's exit may land after the gauge is restored).
                # Delta recording only while degraded: a persistent
                # mirror's healthy admits settle on-device normally.
                self._threads[op.resource] = self._threads.get(op.resource, 0) + 1
                # Speculative ops must NOT record here even while
                # degraded: they still ride the flush, so their admit
                # deltas are recorded exactly once at fill time
                # (note_unsettled_admit) — and if recovery lands before
                # the fill, the device settles the chunk itself and no
                # replay delta is owed at all. Recording at both points
                # double-counts and pins the restored gauge.
                if self._track_deltas and not speculative:
                    for r in op.rows:
                        if r >= 0:
                            self._admit_rows[r] = self._admit_rows.get(r, 0) + 1
            if self._track_deltas and not speculative:
                for r in thr_prows:
                    self._admit_prows[r] = self._admit_prows.get(r, 0) + 1
            # Final admit: charge the system gate (the device's global
            # QPS/thread stats count admitted entries only) and the
            # shaping rules' pass mirrors.
            if sys_state is not None:
                if sys_state.bucket is not None:
                    sys_state.bucket.consume(op.acquire)
                sys_state.threads += 1
            if has_shaping and self.shaping_enabled:
                self._shaping_note_pass_locked(findex, op)
        return Verdict(
            admitted=True, reason=E.PASS, wait_ms=wait_ms, blocked_rule=None,
            degraded=degraded, speculative=speculative,
        )

    # ------------------------------------------------------------------
    # bulk admission (vectorized)
    # ------------------------------------------------------------------
    def bulk_shaping_servable(self, g, findex) -> bool:
        """The host closed-form preconditions: every shaping slot a
        plain RATE_LIMITER, ONE distinct ts, ONE acquire >= 1. Narrower
        than Engine._shaping_rounds_for's ``-1`` path, which also takes
        the warm-up behaviors: ``shaping.mirror_pacer_bulk`` serves only
        the plain pacer. The speculative tier declines non-servable
        shaped groups to the device; the degraded fill (no device to
        decline to) falls back to the plain-bucket stance for them."""
        sg = findex.shaping_gids
        if not sg or not any(gid in sg for gid, _crow in g.slots):
            return True
        ts = np.asarray(g.ts)
        acq = np.asarray(g.acquire)
        if ts.size and int(ts.min()) != int(ts.max()):
            return False
        if acq.size and (
            int(acq.min()) != int(acq.max()) or int(acq.min()) < 1
        ):
            return False
        for gid, _crow in g.slots:
            if gid in sg:
                info = findex.mirror_shaping_info(gid)
                if info is None or info[1] != C.CONTROL_BEHAVIOR_RATE_LIMITER:
                    return False
        return True

    def admit_bulk(
        self, g, now_ms: int, apply_policy: bool = True,
        speculative: bool = False,
        shaping_servable: Optional[bool] = None,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Array verdicts ``(admitted, reason, wait_ms)`` for one bulk
        group: numpy prefix math against the same buckets/counters the
        singles path consumes (QPS-grade hot-param columns pass — bulk
        rejects THREAD/cluster param rules at submit, and per-value
        buckets per row would be the per-row Python work the bulk path
        exists to avoid). Shaping slots run the closed-form host pacer
        when :meth:`bulk_shaping_servable` holds (exact rank math, the
        kernel's ``rounds == -1`` twin); otherwise they degrade to the
        plain-bucket stance."""
        n = g.n
        admitted = np.ones(n, dtype=bool)
        reason = np.full(n, E.PASS, dtype=np.int32)
        wait = np.zeros(n, dtype=np.int32)

        def block(mask: np.ndarray, code: int) -> None:
            sel = admitted & mask
            admitted[sel] = False
            reason[sel] = code

        if apply_policy and self.policy_for(g.resource) == "closed":
            block(np.ones(n, dtype=bool), E.BLOCK_FAILOVER)
            return admitted, reason, wait
        if g.custom_veto_mask is not None:
            block(np.asarray(g.custom_veto_mask, dtype=bool), E.BLOCK_CUSTOM)
        if not g.auth_ok:
            block(np.ones(n, dtype=bool), E.BLOCK_AUTHORITY)
        findex = g.src[0] if g.src is not None else self._engine.flow_index
        acquire = np.asarray(g.acquire, dtype=np.int64)
        sys_cfg = self._engine.system_config
        is_in = g.rows is not None and g.rows[3] >= 0
        with self._lock:
            # --- system protection (inbound groups only; QPS/thread
            # are per-row prefix math, RT/load/cpu scalar snapshots) ---
            sys_state = None
            if sys_cfg is not None and is_in:
                sys_state = self._sys_state_locked(sys_cfg, now_ms)
                if sys_cfg.qps >= 0 and sys_state.bucket is not None:
                    avail = sys_state.bucket.available(now_ms)
                    cum = np.cumsum(np.where(admitted, acquire, 0))
                    block(cum > avail, E.BLOCK_SYSTEM)
                if sys_cfg.max_thread >= 0:
                    adm_i = admitted.astype(np.int64)
                    excl = np.cumsum(adm_i) - adm_i
                    block(
                        excl + sys_state.threads > sys_cfg.max_thread,
                        E.BLOCK_SYSTEM,
                    )
                sys_state.roll(now_ms)
                dim = self._sys_check_scalar_locked(sys_state, sys_cfg)
                if dim is not None:
                    block(np.ones(n, dtype=bool), E.BLOCK_SYSTEM)
            # --- shaping slots (closed-form pacer; before the plain
            # buckets so a pacer block consumes no bucket tokens) ---
            sg = findex.shaping_gids
            shaped_gids = (
                [gid for gid, _crow in g.slots if gid in sg] if sg else []
            )
            shaping_as_bucket = False
            if shaped_gids:
                # ``shaping_servable`` lets the speculative tier pass
                # its already-computed predicate verdict instead of
                # re-scanning the group's ts/acquire columns here.
                if shaping_servable is None:
                    shaping_servable = self.bulk_shaping_servable(g, findex)
                if self.shaping_enabled and shaping_servable:
                    from sentinel_tpu.rules.shaping import (
                        mirror_pacer_bulk,
                        mirror_pacer_cost,
                    )

                    ts0 = int(np.asarray(g.ts)[0]) if n else now_ms
                    acq0 = int(acquire[0]) if n else 1
                    for gid in shaped_gids:
                        st = self._shaping_for(findex, gid)
                        if st is None:
                            continue
                        count, maxq = st.info[2], st.info[3]
                        cost = mirror_pacer_cost(acq0, count, st.info[4])
                        # Ranks over still-admitted rows == the
                        # kernel's shaping_live gating: the device scan
                        # also excludes custom/auth/system-blocked rows
                        # (live), and bucket/breaker blocks land AFTER
                        # the shaping stage on both planes.
                        ranks = np.cumsum(admitted.astype(np.int64))
                        ok, w, latest = mirror_pacer_bulk(
                            st.latest, count, maxq, cost, ts0, ranks
                        )
                        st.latest = latest
                        np.maximum(
                            wait,
                            np.where(admitted & ok, w, 0).astype(np.int32),
                            out=wait,
                        )
                        block(~ok, E.BLOCK_FLOW)
                else:
                    shaping_as_bucket = True
            thread_rule = None
            for gid, _crow in g.slots:
                if shaped_gids and gid in shaped_gids and not shaping_as_bucket:
                    continue  # decided by the closed-form pacer above
                info = findex.mirror_info(gid)
                if info is None:
                    continue
                rule, grade, cap, window_ms = info
                if grade == C.FLOW_GRADE_THREAD:
                    thread_rule = rule
                    cur = self._threads.get(g.resource, 0)
                    headroom = max(0, int(cap) - cur)
                    # +1 thread per admitted entry: the first `headroom`
                    # still-live rows pass, the rest block.
                    live_rank = np.cumsum(admitted)
                    block(live_rank > headroom, E.BLOCK_FLOW)
                else:
                    bucket = self._bucket_for(rule, now_ms, cap, window_ms)
                    avail = bucket.available(now_ms)
                    cum = np.cumsum(np.where(admitted, acquire, 0))
                    block(cum > avail, E.BLOCK_FLOW)
                    bucket.consume(int(np.where(admitted, acquire, 0).sum()))
            if self._breaker_open(g.d_gids):
                block(np.ones(n, dtype=bool), E.BLOCK_DEGRADE)
            n_adm = int(admitted.sum())
            if thread_rule is not None:
                self._threads[g.resource] = (
                    self._threads.get(g.resource, 0) + n_adm
                )
                # Same single-recording-point rule as admit():
                # speculative groups record at fill time
                # (note_unsettled_admit_bulk), never here.
                if self._track_deltas and not speculative:
                    for r in g.rows:
                        if r >= 0:
                            self._admit_rows[r] = (
                                self._admit_rows.get(r, 0) + n_adm
                            )
            if sys_state is not None and n_adm:
                if sys_state.bucket is not None:
                    sys_state.bucket.consume(
                        int(np.where(admitted, acquire, 0).sum())
                    )
                sys_state.threads += n_adm
        return admitted, reason, wait

    def on_exit(
        self, resource: str, n: int = 1, rows=None, rt: int = 0,
        count: int = 0, now_ms: Optional[int] = None,
        min_rt: Optional[int] = None,
    ) -> None:
        """Thread release for exits settled while DEGRADED (and, on a
        persistent mirror, synchronously at submit_exit). Clamped at
        zero: exits of entries admitted on-device before the fault were
        never counted here. ``rows``/``rt``/``count`` feed the host
        system gate when present: an inbound entry's exit (rows[3] >= 0
        — the global entry-node row) releases the global concurrency
        mirror and lands its completion in the per-second RT window
        (``rt`` = the group RT SUM, ``min_rt`` = its per-exit minimum —
        None means single exit, rt is its own sample)."""
        with self._lock:
            cur = self._threads.get(resource)
            if cur is not None:
                self._threads[resource] = max(0, cur - n)
            s = self._sys
            if (
                s is not None
                and rows is not None
                and len(rows) > 3
                and rows[3] is not None
                and rows[3] >= 0
            ):
                s.release(n)
                if count > 0 and now_ms is not None:
                    s.note_complete(now_ms, rt, count, min_rt=min_rt)

    def note_device_exit(self, rows, p_rows=(), n: int = 1) -> None:
        """Record the DEVICE-gauge releases one degraded exit would
        have scattered (all four node rows, plus param thread rows) —
        the device never sees these, so recovery replays them into the
        restored checkpoint's gauges."""
        with self._lock:
            for r in rows:
                if r is not None and r >= 0:
                    self._exit_rows[r] = self._exit_rows.get(r, 0) + n
            for r in p_rows:
                if r >= 0:
                    self._exit_prows[r] = self._exit_prows.get(r, 0) + n

    def note_unsettled_admit(self, op) -> None:
        """A speculative-admitted entry whose chunk the device never
        applied (quarantined, or filled while DEGRADED with its verdict
        already served): record its THREAD-gauge admit deltas for the
        restore replay, exactly as :meth:`admit` would have — WITHOUT
        re-running admission (the caller already holds a verdict and
        the mirror's live counter already counted it at admit time)."""
        if not self._track_deltas:
            return
        findex = op.src[0] if op.src is not None else self._engine.flow_index
        thread = any(
            (info := findex.mirror_info(gid)) is not None
            and info[1] == C.FLOW_GRADE_THREAD
            for gid, _crow in op.slots
        )
        with self._lock:
            if not self._track_deltas:
                return
            if thread:
                for r in op.rows:
                    if r >= 0:
                        self._admit_rows[r] = self._admit_rows.get(r, 0) + 1
            for ps in op.p_slots:
                if ps.grade != C.FLOW_GRADE_QPS and ps.prow >= 0:
                    self._admit_prows[ps.prow] = (
                        self._admit_prows.get(ps.prow, 0) + 1
                    )

    def note_unsettled_admit_bulk(self, g, n_adm: int) -> None:
        """Bulk analog of :meth:`note_unsettled_admit`: ``n_adm``
        speculative-admitted rows of a group the device never applied."""
        if not self._track_deltas or n_adm <= 0:
            return
        findex = g.src[0] if g.src is not None else self._engine.flow_index
        if any(
            (info := findex.mirror_info(gid)) is not None
            and info[1] == C.FLOW_GRADE_THREAD
            for gid, _crow in g.slots
        ):
            self.note_unsettled_admit_rows(g.rows, n_adm)

    def note_unsettled_admit_rows(self, rows, n: int = 1) -> None:
        """Raw-row variant of :meth:`note_unsettled_admit` for the
        speculative tier's +thread gauge-compensation ops caught in a
        degraded window (the device never saw the +n either)."""
        if not self._track_deltas:
            return
        with self._lock:
            for r in rows:
                if r is not None and r >= 0:
                    self._admit_rows[r] = self._admit_rows.get(r, 0) + n

    # ------------------------------------------------------------------
    # reconciliation clamps (speculative tier)
    # ------------------------------------------------------------------
    def drain_bucket(self, rule) -> bool:
        """Settlement said this rule's mirror was too generous (a
        speculative admit the device blocked): empty the bucket so the
        mirror stops admitting until refill — the clamp that bounds an
        over-admit streak to one detection lag per window."""
        with self._lock:
            ent = self._buckets.get(id(rule))
            if ent is not None and ent[0] is rule:
                b = ent[1]
                b.consume(b.tokens)
                return True
        return False

    def drain_pbucket(self, prow: int) -> bool:
        """Per-value clamp, same contract as :meth:`drain_bucket`."""
        with self._lock:
            ent = self._pbuckets.get(prow)
            if ent is not None:
                b = ent[1]
                b.consume(b.tokens)
                return True
        return False

    def invalidate_rule_mirrors(self) -> None:
        """A rule reload swapped the indexes: every bucket keys a rule
        object (or a prow) of the OLD world — retire them so the next
        admit compiles fresh mirrors against the new tables (the device
        dyn states are rebuilt on reload too, so a fresh full bucket is
        the faithful mirror of the fresh device window). Live THREAD
        counters persist like the device's stats gauge does."""
        with self._lock:
            self._buckets.clear()
            self._pbuckets.clear()
            self._shaping.clear()
            self._shaping_src = None

    def peek_gauge_deltas(
        self,
    ) -> Tuple[Dict[int, int], Dict[int, int], Dict[int, int], Dict[int, int]]:
        """Non-destructive ``(exit_rows, exit_prows, admit_rows,
        admit_prows)`` snapshot: a restore that later FAILS its probes
        must not lose the deltas for the next attempt — they clear only
        once a recovery fully succeeds (:meth:`clear_gauge_deltas`)."""
        with self._lock:
            return (
                dict(self._exit_rows),
                dict(self._exit_prows),
                dict(self._admit_rows),
                dict(self._admit_prows),
            )

    def clear_gauge_deltas(self) -> None:
        with self._lock:
            self._exit_rows.clear()
            self._exit_prows.clear()
            self._admit_rows.clear()
            self._admit_prows.clear()

    def snapshot(self) -> dict:
        with self._lock:
            s = self._sys
            return {
                "policy_default": self._policy_default,
                "policy_overrides": dict(self._policy_by_resource),
                "qps_buckets": len(self._buckets),
                "param_buckets": len(self._pbuckets),
                "live_threads": dict(self._threads),
                "shaping_enabled": self.shaping_enabled,
                "shaping_mirrors": len(self._shaping),
                "system_gate": (
                    None
                    if s is None
                    else {
                        "threads": s.threads,
                        "qps_tokens": (
                            round(s.bucket.tokens, 2)
                            if s.bucket is not None
                            else None
                        ),
                    }
                ),
            }


class _CompileClock:
    """Seconds each thread has spent in JAX's first-use work (trace,
    lowering, XLA compile), read from ``jax.monitoring``: JAX reports
    each span's start as a scalar and its end as a duration, both on
    the thread doing the work. One per process (listeners are global)."""

    EVENTS = frozenset((
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    ))

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._open: Dict[int, List[float]] = {}  # ident -> [depth, start]
        self._spent: Dict[int, float] = {}
        jax.monitoring.register_scalar_listener(self._on_start)
        jax.monitoring.register_event_duration_secs_listener(self._on_end)

    def _on_start(self, event: str, _value, **_kw) -> None:
        if event in self.EVENTS:
            with self._lock:
                span = self._open.setdefault(
                    threading.get_ident(), [0, time.monotonic()]
                )
                span[0] += 1

    def _on_end(self, event: str, _secs, **_kw) -> None:
        if event not in self.EVENTS:
            return
        ident = threading.get_ident()
        with self._lock:
            span = self._open.get(ident)
            if span is None:
                return
            span[0] -= 1
            if span[0] == 0:
                del self._open[ident]
                self._spent[ident] = (
                    self._spent.get(ident, 0.0) + time.monotonic() - span[1]
                )

    def seconds(self, ident: Optional[int]) -> float:
        """Compile seconds of thread ``ident`` so far, an open span
        included."""
        with self._lock:
            s = self._spent.get(ident, 0.0)
            span = self._open.get(ident)
            if span is not None:
                s += time.monotonic() - span[1]
        return s


_COMPILE_CLOCK: Optional[_CompileClock] = None
_COMPILE_CLOCK_LOCK = threading.Lock()


def compile_clock() -> _CompileClock:
    global _COMPILE_CLOCK
    with _COMPILE_CLOCK_LOCK:
        if _COMPILE_CLOCK is None:
            _COMPILE_CLOCK = _CompileClock()
        return _COMPILE_CLOCK


class _Waiter:
    """One persistent watchdog waiter thread: the engine submits a
    device call to it and waits with a timeout, so a wedged
    ``device_get`` strands THIS thread, never the submitter. A
    timed-out waiter is marked lost and abandoned (the call cannot be
    cancelled); the manager replaces it. Persistent rather than
    per-call: a thread spawn per flush costs milliseconds on small
    flushes. One waiter serves ONE call at a time — concurrent watched
    calls each take their own waiter from the manager's pool, so one
    slow call's queueing can never count against another's timeout."""

    __slots__ = ("lost", "_jobs", "_thread")

    @property
    def ident(self) -> Optional[int]:
        return self._thread.ident

    def __init__(self, name: str) -> None:
        import queue

        self.lost = False
        self._jobs: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._loop, name=name, daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:
                return
            fn, box, done = job
            try:
                box["v"] = fn()
            except BaseException as exc:
                box["e"] = exc
            finally:
                done.set()
            if self.lost:
                # The submitter timed out and abandoned us, but the
                # call DID finish — exit instead of parking forever on
                # an empty queue no one will ever feed again.
                return

    def submit(self, fn) -> Tuple[dict, threading.Event]:
        box: dict = {}
        done = threading.Event()
        self._jobs.put((fn, box, done))
        return box, done

    def stop(self) -> None:
        self._jobs.put(None)


class FailoverManager:
    """Engine-scoped failure-domain coordinator (one per Engine).

    When disarmed (``sentinel.tpu.failover.enabled`` false, the
    default) every engine hook is a single attribute read — the hot
    path pays nothing and semantics are exactly the pre-failover
    engine's (device errors re-raise to callers)."""

    def __init__(self, engine) -> None:
        self._engine = engine
        self.armed = config.get_bool(config.FAILOVER_ENABLED, False)
        self.fetch_timeout_ms = config.get_int(
            config.FAILOVER_FETCH_TIMEOUT_MS, 5000
        )
        self.checkpoint_every = max(
            0, config.get_int(config.FAILOVER_CHECKPOINT_EVERY, 8)
        )
        self.probe_k = max(1, config.get_int(config.FAILOVER_PROBE_FLUSHES, 3))
        self.retry_ms = max(0, config.get_int(config.FAILOVER_RETRY_MS, 1000))
        self._lock = threading.RLock()
        self.state = HEALTHY
        self.state_since_ms = 0
        self._last_attempt_ms: Optional[int] = None
        self._ckpt: Optional[Checkpoint] = None
        self.fallback = HostFallbackAdmitter(engine)
        self.counters: Dict[str, int] = {
            "trips": 0,
            "transitions": 0,
            "quarantined_records": 0,
            "degraded_admits": 0,
            "degraded_blocks": 0,
            "checkpoints": 0,
            "restores": 0,
            "probe_flushes": 0,
            "fetch_timeouts": 0,
            "recoveries": 0,
            "durable_writes": 0,
            "durable_write_errors": 0,
            "durable_loads": 0,
            "durable_load_cold": 0,
        }
        # Durable checkpoint spill (sentinel.tpu.failover.checkpoint.
        # path): unset (the default) = no writer thread, no file IO,
        # the in-memory-only PR-5 behavior exactly. Serialization and
        # file IO happen on a dedicated writer thread — store_checkpoint
        # runs on the drain path and must never pay the spill cost.
        self.durable_path = (
            config.get(config.FAILOVER_CKPT_PATH) or ""
        ).strip()
        self.durable_interval_ms = max(
            0, config.get_int(config.FAILOVER_CKPT_INTERVAL_MS, 1000)
        )
        self._durable_pending: Optional[Checkpoint] = None
        self._ckpt_force = False
        self._durable_event = threading.Event()
        self._durable_stop = False
        self._durable_thread: Optional[threading.Thread] = None
        # (wall_ms, seq, write_ms, bytes) of the last successful spill.
        self.last_durable: Optional[Tuple[int, int, float, int]] = None
        self.events: "deque[HealthEvent]" = deque(maxlen=64)
        self.last_fault = ""
        # Pool of idle watchdog waiters (see _Waiter): each watched
        # call takes its own, so concurrent calls never queue behind
        # each other (queueing delay counting against another caller's
        # timeout would false-trip the engine DEGRADED). Timed-out
        # waiters are abandoned; overflow returns are stopped.
        self._idle_waiters: List[_Waiter] = []
        self._waiter_lock = threading.Lock()
        # Bumped per restore attempt (and again when one times out):
        # an abandoned restore's install is gated on holding the
        # current generation — see _restore_locked.
        self._restore_gen = 0
        self._compiles = compile_clock()

    # ------------------------------------------------------------------
    # state machine
    # ------------------------------------------------------------------
    @property
    def healthy(self) -> bool:
        return self.state == HEALTHY

    @property
    def degraded(self) -> bool:
        return self.state == DEGRADED

    def _set_state_locked(self, to: str, reason: str) -> None:
        frm = self.state
        if frm == to:
            return
        now = self._engine.clock.now_ms()
        self.state = to
        self.state_since_ms = now
        self.counters["transitions"] += 1
        self.events.append(HealthEvent(now, frm, to, reason))
        tele = self._engine.telemetry
        if tele.enabled:
            tele.note_health(frm, to, reason, now_ms=now)
        cap = getattr(self._engine, "capture", None)
        if cap is not None:
            # Every transition rides the capture's rule-timeline; a
            # transition INTO DEGRADED additionally freezes the recent
            # segments (the traffic that rode the fault).
            cap.note_health({
                "event": "failover", "from": frm, "to": to,
                "reason": reason, "now_ms": now,
            })

    def trip(self, where: str, exc: BaseException, seq: object = -1) -> None:
        """A device fault (dispatch/fetch failure or watchdog timeout):
        transition to DEGRADED and quarantine the in-flight queue.
        Idempotent — later faults while already DEGRADED only update
        ``last_fault``."""
        eng = self._engine
        with self._lock:
            first = self.state != DEGRADED
            self.last_fault = f"{where}@{seq}: {type(exc).__name__}: {exc}"
            if isinstance(exc, DeviceFetchTimeout):
                self.counters["fetch_timeouts"] += 1
            if first:
                self.counters["trips"] += 1
                self._set_state_locked(DEGRADED, self.last_fault)
                self.fallback.begin(eng.clock.now_ms())
                # Auto-recovery waits retry.ms from the trip; an
                # explicit try_recover() is always allowed.
                self._last_attempt_ms = eng.clock.now_ms()
        if first:
            record_log.error("[Failover] engine DEGRADED (%s)", self.last_fault)
            eng._quarantine_pending()

    def recovery_due(self, now_ms: int) -> bool:
        if self._engine.mesh is not None:
            return False  # see try_recover's mesh gate
        with self._lock:
            if self.state != DEGRADED:
                return False
            last = self._last_attempt_ms
            return last is None or now_ms - last >= self.retry_ms

    def try_recover(self) -> bool:
        """DEGRADED → RECOVERING → (restore + K probe flushes) →
        HEALTHY; any restore/probe fault falls back to DEGRADED.
        Serialized with real flushes on the engine's flush lock."""
        eng = self._engine
        if eng.mesh is not None:
            # Restore + probe are single-chip: installing unsharded
            # states under a live mesh (or probing past one) would hand
            # the sharded kernels wrong inputs. Stay DEGRADED with an
            # actionable reason; the host fallback keeps serving.
            with self._lock:
                if self.state == HEALTHY:
                    return True
                self.last_fault = (
                    "recovery unsupported while mesh mode is enabled — "
                    "disable_mesh() first, then try_recover()"
                )
            record_log.warn("[Failover] %s", self.last_fault)
            return False
        with eng._flush_lock:
            with self._lock:
                if self.state == HEALTHY:
                    return True
                self._set_state_locked(RECOVERING, "recovery attempt")
                self._last_attempt_ms = eng.clock.now_ms()
            try:
                self._restore_locked()
                for _ in range(self.probe_k):
                    self._probe_locked()
                self._reanchor_checkpoint()
            except BaseException as exc:
                with self._lock:
                    self.last_fault = (
                        f"recovery: {type(exc).__name__}: {exc}"
                    )
                    self._set_state_locked(DEGRADED, self.last_fault)
                record_log.warn(
                    "[Failover] recovery failed (%s); staying DEGRADED",
                    self.last_fault,
                )
                return False
            self.fallback.clear_gauge_deltas()
            self.fallback.end_degraded()
            with self._lock:
                self.counters["recoveries"] += 1
                self._set_state_locked(HEALTHY, "recovered")
        record_log.info("[Failover] engine HEALTHY again")
        return True

    def _reanchor_checkpoint(self) -> None:
        """Replace the stored checkpoint with the just-installed world.

        The restore replayed the degraded window's NET gauge deltas
        into the states it installed — but the stored checkpoint still
        holds the PRE-replay world. If a second fault hits before any
        clean drain stores a fresh checkpoint, restoring that stale
        world again would resurrect gauge entries whose exits were
        already replayed and (on success) cleared from the ledger —
        leaking the THREAD gauge by exactly the replayed net, forever.

        Runs INSIDE try_recover's fault handling, after the probes and
        BEFORE clear_gauge_deltas: a fault here falls back to DEGRADED
        with the old (checkpoint, ledger) pair intact — the two are
        only ever replaced/cleared together. Caller holds the flush
        lock; the device just round-tripped the probes, so one more
        watched fetch is the expected-healthy case."""
        eng = self._engine
        meta = self.begin_checkpoint(
            eng.flush_seq, eng.clock.now_ms(),
            eng.flow_index, eng.degrade_index, eng.param_index,
        )
        sk = eng.sketch.dev_state if eng.sketch.armed else None
        states = self.watched(
            lambda: jax.device_get(
                (eng.stats, eng.flow_dyn, eng.degrade_dyn, eng.param_dyn,
                 sk)
            ),
            "checkpoint re-anchor fetch", (),
        )
        self.store_checkpoint(meta, states)

    # ------------------------------------------------------------------
    # watchdog
    # ------------------------------------------------------------------
    # A kernel DISPATCH includes first-use XLA compilation, which takes
    # a minute or more cold at full batch on a v5e: the bound counts only
    # the waiter's time outside compiles, and a compile gets this one.
    COMPILE_TIMEOUT_MS = 600_000

    def watched(self, fn, what: str, seqs: Sequence[int],
                timeout_ms: Optional[int] = None):
        """Run ``fn`` on the persistent watchdog waiter thread bounded
        by the fetch timeout, not counting the waiter's time in JAX
        compiles. A wedged device call cannot be cancelled, only
        abandoned: on timeout the waiter is marked lost (it parks on the
        dead call forever, daemonic) and the next watched call lazily
        starts a replacement."""
        if timeout_ms is None:
            timeout_ms = self.fetch_timeout_ms
        with self._waiter_lock:
            w = self._idle_waiters.pop() if self._idle_waiters else None
        if w is None or w.lost:
            w = _Waiter("sentinel-failover-waiter")
        clock = self._compiles
        c0 = clock.seconds(w.ident)
        t0 = time.monotonic()
        box, done = w.submit(fn)
        limit_s = timeout_ms / 1000.0
        cap_s = self.COMPILE_TIMEOUT_MS / 1000.0
        try:
            left_s = min(limit_s, cap_s)
            while not done.wait(left_s):
                compile_s = clock.seconds(w.ident) - c0
                outside_left = limit_s - (time.monotonic() - t0 - compile_s)
                compile_left = cap_s - compile_s
                if outside_left > 0 and compile_left > 0:
                    left_s = max(min(outside_left, compile_left), 0.01)
                    continue
                w.lost = True
                # Also queue the stop sentinel: if the wedged call
                # finishes after this flag but before its own lost
                # check, the sentinel still unparks the thread — no
                # waiter may block forever on a queue nobody feeds.
                w.stop()
                raise DeviceFetchTimeout(
                    f"{what} exceeded {timeout_ms} ms"
                    f" ({compile_s:.3f} s of it compiling;"
                    f" flush seqs {list(seqs)})"
                )
        finally:
            if not w.lost:
                with self._waiter_lock:
                    if len(self._idle_waiters) < 4:
                        self._idle_waiters.append(w)
                        w = None
                if w is not None:
                    w.stop()  # pool full: retire rather than leak
        if "e" in box:
            raise box["e"]
        return box["v"]

    # ------------------------------------------------------------------
    # degraded fill (the one home of policy-verdict assembly)
    # ------------------------------------------------------------------
    def fill_degraded(
        self, entries, exits=(), bulk=(), bulk_exits=(),
        run_custom_slots: bool = True,
    ) -> List[tuple]:
        """Fill every op's verdict from the fallback admitter; returns
        the block-log items. Used by the degraded flush path, the
        chunk-level fault handler and quarantined record fills.
        ``run_custom_slots=False`` for ops whose chunk already ran the
        custom ProcessorSlot checks before the fault — re-running a
        user slot would double its side effects (check_entry returns
        None for a pass, so custom_veto-is-None can't tell 'passed'
        from 'not checked')."""
        from sentinel_tpu.core.slots import SlotChainRegistry, SlotEntryContext

        eng = self._engine
        now = eng.clock.now_ms()
        fb = self.fallback
        tracer = eng.admission_trace
        end_pc = time.perf_counter()
        items: List[tuple] = []
        n_admit = 0
        n_block = 0
        slots_active = run_custom_slots and bool(SlotChainRegistry.slots())
        for op in entries:
            v0 = op._verdict
            if v0 is not None and v0.speculative:
                # The speculative tier already served this op's verdict
                # at submit time from the SAME (persistent) mirror —
                # keep it (the caller may have acted on it) and do only
                # the bookkeeping its settlement would have done: the
                # device never applied this chunk, so an admitted
                # THREAD entry's gauge deltas must join the restore
                # replay.
                op._pending = None
                if eng.resource_metrics.enabled:
                    # The device never settles this chunk, so the serve
                    # note that normally lands at _fill_results lands
                    # here (serve-time degraded mark rides v0).
                    eng.resource_metrics.note(
                        op.ts, op.resource, spec=op.acquire,
                        degraded=op.acquire if v0.degraded else 0,
                    )
                if v0.admitted:
                    n_admit += 1
                    fb.note_unsettled_admit(op)
                else:
                    n_block += 1
                    limit_app = (
                        getattr(v0.blocked_rule, "limit_app", None)
                        or "default"
                    )
                    items.append((
                        op.resource, E.exc_name_for_code(v0.reason),
                        limit_app, op.origin, op.acquire,
                    ))
                if op.trace is not None:
                    tracer.record_admission(
                        op.trace, op.resource, op.origin, op.context_name,
                        v0.admitted, v0.reason, -1,
                        op.spec_end_pc or end_pc,
                        degraded=v0.degraded, provenance="speculative",
                    )
                    op.trace = None
                continue
            if slots_active and not op.custom_checked:
                op.custom_veto = SlotChainRegistry.check_entry(
                    SlotEntryContext(
                        op.resource, op.context_name, op.origin,
                        op.acquire, op.prio, op.args,
                    )
                )
                op.custom_checked = True
            v = fb.admit(op, now)
            op.verdict = v
            op._pending = None
            if eng.resource_metrics.enabled:
                # Per-resource degraded serve at the op's SUBMIT ts
                # (speculative-kept verdicts above were already noted —
                # with both marks — at serve time).
                eng.resource_metrics.note(
                    op.ts, op.resource, degraded=op.acquire
                )
            if v.admitted:
                n_admit += 1
            else:
                n_block += 1
                limit_app = (
                    getattr(v.blocked_rule, "limit_app", None) or "default"
                )
                items.append((
                    op.resource, E.exc_name_for_code(v.reason), limit_app,
                    op.origin, op.acquire,
                ))
            if op.trace is not None:
                tracer.record_admission(
                    op.trace, op.resource, op.origin, op.context_name,
                    v.admitted, v.reason, -1, end_pc, degraded=True,
                )
                op.trace = None
        for g in bulk:
            if g.spec_admitted is not None and g._admitted is not None:
                # Bulk analog of the kept speculative verdict above.
                g._pending = None
                adm = g._admitted
                rsn = g._reason
                n_adm = int(adm.sum())
                blocked = ~adm
                n_admit += n_adm
                n_block += int(blocked.sum())
                fb.note_unsettled_admit_bulk(g, n_adm)
                if blocked.any():
                    for r in np.unique(rsn[blocked]):
                        cnt = int(
                            np.asarray(g.acquire)[blocked & (rsn == r)].sum()
                        )
                        items.append((
                            g.resource, E.exc_name_for_code(int(r)),
                            "default", g.origin, cnt,
                        ))
                if g.trace is not None:
                    tracer.record_bulk(
                        g.trace, g.resource, g.origin, g.context_name,
                        adm, rsn, -1, end_pc, degraded=g.spec_degraded,
                        provenance="speculative",
                    )
                    g.trace = None
                continue
            if slots_active:
                # Same shared per-distinct-acquire check as the device
                # bulk path — a registered slot's veto must keep
                # applying to bulk traffic while DEGRADED.
                SlotChainRegistry.check_bulk_entry(g)
            adm, rsn, wait = fb.admit_bulk(g, now)
            g.admitted = adm
            g.reason = rsn
            g.wait_ms = wait
            g._pending = None
            if eng.resource_metrics.enabled:
                eng.resource_metrics.note_col(
                    g.resource, g.ts, weights=g.acquire, degraded=True
                )
            blocked = ~adm
            n_admit += int(adm.sum())
            n_block += int(blocked.sum())
            if blocked.any():
                for r in np.unique(rsn[blocked]):
                    cnt = int(
                        np.asarray(g.acquire)[blocked & (rsn == r)].sum()
                    )
                    items.append((
                        g.resource, E.exc_name_for_code(int(r)), "default",
                        g.origin, cnt,
                    ))
            if g.trace is not None:
                tracer.record_bulk(
                    g.trace, g.resource, g.origin, g.context_name,
                    adm, rsn, -1, end_pc, degraded=True,
                )
                g.trace = None
        for x in exits:
            if x.thr < 0:
                fb.note_device_exit(
                    x.rows, getattr(x, "p_rows", ()) or (), -x.thr
                )
                if x.resource is not None and not fb.persistent:
                    # Persistent mirrors already released at
                    # submit_exit time (Engine routes exits to the
                    # speculative tier synchronously).
                    fb.on_exit(x.resource, 1, rows=x.rows, rt=x.rt,
                               count=x.count, now_ms=now)
            elif x.thr > 0:
                # A speculative +thread gauge-compensation op caught in
                # a degraded window: the device never saw the +n, so it
                # joins the restore replay as an unsettled admit.
                fb.note_unsettled_admit_rows(x.rows, x.thr)
        for gx in bulk_exits:
            if gx.thr < 0:
                fb.note_device_exit(gx.rows, (), gx.n)
                if gx.resource is not None and not fb.persistent:
                    fb.on_exit(gx.resource, gx.n, rows=gx.rows,
                               rt=int(gx.rt.sum()),
                               count=int(gx.count.sum()), now_ms=now,
                               min_rt=int(gx.rt.min()))
        with self._lock:
            self.counters["degraded_admits"] += n_admit
            self.counters["degraded_blocks"] += n_block
        tele = eng.telemetry
        if tele.enabled and (n_admit or n_block):
            tele.note_degraded(n_admit, n_block)
        return items

    def note_quarantined(self, n: int = 1) -> None:
        with self._lock:
            self.counters["quarantined_records"] += n

    # ------------------------------------------------------------------
    # checkpoint / restore
    # ------------------------------------------------------------------
    def checkpoint_due(self, seq: int) -> bool:
        # Sharded device states restore as single-chip arrays; skip
        # checkpoints under a mesh rather than restore wrong.
        if self._engine.mesh is not None:
            return False
        if self._ckpt_force:
            # One-shot (planned handoff): the NEXT flush checkpoints
            # regardless of the cadence so the final durable spill
            # carries the freshest state the successor can warm from.
            self._ckpt_force = False
            return True
        return (
            self.checkpoint_every > 0
            and seq % self.checkpoint_every == 0
        )

    def request_checkpoint(self) -> None:
        """Arm a one-shot checkpoint on the next flush (planned
        handoff's final-spill hook)."""
        self._ckpt_force = True

    def begin_checkpoint(self, seq, now_ms, findex, dindex, pindex) -> Checkpoint:
        """Metadata for a checkpoint whose state arrays ride the
        chunk's coalesced device fetch (engine._run_chunk)."""
        return Checkpoint(
            seq=seq,
            now_ms=now_ms,
            epoch_wall_ms=self._engine.clock.epoch_wall_ms,
            win_key=_ncfg.SECOND_CFG,
            findex_ref=weakref.ref(findex),
            dindex_ref=weakref.ref(dindex),
            pindex_ref=weakref.ref(pindex),
        )

    def store_checkpoint(self, meta: Checkpoint, host_states: tuple) -> None:
        if len(host_states) == 4:
            # Callers that predate the sketch component (probe paths,
            # tests): the sketch slot is simply absent.
            host_states = host_states + (None,)
        meta.states = host_states
        with self._lock:
            # Out-of-order materialization of two in-flight checkpointed
            # chunks must never replace a newer checkpoint with an
            # older one (seqs are dispatch-ordered).
            if self._ckpt is None or self._ckpt.seq <= meta.seq:
                self._ckpt = meta
            self.counters["checkpoints"] += 1
        if self.durable_path:
            # Capture the value→row interning maps NOW (not at spill
            # time): the writer thread runs later, and by then the live
            # index may have LRU-recycled rows the fetched param_dyn
            # still describes. Second-scale bucket drift between fetch
            # and this capture matches the in-memory restore's stance.
            pindex = meta.pindex_ref()
            if pindex is not None and meta.states[3] is not None:
                meta.param_values = pindex.values_snapshot()
            self._durable_schedule(meta)

    # ------------------------------------------------------------------
    # durable spill (sentinel.tpu.failover.checkpoint.path)
    # ------------------------------------------------------------------
    def _durable_schedule(self, meta: Checkpoint) -> None:
        with self._lock:
            if (
                self._durable_pending is None
                or self._durable_pending.seq <= meta.seq
            ):
                self._durable_pending = meta
            if self._durable_thread is None and not self._durable_stop:
                self._durable_thread = threading.Thread(
                    target=self._durable_loop,
                    name="sentinel-ckpt-writer", daemon=True,
                )
                self._durable_thread.start()
        self._durable_event.set()

    def _durable_loop(self) -> None:
        while True:
            self._durable_event.wait()
            if self._durable_stop:
                return
            # Rate limit by wall time: high flush rates keep the
            # in-memory cadence, the file sees at most one write per
            # interval (the NEWEST pending checkpoint wins).
            if self.durable_interval_ms > 0 and self.last_durable:
                gap = time.time() * 1000 - self.last_durable[0]
                wait = (self.durable_interval_ms - gap) / 1e3
                if wait > 0:
                    time.sleep(wait)
                    if self._durable_stop:
                        return
            self._durable_event.clear()
            with self._lock:
                meta, self._durable_pending = self._durable_pending, None
            if meta is None or meta.states is None:
                continue
            try:
                t0 = time.perf_counter()
                nbytes = self._durable_spill(meta)
                with self._lock:
                    self.counters["durable_writes"] += 1
                    self.last_durable = (
                        int(time.time() * 1000), meta.seq,
                        (time.perf_counter() - t0) * 1e3, nbytes,
                    )
            except Exception:
                with self._lock:
                    self.counters["durable_write_errors"] += 1
                record_log.error(
                    "[Failover] durable checkpoint spill failed",
                    exc_info=True,
                )

    def _durable_spill(self, meta: Checkpoint) -> int:
        """Serialize one checkpoint to the durable file (writer thread).
        Components whose index weakref died (a reload swapped the
        index) are omitted — they would restore as fresh states anyway.
        """
        from sentinel_tpu.runtime import durable

        eng = self._engine
        states = meta.states
        comp_leaves: List = []
        comps: Dict[str, int] = {}
        fps: Dict[str, int] = {}

        def put(name: str, tree, ok: bool) -> None:
            if not ok or tree is None:
                comps[name] = 0
                return
            leaves = jax.tree_util.tree_leaves(tree)
            comps[name] = len(leaves)
            comp_leaves.extend(np.asarray(a) for a in leaves)

        findex = meta.findex_ref()
        dindex = meta.dindex_ref()
        put("stats", states[0], True)
        put("flow", states[1], findex is not None)
        put("degrade", states[2], dindex is not None)
        # param_dyn rows name dynamically-interned (rule, value) pairs
        # whose assignment order cannot be reproduced by replaying
        # traffic in a fresh process — so the checkpoint carries the
        # value→row maps themselves (Checkpoint.param_values, captured
        # when the states materialized); the loader re-installs them
        # into the fresh ParamIndex before trusting the rows.
        pindex = meta.pindex_ref()
        put(
            "param",
            states[3],
            pindex is not None and meta.param_values is not None,
        )
        put("sketch", states[4], states[4] is not None)
        if findex is not None:
            fps["flow"] = durable.rules_fingerprint(findex.rules)
        if dindex is not None:
            fps["degrade"] = durable.rules_fingerprint(dindex.rules)
        if comps.get("param"):
            fps["param"] = durable.rules_fingerprint(pindex.rules)
        cur = _ncfg.SECOND_CFG
        header = {
            "seq": meta.seq,
            "now_ms": meta.now_ms,
            # Stats arrays are padded past the registry (capacity
            # doubling): the loader needs the captured row count to
            # rebuild the reference tree for shape validation.
            "stats_rows": int(np.shape(states[0].threads)[0]),
            "epoch_wall_ms": meta.epoch_wall_ms,
            "wall_ms": int(time.time() * 1000),
            "win": [cur.sample_count, cur.interval_ms, cur.max_rt],
            "components": comps,
            "fingerprints": fps,
            # Row-ordered registry keys AT SPILL TIME: rows are never
            # reassigned, so a key list captured slightly after the
            # states still maps every row the states contain.
            "node_keys": eng.nodes.keys_snapshot(),
        }
        if comps.get("param"):
            header["param_values"] = meta.param_values
            header["param_rows"] = int(np.shape(states[3].tokens)[0])
        return durable.write_checkpoint(
            self.durable_path, header, comp_leaves
        )

    def restore_durable(self, path: Optional[str] = None) -> bool:
        """Warm-start a FRESH engine process from the durable
        checkpoint file: load + validate, remap the stats rows through
        the node-registry key list, install via the standard
        DEGRADED → RECOVERING machinery (restore + probe flushes), and
        return True when the engine came back HEALTHY. Every validation
        failure — missing/corrupt/stale file, window-geometry change,
        rule-fingerprint mismatch — degrades to a cold start with a
        counted event (``durable_load_cold``), NEVER an exception:
        a bad optimization file must not take the engine down.

        THREAD gauges restore as ZERO: live concurrency is not a decayed
        statistic but a set of currently-running callers, and in the
        new world that set is rebuilt exactly from the workers'
        ledger re-assertions (ipc/plane.py) — restoring the captured
        gauges would double-charge every re-asserted admission."""
        from sentinel_tpu.metrics.nodes import make_stats
        from sentinel_tpu.runtime import durable

        eng = self._engine
        p = (path or self.durable_path).strip()
        if not p or eng.mesh is not None:
            return False
        import os as _os

        if not _os.path.exists(p):
            return False
        try:
            header, leaves = durable.read_checkpoint(p)
        except (durable.DurableCheckpointError, OSError) as e:
            with self._lock:
                self.counters["durable_load_cold"] += 1
            record_log.warn(
                "[Failover] durable checkpoint unusable (%s) — cold start",
                e,
            )
            return False
        stale_ms = config.get_int(config.FAILOVER_CKPT_STALE_MS, 0)
        age = int(time.time() * 1000) - int(header.get("wall_ms", 0))
        if stale_ms > 0 and age > stale_ms:
            with self._lock:
                self.counters["durable_load_cold"] += 1
            record_log.warn(
                "[Failover] durable checkpoint stale (%d ms > %d) — cold "
                "start", age, stale_ms,
            )
            return False
        try:
            ck = self._build_durable_checkpoint(header, leaves, make_stats)
        except Exception:
            with self._lock:
                self.counters["durable_load_cold"] += 1
            record_log.error(
                "[Failover] durable checkpoint rejected — cold start",
                exc_info=True,
            )
            return False
        with self._lock:
            self._ckpt = ck
            self.counters["durable_loads"] += 1
            if self.state == HEALTHY:
                self._set_state_locked(DEGRADED, "durable restore")
                self.fallback.begin(eng.clock.now_ms())
            self._last_attempt_ms = eng.clock.now_ms()
        return self.try_recover()

    def _build_durable_checkpoint(self, header, leaves, make_stats) -> Checkpoint:
        """Validate per component and assemble an installable
        :class:`Checkpoint` aligned with THIS process's world. Raises on
        structural surprises (the caller converts to a counted cold
        start)."""
        from sentinel_tpu.runtime import durable
        from sentinel_tpu.runtime.sketch import make_sketch_state

        eng = self._engine
        comps = header.get("components") or {}
        fps = header.get("fingerprints") or {}
        split: Dict[str, List[np.ndarray]] = {}
        off = 0
        for name in ("stats", "flow", "degrade", "param", "sketch"):
            n = int(comps.get(name, 0))
            split[name] = leaves[off : off + n]
            off += n

        def rebuild(name: str, ref_tree) -> Optional[object]:
            """Leaves → the reference tree's structure, gated on exact
            shape+dtype agreement (a changed rule set changes shapes)."""
            got = split[name]
            ref_leaves, treedef = jax.tree_util.tree_flatten(ref_tree)
            if len(got) != len(ref_leaves):
                return None
            for a, r in zip(got, ref_leaves):
                if tuple(a.shape) != tuple(np.shape(r)) or a.dtype != np.asarray(r).dtype:
                    return None
            return jax.tree_util.tree_unflatten(treedef, list(got))

        win = list(header.get("win") or [])
        cur = _ncfg.SECOND_CFG
        win_ok = win == [cur.sample_count, cur.interval_ms, cur.max_rt]

        # Stats: remap rows by NAME through the registry key replay —
        # a fresh process's registration order need not match the dead
        # one's. THREAD gauges zero (see restore_durable docstring).
        stats_tree = None
        node_keys = header.get("node_keys") or []
        stats_rows = int(header.get("stats_rows", 0))
        if win_ok and split["stats"] and node_keys and stats_rows >= len(
            node_keys
        ):
            mapping = eng.nodes.adopt_keys(list(node_keys))
            n_new = max(len(eng.nodes), eng.stats.n_rows)
            fresh = jax.tree_util.tree_map(
                lambda a: np.array(a), jax.device_get(make_stats(n_new))
            )
            old_tree = rebuild("stats", jax.device_get(
                make_stats(stats_rows)
            ))
            if old_tree is not None and mapping:
                old_rows = np.fromiter(mapping.keys(), np.int64, len(mapping))
                new_rows = np.fromiter(
                    mapping.values(), np.int64, len(mapping)
                )

                def scatter(fresh_leaf, old_leaf):
                    out = np.array(fresh_leaf)
                    out[new_rows] = np.asarray(old_leaf)[old_rows]
                    return out

                stats_tree = jax.tree_util.tree_map(
                    scatter, fresh, old_tree
                )
                stats_tree = stats_tree._replace(
                    threads=np.zeros_like(np.asarray(stats_tree.threads))
                )

        findex = eng.flow_index
        flow_tree = None
        if split["flow"] and fps.get("flow") == durable.rules_fingerprint(
            findex.rules
        ):
            flow_tree = rebuild(
                "flow", jax.device_get(findex.make_dyn_state())
            )
        dindex = eng.degrade_index
        degrade_tree = None
        if split["degrade"] and fps.get("degrade") == durable.rules_fingerprint(
            dindex.rules
        ):
            degrade_tree = rebuild(
                "degrade", jax.device_get(dindex.make_dyn_state())
            )
        # Param: restorable only when the compiled rules match AND the
        # fresh index accepts the spilled value→row maps (it must still
        # be value-free — adopted rows would otherwise collide with
        # live interning). Any refusal restores param cold, exactly the
        # pre-snapshot behavior.
        pindex = eng.param_index
        param_tree = None
        pvals = header.get("param_values")
        prows = int(header.get("param_rows", 0))
        if (
            split["param"]
            and pvals
            and prows > 0
            and fps.get("param") == durable.rules_fingerprint(pindex.rules)
        ):
            from sentinel_tpu.rules.param_table import make_param_state

            candidate = rebuild(
                "param", jax.device_get(make_param_state(prows))
            )
            if candidate is not None and pindex.adopt_values(pvals):
                # THREAD gauges zero for the same reason the stats
                # threads do (see restore_durable docstring): the live
                # set is rebuilt from worker ledger re-assertions.
                param_tree = candidate._replace(
                    threads=np.zeros_like(np.asarray(candidate.threads))
                )
        sketch_tree = None
        tier = eng.sketch
        if split["sketch"] and tier.armed:
            sketch_tree = rebuild(
                "sketch",
                jax.device_get(make_sketch_state(
                    tier.depth, tier.width, tier.candidates
                )),
            )

        def ref_or_dead(obj, ok: bool):
            if ok:
                return weakref.ref(obj)
            return _dead_ref()

        ck = Checkpoint(
            seq=int(header.get("seq", 0)),
            now_ms=int(header.get("now_ms", 0)),
            epoch_wall_ms=int(header.get("epoch_wall_ms", 0)),
            win_key=(cur if (win_ok and stats_tree is not None)
                     else ("durable-win-mismatch",)),
            findex_ref=ref_or_dead(findex, flow_tree is not None),
            dindex_ref=ref_or_dead(dindex, degrade_tree is not None),
            pindex_ref=ref_or_dead(pindex, param_tree is not None),
            states=(
                stats_tree
                if stats_tree is not None
                else jax.device_get(make_stats(eng.stats.n_rows)),
                flow_tree
                if flow_tree is not None
                else jax.device_get(findex.make_dyn_state()),
                degrade_tree
                if degrade_tree is not None
                else jax.device_get(dindex.make_dyn_state()),
                param_tree,
                sketch_tree,
            ),
        )
        return ck

    def _restore_locked(self) -> None:
        """Re-seed the engine's device states from the last good
        checkpoint; the body runs on the watchdog waiter — restore
        does host→device transfers and scatter math against the very
        device that just faulted, and an unbounded wedge here would
        hold the flush lock (and every submitter) forever. Caller
        holds the flush lock.

        A timed-out restore cannot be cancelled, only abandoned — the
        generation token makes the zombie's eventual completion a
        no-op (its install check in ``_restore_body`` fails) instead
        of overwriting whatever world is live by then."""
        with self._lock:
            self._restore_gen += 1
            gen = self._restore_gen
        try:
            self.watched(
                lambda: self._restore_body(gen), "restore dispatch", ()
            )
        except BaseException:
            with self._lock:
                self._restore_gen += 1
            raise

    def _restore_body(self, gen: int) -> None:
        """Fresh states when no checkpoint exists or a component went
        stale; re-based through the shared ``shift_ws`` machinery if
        the clock epoch moved since capture."""
        from sentinel_tpu.metrics.nodes import make_stats
        from sentinel_tpu.rules.param_table import make_param_state

        eng = self._engine
        if eng.faults is not None:
            eng.faults.on_restore()

        def to_dev(tree):
            # COPY, never jnp.asarray: on CPU asarray can be zero-copy,
            # making the device buffer alias the checkpoint's retained
            # numpy arrays — the next flush donates the state and XLA
            # may rewrite that memory in place, corrupting the stored
            # checkpoint for any later restore (same hazard class as
            # the encode-arena's staging rule).
            return jax.tree_util.tree_map(
                lambda a: jnp.array(a, copy=True), tree
            )
        ck = self._ckpt
        with self._lock:
            self.counters["restores"] += 1
        with eng._lock:
            fresh_stats = ck is None or ck.win_key != _ncfg.SECOND_CFG
            if fresh_stats:
                stats = make_stats(eng.stats.n_rows)
            else:
                stats = to_dev(ck.states[0])
            if ck is not None and ck.findex_ref() is eng.flow_index:
                flow_dyn = to_dev(ck.states[1])
            else:
                flow_dyn = eng.flow_index.make_dyn_state()
            if ck is not None and ck.dindex_ref() is eng.degrade_index:
                degrade_dyn = to_dev(ck.states[2])
                restored_breakers = np.asarray(
                    ck.states[2].state, dtype=np.int32
                ).reshape(-1)
            else:
                degrade_dyn = eng.degrade_index.make_dyn_state()
                restored_breakers = None
            if ck is not None and ck.pindex_ref() is eng.param_index:
                param_dyn = to_dev(ck.states[3])
            else:
                param_dyn = make_param_state(8)
            offset = (
                eng.clock.epoch_wall_ms - ck.epoch_wall_ms
                if ck is not None
                else 0
            )
            if offset > 0:
                # The clock epoch re-anchored between capture and now:
                # run the restored states through the same timestamp
                # shift the live rebase applies (engine._shift_states).
                stats, flow_dyn, degrade_dyn, param_dyn = eng._shift_states(
                    stats, flow_dyn, degrade_dyn, param_dyn, offset
                )
            # Replay the degraded window's NET thread-gauge deltas: a
            # gauge has no time decay, so exits the device never saw
            # must be subtracted (or the restored budget stays pinned
            # forever) AND fallback-admitted entries still in flight
            # must be added (or their post-recovery exits drive the
            # gauge negative, permanently under-enforcing the limit) —
            # an entry admitted and exited while degraded cancels
            # itself. Clamped at 0 against residual mismatch. Peeked,
            # not drained: a failed probe must not lose the deltas for
            # the next attempt (try_recover clears them on success).
            # Residual approximation: exits of chunks that settled
            # cleanly between the checkpoint and the fault are still
            # lost — bounded by the checkpoint cadence.
            (
                rel_rows, rel_prows, adm_rows, adm_prows,
            ) = self.fallback.peek_gauge_deltas()
            net_rows = {
                r: adm_rows.get(r, 0) - rel_rows.get(r, 0)
                for r in set(adm_rows) | set(rel_rows)
            }
            net_rows = {r: d for r, d in net_rows.items() if d != 0}
            if net_rows:
                rows = jnp.asarray(list(net_rows), dtype=jnp.int32)
                cnt = jnp.asarray(
                    [net_rows[r] for r in net_rows], dtype=jnp.int32
                )
                threads = stats.threads.at[rows].add(cnt, mode="drop")
                stats = stats._replace(threads=jnp.maximum(threads, 0))
            # Param thread rows get the same NET treatment as the node
            # gauges above: fallback admits seed (+), degraded-window
            # exits replay (−), an entry admitted and exited while
            # degraded cancels itself. Only meaningful while the live
            # param index is the checkpoint's — after a reload the rows
            # name different (rule, value) pairs.
            if ck is not None and ck.pindex_ref() is eng.param_index:
                net_prows = {
                    r: adm_prows.get(r, 0) - rel_prows.get(r, 0)
                    for r in set(adm_prows) | set(rel_prows)
                }
                net_prows = {r: d for r, d in net_prows.items() if d != 0}
                if net_prows:
                    rows = jnp.asarray(list(net_prows), dtype=jnp.int32)
                    cnt = jnp.asarray(
                        [net_prows[r] for r in net_prows], dtype=jnp.int32
                    )
                    pthreads = param_dyn.threads.at[rows].add(cnt, mode="drop")
                    param_dyn = param_dyn._replace(
                        threads=jnp.maximum(pthreads, 0)
                    )
            if gen != self._restore_gen:
                # The watchdog abandoned THIS restore (timeout) and the
                # engine moved on — a newer restore may have installed a
                # newer world, or post-recovery flushes are already
                # chaining live state. Installing now would silently
                # replace live states with stale ones and resize tables
                # under a concurrent flush; become a no-op instead.
                # (Plain int read: the GIL makes it atomic, and taking
                # self._lock under eng._lock would order locks against
                # other paths.)
                return
            eng.stats = stats
            eng.flow_dyn = flow_dyn
            eng.degrade_dyn = degrade_dyn
            eng.param_dyn = param_dyn
            # Sketch tier: the checkpoint CARRIES the device SketchState
            # (PR 15 — an engine trip used to silently reset it, which
            # dropped heavy-hitter protection until counts re-accumulated
            # and let the demotion clock tear down every promoted rule).
            # Keys are stable CRC ids, so the table is position-
            # independent: restore verbatim when shapes still match the
            # live config; promotion state is host-side and survives
            # untouched, and the restored candidate table keeps the
            # promoted keys' estimates above the demotion threshold.
            sk = ck.states[4] if ck is not None and len(ck.states) > 4 else None
            if (
                sk is not None
                and eng.sketch.armed
                and eng.sketch.dev_state is not None
                and all(
                    tuple(np.shape(a)) == tuple(np.shape(b))
                    for a, b in zip(sk, eng.sketch.dev_state)
                )
            ):
                eng.sketch.dev_state = to_dev(sk)
            else:
                eng.sketch.reset_device_state()
            # Resync the breaker host mirror to the restored world so
            # observers (and a later degraded window) never diff
            # against pre-fault state.
            eng._reset_breaker_mirror()
            if restored_breakers is not None and restored_breakers.shape == (
                eng._breaker_state_host.shape[0],
            ):
                with eng._breaker_mirror_lock:
                    eng._breaker_state_host = restored_breakers
            eng._ensure_capacity()

    def _probe_locked(self) -> None:
        """One probe no-op flush: full dispatch → execute → fetch
        round-trip through the real kernel with an all-invalid batch;
        raises on any fault (watchdog-bounded). Caller holds the flush
        lock."""
        from sentinel_tpu.runtime.flush import flush_step_jit, make_probe_batch

        eng = self._engine
        seq = eng._next_flush_seq()
        if eng.faults is not None:
            eng.faults.on_dispatch(seq)
        batch = make_probe_batch(eng.clock.now_ms())
        out = self.watched(
            lambda: flush_step_jit(
                eng.stats,
                eng.flow_index.device,
                eng.flow_dyn,
                eng.degrade_index.device,
                eng.degrade_dyn,
                eng.param_dyn,
                eng._system_device(),
                batch,
                occupy_timeout_ms=config.occupy_timeout_ms,
                with_occupy=False,
                with_system=False,
                with_degrade=False,
                with_exits=False,
                blk_topk=0,
                win_key=_ncfg.SECOND_CFG,
            ),
            "probe dispatch",
            (seq,),
        )
        eng.stats, eng.flow_dyn, eng.degrade_dyn, eng.param_dyn, _sk, result = out
        eng._fetch_refs((result.admitted,), (seq,))
        with self._lock:
            self.counters["probe_flushes"] += 1
        tele = eng.telemetry
        if tele.enabled:
            tele.note_probe()

    # ------------------------------------------------------------------
    # readers
    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Back to HEALTHY with no checkpoint (engine reset)."""
        with self._lock:
            self._set_state_locked(HEALTHY, "engine reset")
            self._ckpt = None
            self._last_attempt_ms = None
        self.fallback.clear_gauge_deltas()
        self.fallback.end_degraded()

    def warm_probe(self, k: int = 1) -> float:
        """Standby warm-compile: drive ``k`` all-invalid probe batches
        through the REAL flush kernel (dispatch → execute → fetch) so
        every jit cache entry the serving path needs exists before this
        engine ever attaches to the rings. Probe batches are pow2-padded
        to the serving shapes, so the first real flush after takeover
        pays zero compiles. Returns elapsed milliseconds (the bench's
        ``standby_warm_boot_ms`` numerator). Raises on kernel faults —
        a standby that cannot run the kernel must not report ready."""
        eng = self._engine
        t0 = time.perf_counter()
        with eng._flush_lock:
            for _ in range(max(1, int(k))):
                self._probe_locked()
        return (time.perf_counter() - t0) * 1e3

    def spill_durable_now(self) -> bool:
        """Planned-handoff final spill: write the newest checkpoint
        (pending-for-the-writer first, else last-good) synchronously on
        the CALLER's thread — the async writer's rate limit must not
        hold the draining engine's exit, and the successor's final
        restore wants this state on disk before the old process dies.
        Returns True on a successful write; never raises."""
        if not self.durable_path:
            return False
        with self._lock:
            meta = self._durable_pending or self._ckpt
            self._durable_pending = None
        if meta is None or meta.states is None:
            return False
        try:
            t0 = time.perf_counter()
            nbytes = self._durable_spill(meta)
            with self._lock:
                self.counters["durable_writes"] += 1
                self.last_durable = (
                    int(time.time() * 1000), meta.seq,
                    (time.perf_counter() - t0) * 1e3, nbytes,
                )
            return True
        except Exception:
            with self._lock:
                self.counters["durable_write_errors"] += 1
            record_log.error(
                "[Failover] final durable spill failed", exc_info=True
            )
            return False

    def close(self) -> None:
        """Retire the idle watchdog waiter pool (engine shutdown) —
        without this every armed engine leaks up to 4 parked daemon
        threads for the process's lifetime. Non-destructive: a later
        watched call lazily starts fresh waiters, so the engine stays
        usable (matching Engine.close's contract)."""
        with self._waiter_lock:
            waiters, self._idle_waiters = self._idle_waiters, []
        for w in waiters:
            w.stop()
        # Stop the durable-checkpoint writer (if one ever started) —
        # non-destructive like the waiters: a later store_checkpoint
        # would lazily start a fresh writer.
        with self._lock:
            self._durable_stop = True
            t, self._durable_thread = self._durable_thread, None
        self._durable_event.set()
        if t is not None:
            t.join(timeout=5.0)
        with self._lock:
            self._durable_stop = False
            self._durable_event.clear()

    def snapshot(self) -> dict:
        with self._lock:
            ck = self._ckpt
            return {
                "enabled": self.armed,
                "state": self.state,
                "state_since_ms": self.state_since_ms,
                "policy": config.get(config.FAILOVER_POLICY) or "open",
                "fetch_timeout_ms": self.fetch_timeout_ms,
                "checkpoint_every": self.checkpoint_every,
                "probe_flushes": self.probe_k,
                "retry_ms": self.retry_ms,
                "last_fault": self.last_fault,
                "counters": dict(self.counters),
                "checkpoint": (
                    {"seq": ck.seq, "now_ms": ck.now_ms}
                    if ck is not None and ck.states is not None
                    else None
                ),
                "durable": {
                    "path": self.durable_path,
                    "interval_ms": self.durable_interval_ms,
                    "writes": self.counters["durable_writes"],
                    "write_errors": self.counters["durable_write_errors"],
                    "loads": self.counters["durable_loads"],
                    "load_cold": self.counters["durable_load_cold"],
                    "last": (
                        {
                            "wall_ms": self.last_durable[0],
                            "seq": self.last_durable[1],
                            "write_ms": round(self.last_durable[2], 3),
                            "bytes": self.last_durable[3],
                            "age_ms": max(
                                0,
                                int(time.time() * 1000)
                                - self.last_durable[0],
                            ),
                        }
                        if self.last_durable
                        else None
                    ),
                },
                "events": [e.as_dict() for e in self.events],
                "fallback": self.fallback.snapshot(),
            }
