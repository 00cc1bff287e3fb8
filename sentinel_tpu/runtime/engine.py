"""The batched engine: host-side op buffering, encoding, and flushing.

This object plays the role of the reference's ``CtSph`` + slot chain +
node map (reference: sentinel-core/.../CtSph.java:43-233): it owns the
device-resident statistics (`StatsState`), the compiled rule tables, the
host node registry, and the pending-op buffer. ``entry()``-style calls
enqueue ops; ``flush()`` encodes them into padded arrays and runs the
jitted flush kernel once for the whole batch.

Two usage modes:

* **sync** (default for the public API): every entry call flushes the
  pending buffer and returns that entry's verdict — semantically the
  reference's synchronous ``SphU.entry``. Batching still happens
  naturally whenever multiple ops accumulated since the last flush
  (exits, traces, other threads' entries).
* **deferred**: callers ``submit_many`` (or ``submit_entry`` in a loop)
  and ``flush()`` once — the high-throughput path (the analog of the
  reference's cluster client, which already tolerates decision latency;
  see SURVEY.md §7). Verdicts appear on the returned ops after the
  flush. The pending buffer is bounded: reaching ``max_batch``
  (csp.sentinel.flush.max.batch) triggers a flush-on-size, and one
  flush processes at most ``max_batch`` ops per kernel launch.

Locking: ``_lock`` guards the pending buffers and host indexes and is
held only briefly; ``_flush_lock`` serializes flushes and owns the
device state during a flush. Kernel dispatch and the device→host fetch
run under ``_flush_lock`` alone, so submission proceeds concurrently
with a device round-trip (lock order: ``_flush_lock`` → ``_lock``).
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sentinel_tpu.core import errors as E
from sentinel_tpu.metrics.events import MetricEvent
from sentinel_tpu.metrics import metric_array as ma
from sentinel_tpu.metrics import nodes as _ncfg
from sentinel_tpu.metrics.nodes import (
    MINUTE_CFG,
    NodeRegistry,
    StatsState,
    grow_stats,
    make_stats,
)
from sentinel_tpu.metrics.admission_trace import AdmissionTracer
from sentinel_tpu.metrics.telemetry import TelemetryBus
from sentinel_tpu.models import constants as C
from sentinel_tpu.models.rules import FlowRule
from sentinel_tpu.rules.flow_table import FlowIndex, FlowRuleDynState
from sentinel_tpu.models.rules import AuthorityRule, DegradeRule, ParamFlowRule
from sentinel_tpu.rules.degrade_table import DegradeDynState, DegradeIndex
from sentinel_tpu.rules.param_table import (
    PARAM_CLOSED_MAX_SEGMENTS,
    ArgsColumns,
    ParamBatch,
    ParamDynState,
    ParamIndex,
    ParamSlotInfo,
    grow_param_state,
    make_param_state,
)
from sentinel_tpu.rules.shaping import ShapingBatch
from sentinel_tpu.runtime.flush import (
    SYS_TYPE_NAMES,
    FlushBatch,
    SystemDevice,
    flush_step_full_jit,
    flush_step_jit,
    flush_step_param_jit,
    flush_step_shaping_jit,
)
from sentinel_tpu.runtime.sketch import SketchBatch
from sentinel_tpu.utils.system_status import sampler as system_sampler
from sentinel_tpu.utils.clock import Clock, SystemClock, default_clock
from sentinel_tpu.utils.config import config
from sentinel_tpu.utils.numeric import pad_pow2 as _pad_pow2


class Verdict(NamedTuple):
    admitted: bool
    reason: int  # errors.PASS / BLOCK_*
    wait_ms: int
    blocked_rule: Optional[object]  # the rule bean that blocked, if attributable
    limit_type: str = ""  # system block dimension (qps/thread/rt/load/cpu)
    slot_name: str = ""  # custom slot that vetoed (reason BLOCK_CUSTOM)
    # Verdict provenance: True when the decision came from the host
    # fallback admitter while the engine was DEGRADED (device lost) —
    # never from the device path (runtime/failover.py).
    degraded: bool = False
    # True when the decision came from the speculative host tier
    # (runtime/speculative.py) — the device flush settles the same op
    # later and reconciliation diffs the two; ``degraded`` composes
    # (a speculative verdict served while the device is lost carries
    # both marks).
    speculative: bool = False


class _ChunkResults(NamedTuple):
    """What one chunk's settle fetches: the device arrays, then their
    host values. A drain batches MANY chunks' records into one
    coalesced ``jax.device_get`` (each separate fetch costs a full
    device round-trip), which keeps the record type and its None
    fields. The optional groups ride the same fetch when they apply."""

    admitted: object
    reason: object
    slot_ok: object
    wait_ms: object
    sys_type: object
    dslot_ok: object
    # (rows, weight) of the blocked-resource top-K, folded on the device
    # when telemetry is on (the sharded mesh flush has no fold).
    blk: Optional[tuple] = None
    # Post-flush breaker states, when breaker observers are registered.
    breaker: object = None
    # Settled pacer / warm-up dyn columns, for the speculative shaping
    # mirror.
    shaping: Optional[tuple] = None
    # Sketch candidate (ids, counts), when the sketch tier is armed.
    sketch: Optional[tuple] = None
    # The device states, when a failover checkpoint is due.
    ckpt: Optional[tuple] = None


class _PendingChunk:
    """One dispatched flush chunk, from its dispatch to its settled
    verdicts. ``Engine._run_chunk`` returns one for every chunk, and
    :meth:`settle` is the one path that settles it, whatever the
    pipeline depth: depth 0 settles it right after its dispatch, under
    the flush lock; the depth-K ``flush()`` and ``flush_async`` queue
    it, and ``Engine._drain_pending`` settles it FIFO, usually from one
    coalesced ``jax.device_get`` across many records. The record holds
    its own device-array references and fill closure (with their own
    index snapshots), so rule reloads after dispatch cannot skew
    attribution.

    ``seq`` is the chunk's flush sequence number (the fault injector's
    key and the watchdog's attribution), or -1 when the engine was
    degraded before the chunk got one: such a chunk never reached the
    device. A record with no ``refs`` has nothing to fetch and settles
    from policy.

    A queued record has its own RLock: the blocking device round-trip
    and any user callbacks in post work run WITHOUT the engine's deque
    lock held (concurrent dispatchers must not stall behind a fetch),
    and re-entrant materialization from a callback is a no-op."""

    __slots__ = (
        "_engine", "_entries", "_exits", "_bulk", "_bulk_exits", "_refs",
        "_fill", "_staging", "_span", "_seq", "_cap_tok", "_deferred",
        "_done", "_error", "_lock",
    )

    def __init__(
        self, engine: "Engine", entries: List["_EntryOp"], exits: list,
        bulk: List["BulkOp"], bulk_exits: list,
        refs: Optional[_ChunkResults] = None, fill=None,
        staging: Optional[List[tuple]] = None, span=None, seq: int = -1,
        cap_tok=None, deferred: bool = False,
    ) -> None:
        self._engine = engine
        # The fill closure owns the chunk's normal processing; the op
        # lists are kept so a policy fill can serve verdicts AND record
        # the exits' device-gauge releases (replayed at restore)
        # without the device results.
        self._entries = entries
        self._exits = exits
        self._bulk = bulk
        self._bulk_exits = bulk_exits
        self._refs = refs  # device arrays awaiting their host fetch
        # (fetched _ChunkResults) -> (blocked_items, spec_groups)
        self._fill = fill
        # Arena staging buffers held until the fetch completes (the
        # dispatched computation may read them zero-copy until then).
        self._staging = staging or []
        # Flight-recorder span closed at settle (None when telemetry
        # is disabled).
        self._span = span
        self._seq = seq
        # Capture-journal verdict token (one-shot): the fill closure
        # spills verdicts on success; a policy fill spills the degraded
        # verdicts instead.
        self._cap_tok = cap_tok
        # Queued (depth K / flush_async) rather than settled at once.
        self._deferred = deferred
        self._done = False
        self._error: Optional[BaseException] = None
        self._lock = threading.RLock()

    def settle(self, got: Optional[_ChunkResults] = None) -> List[tuple]:
        """Settle the chunk, exactly once, and return its block-log
        items; the caller runs the post work (``Engine._post_flush``).
        The caller holds ``self._lock``, or owns the record alone
        (depth 0). ``got`` is the record's results from a coalesced
        drain; None → fetched here through ``Engine._fetch_refs``.

        With failover armed, a fetch or fill fault (or an engine already
        DEGRADED before the fetch) trips failover and serves the chunk
        from policy. Disarmed, the error is stored for every later
        reader and raised — a device failure must never read as
        'nothing admitted'. Staging returns to the arena only after a
        SUCCESSFUL fetch, which proves the computation consumed its
        possibly-zero-copy inputs; a failed fetch drops it to GC. The
        chunk's references are dropped as they are consumed."""
        eng = self._engine
        fo = eng.failover
        refs, fill, staging = self._refs, self._fill, self._staging
        chunk = (self._entries, self._exits, self._bulk, self._bulk_exits)
        span, cap_tok = self._span, self._cap_tok
        self._done = True
        self._refs = self._fill = self._span = self._cap_tok = None
        self._staging = self._entries = self._exits = ()
        self._bulk = self._bulk_exits = ()
        if refs is None or (got is None and fo.armed and fo.degraded):
            # Nothing dispatched, or the engine degraded while this
            # record waited: don't touch the (possibly wedged) device.
            return self._serve_policy(chunk, span, cap_tok)
        tele = eng.telemetry
        t0 = time.perf_counter()
        try:
            if got is None:
                got = eng._fetch_refs(refs, (self._seq,), span)
                if self._deferred:
                    eng._note_drain_ms((time.perf_counter() - t0) * 1e3)
            with tele.phase("sentinel.fill", span, "fill_ms",
                            flush=self._seq, groups=len(chunk[2])) as ph:
                items, spec_groups = fill(got)
                ph.set(spec_groups=spec_groups, block_items=len(items))
        except BaseException as exc:
            if fo.armed:
                fo.trip("fetch", exc, self._seq)
                return self._serve_policy(chunk, span, cap_tok)
            self._error = exc
            raise
        if staging and eng._arena is not None:
            eng._arena.give_all(staging)
        if span is not None:
            span.spec_groups = spec_groups
            span.block_items = len(items)
            # For a coalesced drain the fetch cost is in the drain
            # histogram: the span's settle window holds this record's
            # own fill (and its own fetch on the fallback path).
            tele.settle(span, t0, time.perf_counter())
        if not self._deferred and eng.ingest.armed:
            # Settle-latency signal for the ingest deadline valve (a
            # queued record's own fetch fed it through _note_drain_ms).
            eng.ingest.note_settle_ms((time.perf_counter() - t0) * 1e3)
        return items

    def _serve_policy(self, chunk: tuple, span, cap_tok) -> List[tuple]:
        """The one policy fill: the chunk's verdicts come from the host
        fallback admitter (degraded provenance; runtime/failover.py)
        instead of device results. A chunk with no flush seq never
        reached the device, so its custom slot checks run here; any
        other chunk's results were lost to a dispatch or fetch fault or
        a quarantine (counted), and its custom slots already ran at
        dispatch — a user hook never runs twice. Exits ride along so
        their thread-gauge releases are recorded for the restore replay
        (the chunk postdates any stored checkpoint)."""
        eng = self._engine
        fo = eng.failover
        lost = self._seq >= 0
        if lost:
            fo.note_quarantined()
        if span is not None:
            span.quarantined = True
            now = time.perf_counter()
            eng.telemetry.settle(span, now, now)
        entries, exits, bulk, bulk_exits = chunk
        items = fo.fill_degraded(entries, exits, bulk, bulk_exits,
                                 run_custom_slots=not lost)
        if cap_tok is not None and eng.capture is not None:
            eng.capture.note_verdicts(cap_tok, entries, bulk, degraded=True)
        return items

    def materialize(self, got: Optional[_ChunkResults] = None) -> None:
        """Settle + post work (block log, cluster-token releases),
        exactly once. A stored settle error re-raises to EVERY caller;
        a post-work failure (log IO, release RPCs) surfaces to this
        caller only — the verdicts ARE filled, so readers must not see
        them as poisoned."""
        with self._lock:
            if self._done:
                if self._error is not None:
                    raise self._error
                return
            entries = self._entries
            items = self.settle(got)
            with self._engine.telemetry.phase("sentinel.post_flush",
                                              flush=self._seq):
                self._engine._post_flush((entries, items))

    def quarantine(self) -> None:
        """Serve the chunk from policy unless already settled
        (``Engine._quarantine_pending``, at a failover trip)."""
        with self._lock:
            if not self._done:
                self._refs = None
                self.materialize()

    def wait(self) -> None:
        self._engine._drain_pending(upto=self)


@dataclass(slots=True)
class _EntryOp:
    resource: str
    ts: int
    acquire: int
    rows: Tuple[int, int, int, int]  # default, cluster, origin|-1, entry|-1
    slots: List[Tuple[int, int]]  # (rule_gid, check_row)
    d_gids: List[int] = field(default_factory=list)  # degrade rule ids
    p_slots: List[ParamSlotInfo] = field(default_factory=list)  # hot-param slots
    auth_ok: bool = True
    prio: bool = False
    cluster_blocked_rule: Optional[object] = None  # token server said BLOCKED
    _verdict: Optional[Verdict] = field(default=None, repr=False)
    # The chunk's record while it is queued (depth K, flush_async).
    _pending: Optional[_PendingChunk] = field(default=None, repr=False, compare=False)
    # Held concurrency tokens acquired from the token service for
    # cluster THREAD-grade rules: [(service, token_id)] — released at
    # exit, or immediately if the entry is ultimately blocked.
    cluster_tokens: List[Tuple[object, int]] = field(default_factory=list)
    # Cluster flow-ids whose verdict the token server already issued
    # (OK/BLOCKED) — a post-reload re-resolve must not re-add these as
    # local slots, but must keep fallback-to-local slots. Keyed by
    # flow_id, which is stable across reloads (gids are not).
    token_decided_flow_ids: frozenset = frozenset()
    # (slot, veto) when a registered custom ProcessorSlot vetoed this op.
    custom_veto: Optional[Tuple[object, object]] = None
    # The slot chain already ran for this op (check_entry returns None
    # for a PASS, so custom_veto-is-None alone cannot distinguish
    # "passed" from "not checked" — without this flag a speculative op
    # whose slots pass would re-run every user hook at encode time).
    custom_checked: bool = False
    # Resolution context: which index objects the gids/rows above came
    # from, plus what is needed to re-resolve if a rule reload swapped
    # the tables between submit and flush (see _flush_locked).
    context_name: str = C.CONTEXT_DEFAULT_NAME
    origin: str = ""
    args: Tuple[object, ...] = ()
    src: Optional[Tuple[object, object, object]] = None  # (findex, dindex, pindex)
    # Admission-trace stamp (admission_trace.TraceTag) — None when the
    # tracer is disabled or the op predates it; consumed (and nulled)
    # when the verdict fill records the admission.
    trace: Optional[object] = field(default=None, repr=False, compare=False)
    # perf_counter when the speculative tier served this op's verdict
    # (0.0 = not speculatively decided) — the latency the admission
    # trace attributes to a speculative record.
    spec_end_pc: float = 0.0

    @property
    def param_thread_rows(self) -> List[int]:
        from sentinel_tpu.models import constants as _C

        return [s.prow for s in self.p_slots if s.grade == _C.FLOW_GRADE_THREAD]

    @property
    def verdict(self) -> Optional[Verdict]:
        """The flush decision; reading it materializes a pending
        flush_async fetch first, so callers never see a half-flushed
        op."""
        if self._verdict is None and self._pending is not None:
            self._pending.wait()
        return self._verdict

    @verdict.setter
    def verdict(self, v: Optional[Verdict]) -> None:
        self._verdict = v


# submit_entry's keyword surface — the submit_many fast path accepts
# exactly these request-dict keys and defers anything else to
# submit_entry(**req) so typos still raise TypeError.
_SUBMIT_ENTRY_KEYS = frozenset(
    ("resource", "context_name", "origin", "acquire", "entry_type",
     "prio", "ts", "args")
)


@dataclass
class _BulkParamCols:
    """One param rule's resolved columns over a bulk group: per-entry
    prow / threshold / throttle-cost arrays (hot items make the
    threshold per-value), with a validity mask for entries whose args
    had no value for this rule. Rule-constant fields ride on ``rule``.
    """

    rule: ParamFlowRule
    valid: np.ndarray  # bool [n]
    prow: np.ndarray  # int32 [n]
    token_count: np.ndarray  # int32 [n]
    cost_ms: np.ndarray  # int32 [n]


@dataclass
class BulkOp:
    """A columnar group of ``n`` identical-shape entries on one
    resource — the TPU-idiomatic bulk path (one slot resolution, one
    numpy-slice encode, array verdicts; no per-op Python objects).

    The reference has no analog — its API is one CAS-racing call per
    request (SphU.entry, CORE/SphU.java:84) — but its *cluster client*
    already concedes that decisions tolerate batch latency; this is
    that concession made into the primary high-throughput surface
    (SURVEY.md §7 "batch-driven" inversion).

    After ``flush()``: ``admitted``/``reason``/``wait_ms`` are dense
    numpy arrays of length ``n``.
    """

    resource: str
    n: int
    ts: np.ndarray  # int32 [n]
    acquire: np.ndarray  # int32 [n]
    rows: Tuple[int, int, int, int]
    slots: List[Tuple[int, int]]
    d_gids: List[int]
    auth_ok: bool
    context_name: str
    origin: str
    src: Optional[Tuple[object, object, object]] = None
    # Hot-param support (QPS grade only): per-entry args (one tuple per
    # entry, e.g. a column of client IPs) resolved to per-rule COLUMNS
    # (one _BulkParamCols per param rule) — the columnar analog of
    # _EntryOp.p_slots. Distinct values intern once via np.unique;
    # per-request cost is a vectorized gather, not a Python walk.
    args_column: Optional[Sequence] = None
    p_cols: List["_BulkParamCols"] = field(default_factory=list)
    custom_veto: Optional[Tuple[object, object]] = None
    # Which entries a custom slot vetoed (per-acquire-value checks);
    # None = no veto anywhere in the group.
    custom_veto_mask: Optional[np.ndarray] = None
    # The slot chain already ran for this group (see _EntryOp): a
    # vetoless pass leaves both fields above None, so this flag is
    # what makes check_bulk_entry run-once.
    custom_checked: bool = False
    # results (filled by flush; lazily materialized after flush_async)
    _admitted: Optional[np.ndarray] = field(default=None, repr=False)
    _reason: Optional[np.ndarray] = field(default=None, repr=False)
    _wait_ms: Optional[np.ndarray] = field(default=None, repr=False)
    _pending: Optional[_PendingChunk] = field(default=None, repr=False, compare=False)
    # Group-level admission-trace stamp (bounded per-row records land
    # at verdict fill — see AdmissionTracer.record_bulk_groups).
    trace: Optional[object] = field(default=None, repr=False, compare=False)
    # Speculative-tier verdict copy (runtime/speculative.py): non-None
    # marks the group as speculatively decided — the settled device
    # arrays then reconcile against this instead of replacing the
    # caller-visible results.
    spec_admitted: Optional[np.ndarray] = field(default=None, repr=False)
    # Engine health when the speculative verdicts were served (the
    # group-level analog of Verdict.degraded — trace provenance must
    # report serve-time state, not settle-time state).
    spec_degraded: bool = False

    @property
    def speculative(self) -> bool:
        """True when this group's verdicts came from the speculative
        host tier. Pass this to :meth:`Engine.submit_exit_bulk`'s
        ``speculative`` flag (the bulk analog of
        ``Verdict.speculative``) so device-decided groups' exits don't
        release a mirror count they never charged."""
        return self.spec_admitted is not None

    def _materialize(self) -> None:
        if self._admitted is None and self._pending is not None:
            self._pending.wait()

    @property
    def admitted(self) -> Optional[np.ndarray]:
        self._materialize()
        return self._admitted

    @admitted.setter
    def admitted(self, v: Optional[np.ndarray]) -> None:
        self._admitted = v

    @property
    def reason(self) -> Optional[np.ndarray]:
        self._materialize()
        return self._reason

    @reason.setter
    def reason(self, v: Optional[np.ndarray]) -> None:
        self._reason = v

    @property
    def wait_ms(self) -> Optional[np.ndarray]:
        self._materialize()
        return self._wait_ms

    @wait_ms.setter
    def wait_ms(self, v: Optional[np.ndarray]) -> None:
        self._wait_ms = v

    @property
    def admitted_count(self) -> int:
        a = self.admitted
        return int(a.sum()) if a is not None else 0


@dataclass
class _BulkExitOp:
    """Columnar group of ``n`` exits/completions on one resource."""

    rows: Tuple[int, int, int, int]
    n: int
    ts: np.ndarray  # int32 [n]
    count: np.ndarray  # int32 [n]
    rt: np.ndarray  # int32 [n]
    err: np.ndarray  # int32 [n]
    thr: int  # -1 exits, 0 traces
    d_gids: List[int] = field(default_factory=list)
    resource: Optional[str] = None
    src_dindex: Optional[object] = None


@dataclass(slots=True)
class _ExitOp:
    ts: int
    rows: Tuple[int, int, int, int]
    count: int = 0  # success delta
    rt: int = 0
    err: int = 0  # exception delta
    thr: int = 0  # thread delta (-1 for exits, 0 for traces)
    d_gids: List[int] = field(default_factory=list)  # breakers to complete
    p_rows: List[int] = field(default_factory=list)  # param thread rows to release
    resource: Optional[str] = None  # for d_gid re-resolution after a reload
    src_dindex: Optional[object] = None


def _rounds_bucket(keys: np.ndarray) -> int:
    """Host-known max items-per-key in a scan batch, bucketed to a
    power of two (so each bucket compiles once) and capped: above 16
    return 0, selecting the sequential lax.scan fallback (one rule
    dominating the batch makes unrolled rounds pointless)."""
    if keys.size == 0:
        return 1
    m = int(np.unique(keys, return_counts=True)[1].max())
    if m > 16:
        return 0
    return 1 if m <= 1 else 1 << (m - 1).bit_length()


# Least run-table length: below it a shorter table saves the kernel
# little, and every length is one more compiled shape.
_RUN_TABLE_MIN = 4096


def _run_table(
    rows: np.ndarray, ts: np.ndarray, live: int, shards: int
) -> Tuple[np.ndarray, int]:
    """The run table of one staged side (entries or exits): the index of
    the last entry of each maximal span of the first ``live`` entries
    that share all four node rows and one ts (a bulk group, a single
    op, or neighbours that happen to match), cut at shard boundaries.
    Each shard's block holds its runs in shard-local indices, then -1;
    the block length is a power of two, at least 1/16 of the shard and
    ``_RUN_TABLE_MIN``, at most the shard — five lengths per shape at
    most. Returns (int32 [shards * block], number of runs)."""
    per = ts.shape[0] // shards
    last = np.zeros(live, dtype=bool)
    if live:
        r64 = rows.view(np.int64)  # [n, 2]: two node rows per compare
        last[:-1] = (
            (ts[1:live] != ts[: live - 1])
            | (r64[1:live, 0] != r64[: live - 1, 0])
            | (r64[1:live, 1] != r64[: live - 1, 1])
        )
        last[per - 1 :: per] = True
        last[-1] = True
    ends = np.flatnonzero(last).astype(np.int32)
    shard = ends // per
    counts = np.bincount(shard, minlength=shards)
    block = min(per, max(_pad_pow2(int(counts.max()), 1), per // 16, _RUN_TABLE_MIN))
    table = np.full((shards, block), -1, dtype=np.int32)
    first = np.cumsum(counts) - counts
    table[shard, np.arange(ends.size) - first[shard]] = ends - shard * per
    return table.reshape(-1), int(ends.size)


def _weighted_rt(gx: "_BulkExitOp") -> int:
    """Count-weighted mean RT for aggregated completion callbacks — an
    unweighted mean would skew extensions that reconstruct total time
    as rt × count. int64 product: rt·count overflows int32 at
    aggregated counts well within bulk range."""
    total = int(gx.count.sum())
    if total <= 0:
        return 0
    return int((gx.rt.astype(np.int64) * gx.count).sum() // total)


def _tally_blocked(
    admitted: np.ndarray,
    reason: np.ndarray,
    acquire: np.ndarray,
    sizes: np.ndarray,
    slot_ok: np.ndarray,
    dslot_ok: np.ndarray,
    has_slots: np.ndarray,
    has_dgids: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
    """A chunk's blocked bulk rows summed per key (group, reason,
    column), over the bulk region's columns (``sizes``: each group's
    rows, in order). A FLOW row of a group with flow slots keys on its
    first failing column of ``slot_ok``, a DEGRADE row of a group with
    breakers on its first of ``dslot_ok``; every other row, and such a
    row with no failing column (a speculative block the device passed),
    keys on the unattributed column ``unattr``, after every slot.

    Returns (group, reason, column, acquire sum) per key that holds a
    row, in key order — groups in submit order, reasons ascending,
    columns ascending — and ``unattr``. Elementwise passes and a dense
    ``bincount``, no sort and no per-group call: O(rows) numpy for any
    number of groups. The float sums are exact: a chunk's int32 weights
    sum far below 2**53."""
    unattr = max(slot_ok.shape[1], dslot_ok.shape[1])
    blocked = ~admitted
    if not blocked.any():
        e = np.zeros(0, dtype=np.int64)
        return e, e, e, e, unattr
    col = np.full(admitted.size, unattr, dtype=np.int32)
    for code, has, ok in (
        (E.BLOCK_FLOW, has_slots, slot_ok),
        (E.BLOCK_DEGRADE, has_dgids, dslot_ok),
    ):
        attr = blocked & (reason == code) & np.repeat(has, sizes)
        if attr.any():
            # Last column first, so the first failing column wins.
            for j in range(ok.shape[1] - 1, -1, -1):
                col = np.where(attr & ~ok[:, j], j, col)
    big = np.iinfo(reason.dtype).max
    r_lo = int(np.min(reason, where=blocked, initial=big))
    nr = int(np.max(reason, where=blocked, initial=r_lo)) - r_lo + 1
    ncol = unattr + 1
    n_keys = sizes.size * nr * ncol
    key = np.repeat(np.arange(0, n_keys, nr * ncol, dtype=np.intp), sizes)
    key += (reason - r_lo) * ncol
    key += col
    # Admitted rows count in one spare bin past the keys.
    key = np.where(blocked, key, n_keys)
    present = np.flatnonzero(np.bincount(key, minlength=n_keys + 1)[:n_keys])
    weight = np.bincount(key, weights=acquire)[present].astype(np.int64)
    grp_r, c = np.divmod(present, ncol)
    g, ri = np.divmod(grp_r, nr)
    return g, ri + r_lo, c, weight, unattr


def release_cluster_tokens(tokens: Sequence[Tuple[object, int]]) -> None:
    """Best-effort release of held cluster concurrency tokens; a failed
    release is covered by the server's resourceTimeout sweep."""
    from sentinel_tpu.utils.record_log import record_log

    for service, token_id in tokens:
        try:
            service.release_concurrent_token(token_id)
        except Exception:
            record_log.warn("[Engine] release of cluster token %d failed", token_id)


# "No argument passed" marker for the cluster-check seams: None is a
# meaningful service value (no cluster role active), so defaulting
# cannot use it.
_SENTINEL = object()


def _is_cluster_param_slot(s) -> bool:
    """A param slot whose admission the cluster token server owns:
    QPS-grade cluster-mode ParamFlowRule with a flow_id."""
    r = s.rule
    return (
        isinstance(r, ParamFlowRule)
        and r.cluster_mode
        and r.grade == C.FLOW_GRADE_QPS
        and r.cluster_config is not None
        and r.cluster_config.flow_id is not None
    )


class _EncodeArena:
    """Reusable host staging buffers for the chunk encode, keyed by
    padded shape — ``_run_chunk`` and ``_encode_param`` rebuild ~25
    pow2-padded numpy arrays per flush, and at steady state the shapes
    repeat, so fresh-allocation page faults dominate the encode.

    Lifecycle safety: ``jnp.asarray`` may be ZERO-COPY on CPU backends
    (a 64-byte-aligned numpy buffer becomes the device buffer itself —
    alignment-dependent, so it cannot be probed away), which means a
    staging buffer must never be mutated while a dispatched computation
    might still read it. Buffers therefore return to the pool only
    AFTER the chunk's device→host result fetch completes SUCCESSFULLY
    (in the chunk record's settle, ``_PendingChunk.settle``, at any
    pipeline depth) — ``jax.device_get`` of the results blocks until
    the computation that read the inputs has finished. A failed or
    interrupted fetch proves nothing, so its staging is dropped to GC
    instead of pooled. Until then the next chunk's
    ``take()`` simply builds fresh buffers (bounded by max_inflight).
    Returned verdict arrays are always fresh copies, never views of
    staging or fetch buffers. Bounded to the ``max_keys`` most recent
    shape keys (and ``per_key`` sets each; both config-driven —
    sentinel.tpu.host.arena.*) so a shape change retires old buffers
    instead of accumulating them. ``ensure_per_key`` raises the
    per-key bound to at least the flush-pipeline depth + 1: every
    in-flight flush pins one staging set per shape key until its fetch
    lands, so an undersized pool would make deep pipelines silently
    fall back to fresh allocations. give() may run from a drain
    thread, hence the lock."""

    def __init__(
        self, max_keys: Optional[int] = None, per_key: Optional[int] = None
    ) -> None:
        self._lock = threading.Lock()
        self._pool: "OrderedDict[tuple, List[tuple]]" = OrderedDict()
        # Running pool hit/miss counters (telemetry): a take() served
        # from the pool is a hit, a fresh build a miss. Monotonic; the
        # flight recorder records per-flush deltas.
        self.hits = 0
        self.misses = 0
        self.max_keys = max(
            1,
            max_keys
            if max_keys is not None
            else config.get_int(config.ARENA_MAX_KEYS, 8),
        )
        self.per_key = max(
            1,
            per_key
            if per_key is not None
            else config.get_int(config.ARENA_PER_KEY, 4),
        )

    def ensure_per_key(self, n: int) -> None:
        """Raise the per-key bound (never shrinks — pooled sets stay)."""
        with self._lock:
            self.per_key = max(self.per_key, int(n))

    def take(self, key: tuple, build):
        """Buffers for ``key``: pooled, or freshly built via
        ``build()``. The caller owns them (and must reset fills — a
        pooled buffer holds a previous chunk's data) until give()."""
        with self._lock:
            sets = self._pool.get(key)
            if sets:
                self.hits += 1
                return sets.pop()
            self.misses += 1
        return build()

    def give(self, key: tuple, bufs: tuple) -> None:
        """Return buffers once the chunk's results have been fetched
        (i.e. the computation that may alias them has completed)."""
        with self._lock:
            sets = self._pool.get(key)
            if sets is None:
                sets = self._pool[key] = []
            self._pool.move_to_end(key)
            if len(sets) < self.per_key:
                sets.append(bufs)
            while len(self._pool) > self.max_keys:
                self._pool.popitem(last=False)

    def give_all(self, staging: List[Tuple[tuple, tuple]]) -> None:
        for key, bufs in staging:
            self.give(key, bufs)


class Engine:
    """Owns device state + host indexes; thread-safe op submission."""

    def __init__(self, clock: Optional[Clock] = None, initial_rows: Optional[int] = None) -> None:
        self.clock = clock or default_clock()
        self.nodes = NodeRegistry()
        rows = _pad_pow2(initial_rows or config.get_int(config.INITIAL_ROWS, 1024))
        self.stats: StatsState = make_stats(rows)
        self.flow_index = FlowIndex([], cold_factor=config.cold_factor)
        self.flow_dyn: FlowRuleDynState = self.flow_index.make_dyn_state()
        self.degrade_index = DegradeIndex([])
        self.degrade_dyn: DegradeDynState = self.degrade_index.make_dyn_state()
        # Host mirror of breaker states for the opt-in state-change
        # observers (rules/breaker_events.py); all-CLOSED on (re)build.
        # Epoch guards stale deferred fetches across rule reloads; seq
        # orders concurrent/out-of-order _PendingChunk fills; validity
        # marks gaps where flushes ran unobserved (resync silently).
        self._breaker_state_host = np.zeros(
            self.degrade_dyn.state.shape[0], dtype=np.int32
        )
        self._breaker_epoch = 0
        self._breaker_seq = 0
        self._breaker_applied_seq = 0
        self._breaker_mirror_valid = True
        self._breaker_mirror_lock = threading.Lock()
        self.param_index = ParamIndex({})
        self.param_dyn: ParamDynState = make_param_state(8)
        self.system_config = None  # rules/system_manager.SystemConfig or None
        self.authority_rules: Dict[str, AuthorityRule] = {}
        self._entries: List[_EntryOp] = []
        self._exits: List[_ExitOp] = []
        self._bulk_entries: List[BulkOp] = []
        self._bulk_exits: List[_BulkExitOp] = []
        # Running totals of pending bulk rows (flush-on-size checks must
        # not re-sum every group per submit).
        self._bulk_pending_n = 0
        self._bulk_exit_pending_n = 0
        # (resource, ctx, origin, entry_type) -> rows tuple | None.
        self._rows_cache: Dict[tuple, Optional[Tuple[int, int, int, int]]] = {}
        # Background flusher (see start_auto_flush).
        self._auto_flush_thread: Optional[threading.Thread] = None
        self._auto_flush_stop: Optional[threading.Event] = None
        self._auto_flush_interval_s: float = 0.0
        self._lock = threading.RLock()
        # Serializes flushes + rule-table swaps; never taken while
        # holding _lock (fixed order _flush_lock → _lock).
        self._flush_lock = threading.RLock()
        self.max_batch = config.get_int(config.FLUSH_MAX_BATCH, 131072)
        # Host-ingest fast path: the encode-buffer arena (None when
        # sentinel.tpu.host.fastpath is off — every flush then builds
        # fresh staging arrays, the differential-smoke reference).
        self._arena: Optional[_EncodeArena] = (
            _EncodeArena() if config.get_bool(config.HOST_FASTPATH, True) else None
        )
        # Engine flight recorder (metrics/telemetry.py): per-flush
        # spans + histograms + blocked-resource top-K — the one
        # per-flush record (last_flush_host_ms reads it). When
        # disabled, no span is recorded and the kernel blocked-weight
        # fold compiles away (blk_topk=0); the profiler phase spans
        # stay.
        self.telemetry = TelemetryBus()
        self._blk_topk_k = (
            self.telemetry.blocked_topk_k if self.telemetry.enabled else 0
        )
        # Admission tracer (metrics/admission_trace.py): sampled
        # per-request verdict provenance. Disabled = one bool read per
        # submit and one None check per op at fill.
        self.admission_trace = AdmissionTracer()
        # Baseline for per-span intern-cache deltas: (weakref to the
        # param_index the totals came from, hits, misses) — a reload
        # swaps the index and resets its counters, so the baseline must
        # follow the IDENTITY. A weakref (not id()): a freed index's id
        # can be reused by its replacement, which would keep a stale
        # baseline; a dead weakref can't lie.
        self._tele_intern_seen: Tuple[Optional[object], int, int] = (None, 0, 0)
        # Deferred fetches from flush_async / the pipelined flush,
        # oldest first. Lock order: _flush_lock → _pending_lock;
        # nothing under _pending_lock takes another engine lock. RLock:
        # a fetch closure reading a lazy property of its own chunk must
        # not self-deadlock.
        self._pending_fetches: "deque[_PendingChunk]" = deque()
        self._pending_lock = threading.RLock()
        self._max_inflight = config.get_int(config.FLUSH_MAX_INFLIGHT, 2)
        # Depth-K flush pipeline (sentinel.tpu.host.pipeline.depth):
        # flush() keeps up to this many dispatched-but-unfetched
        # flushes in flight; 0 = fully synchronous (the differential
        # oracle). Occupancy counters sample the post-trim in-flight
        # depth once per dispatching flush (see pipeline_stats).
        self._pipeline_depth = max(0, config.get_int(config.PIPELINE_DEPTH, 0))
        self._pipe_dispatches = 0
        self._pipe_inflight_sum = 0
        self._resize_arena()
        # Global on/off switch (Constants.ON, flipped by the setSwitch
        # command): when off, entries pass through unchecked + unrecorded.
        self.enabled = True
        # Monotonic flush sequence number: one per dispatched chunk and
        # per failover probe flush — the fault injector's key and the
        # checkpoint cadence counter. Advanced under _flush_lock only.
        self._flush_seq = 0
        # Deterministic fault injector (testing/faults.FaultInjector);
        # None in production — every hook is a single attribute read.
        self.faults = None
        # Device-failure domain (runtime/failover.py): health state
        # machine, flush watchdog, host-fallback admission, checkpoint/
        # restore. Disarmed by default — one attribute read per hook.
        from sentinel_tpu.runtime.failover import FailoverManager

        self.failover = FailoverManager(self)
        # Speculative admission tier (runtime/speculative.py): host
        # mirrors serve the immediate verdict, the device flush settles,
        # reconciliation at each drain bounds the drift. Disabled by
        # default — one attribute read per entry_sync/submit_bulk. When
        # enabled, the failover fallback IS the speculative mirror, so
        # HEALTHY and DEGRADED share one continuously-reconciled host
        # tier (device failure = zero-transition).
        from sentinel_tpu.runtime.speculative import SpeculativeAdmitter

        self.speculative = SpeculativeAdmitter(self)
        if self.speculative.enabled:
            self.failover.fallback = self.speculative.mirror
        # Adapter-edge batch window (runtime/window.py): concurrent
        # per-request admissions coalesce into columnar submit_bulk
        # rides with per-request verdict fan-out. Disarmed by default
        # — one attribute read per adapter entry; constructed BEFORE
        # the valve below so the valve can count queued window
        # contents toward the bulk bound.
        from sentinel_tpu.runtime.window import BatchWindow

        self.ingest_window = BatchWindow(self)
        # Ingest self-protection valve (runtime/ingest.py): bounded
        # pending queues + deadline-aware shedding. Disarmed by default
        # — one attribute read per submit.
        from sentinel_tpu.runtime.ingest import IngestValve

        self.ingest = IngestValve(self)
        # Per-resource provenance ledger (metrics/provenance.py):
        # (submit-ts second, resource) speculative/degraded/shed/drift
        # counts drained by the metric-log timer into MetricNodeLine v2
        # columns and exported as the bounded sentinel_resource_*
        # Prometheus families. Disabled = one bool read per call site.
        from sentinel_tpu.metrics.provenance import ResourceProvenance

        self.resource_metrics = ResourceProvenance()
        # Statistics sketch tier (runtime/sketch.py): fixed-size
        # on-device count-min + candidate table over EVERY key the
        # engine sees, with heavy-hitter promotion to exact dense rows.
        # Disarmed by default — one attribute read per call site; armed,
        # the fold is threaded through the flush kernel and the
        # candidate table rides the coalesced drain fetch.
        from sentinel_tpu.runtime.sketch import SketchTier

        self.sketch = SketchTier(self)
        # Sketch gossip endpoint (cluster/gossip.py): None unless
        # sketch + gossip are both enabled; armed, a listener folds
        # peer count-min frames into the tier and the tier's promotion
        # controller evaluates the fleet view.
        from sentinel_tpu.cluster.gossip import maybe_build_gossip

        self.gossip = maybe_build_gossip(self.sketch)
        # Self-tuning control plane (runtime/autotune.py): closes the
        # telemetry loop on pipeline depth, the batch window, and the
        # closed-form-vs-scan param path. Disabled by default — one
        # attribute read per drain tick and per param-path pick;
        # enabled decisions run off the hot path on the drain tick.
        # Constructed AFTER telemetry/window/valve: it samples all
        # three.
        from sentinel_tpu.runtime.autotune import AutoTuner

        self.autotune = AutoTuner(self)
        # Param-path measurement seam: None (always) in production;
        # "closed"/"scan" pins closed-form-eligible batches to one path
        # (tools/k2probe.py --seed-out times both arms per shape; the
        # scan/counted tests pin attribution with it).
        self.param_force_path: Optional[str] = None
        # True when a close()/stop could not join a worker thread in
        # time — the shutdown LOOKED clean but leaked a live thread.
        self.closed_dirty = False
        # Sharded (multi-chip) mode — see enable_mesh().
        self.mesh = None
        self._sharded_fns: Optional[Dict[Tuple[bool, bool], object]] = None
        self._n_shards = 1
        # Block log (LogSlot → sentinel-block.log); file IO happens only
        # when a blocked verdict is actually aggregated out.
        from sentinel_tpu.metrics.block_log import BlockLogger

        self.block_log = BlockLogger(clock=self.clock)
        # Multi-process ingest plane (sentinel_tpu/ipc): N worker
        # processes feed this engine through shared-memory rings.
        # Disarmed (the default) this attribute is the ENTIRE footprint
        # — no shared memory, no thread, nothing on any hot path.
        self.ipc_plane = None
        if config.get_bool(config.IPC_ENABLED, False):
            from sentinel_tpu.ipc.plane import IngestPlane

            IngestPlane(self)  # registers itself as self.ipc_plane
        # Black-box flight recorder (runtime/capture.py). Disarmed (the
        # default) this attribute is the entire footprint: every hot
        # path pays exactly one `is None` read.
        from sentinel_tpu.runtime.capture import maybe_build_capture

        self.capture = maybe_build_capture(self)
        # Planned-handoff trigger (ipc/supervise.py `_serve`): the
        # `handoff` transport command sets this and the supervised
        # serve loop drains + exits EXIT_HANDOFF so the warm standby
        # takes over. Unsupervised engines never read it.
        self.handoff_requested = threading.Event()

    # ------------------------------------------------------------------
    # multi-chip mode
    # ------------------------------------------------------------------
    def enable_mesh(self, n_devices: Optional[int] = None) -> None:
        """Switch the engine to sharded multi-chip flushing: entries and
        exits are data-parallel over an n-device ``jax.sharding.Mesh``,
        counter windows / breaker state are all-reduced after each local
        step, and flow budgets (incl. occupy borrows) are conserved
        across the mesh by the two-pass grant split (parallel/ici) — the
        deployable cluster unit, ≙ the reference's token server
        (sentinel-cluster-server-default/.../SentinelDefaultTokenServer.
        java:37) collapsed into ICI collectives.

        All four control behaviors plus hot-param rules run on the
        mesh: the serializing per-rule scans (shaping pacers, param
        token buckets) execute once per chip on globally-replicated
        item batches — identical results everywhere, global-stream
        ordering — so their semantics match single-chip exactly
        (parallel/ici._global_shaping_scan / _global_param_scan).
        """
        from sentinel_tpu.parallel import make_mesh

        drained = ([], [])
        try:
            with self._flush_lock:
                self._flush_locked(drained)
                with self._lock:
                    n = n_devices if n_devices is not None else len(jax.devices())
                    if n < 1 or (n & (n - 1)) != 0:
                        raise ValueError(
                            f"mesh size must be a power of two, got {n}"
                        )
                    self.mesh = make_mesh(n)
                    self._n_shards = n
                    self._sharded_fns = {}
                    self._replicate_tables(self.flow_index, self.degrade_index)
        finally:
            self._post_flush(drained)
    def disable_mesh(self) -> None:
        drained = ([], [])
        try:
            with self._flush_lock:
                self._flush_locked(drained)
                with self._lock:
                    self.mesh = None
                    self._sharded_fns = None
                    self._n_shards = 1
                    # Undo the mesh's placement (tables replicated by
                    # _replicate_tables, state written by sharded
                    # flushes): left there, single-chip flushes would
                    # run replicated on every chip of the old mesh.
                    one = jax.devices()[0]
                    for ix in (self.flow_index, self.degrade_index):
                        ix.device = jax.device_put(ix.device, one)
                    (self.stats, self.flow_dyn, self.degrade_dyn,
                     self.param_dyn) = jax.device_put(
                        (self.stats, self.flow_dyn, self.degrade_dyn,
                         self.param_dyn), one)
        finally:
            self._post_flush(drained)

    def _replicate_tables(self, *indexes) -> None:
        """Replicate rule tables over the mesh once per table build: a
        table left on one device is copied to every chip again by each
        sharded flush."""
        from jax.sharding import NamedSharding, PartitionSpec

        where = NamedSharding(self.mesh, PartitionSpec())
        for ix in indexes:
            ix.device = jax.device_put(ix.device, where)

    def retune_second_window(self, sample_count: int, interval_ms: int) -> None:
        """Live retune of the second-window geometry (reference:
        SampleCountProperty.updateSampleCount / IntervalProperty
        .updateInterval — node/SampleCountProperty.java:33-52): every
        node's rolling second counter is rebuilt to the new
        ``sample_count × (interval_ms / sample_count)`` layout and its
        second-window statistics reset cleanly; minute windows and live
        thread gauges carry over. Pending ops are drain-flushed against
        the OLD geometry first, so no batch ever spans two layouts.
        Invalid geometry (sample_count not dividing interval_ms) raises
        without touching state, like the reference ignoring the update.
        """
        drained = ([], [])
        try:
            with self._flush_lock:
                self._flush_locked(drained)
                with self._lock:
                    cur = _ncfg.SECOND_CFG
                    if (
                        cur.sample_count == int(sample_count)
                        and cur.interval_ms == int(interval_ms)
                    ):
                        return
                    _ncfg.set_second_window(sample_count, interval_ms)
                    self.stats = _ncfg.rebuild_second(self.stats)
                    if self._sharded_fns is not None:
                        # Mesh kernels bake the geometry at trace time;
                        # drop them so the next flush re-traces.
                        self._sharded_fns = {}
        finally:
            self._post_flush(drained)
    def _sharded_fn_for(
        self, with_shaping: bool, with_param: bool,
        shaping_rounds: int = 0, param_rounds: int = 0,
    ):
        """Lazily-built sharded kernel variants (like the four single-
        chip jit variants: traffic without shaping/param rules never
        pays for their machinery; the rounds buckets pick the
        vectorized recurrence path for the global scans)."""
        from sentinel_tpu.parallel import make_sharded_flush

        key = (with_shaping, with_param, shaping_rounds, param_rounds)
        fn = self._sharded_fns.get(key)
        if fn is None:
            fn = make_sharded_flush(
                self.mesh,
                occupy_timeout_ms=config.occupy_timeout_ms,
                with_shaping=with_shaping,
                with_param=with_param,
                shaping_rounds=shaping_rounds,
                param_rounds=param_rounds,
            )
            self._sharded_fns[key] = fn
        return fn

    # ------------------------------------------------------------------
    # rule plumbing (called by rule managers)
    # ------------------------------------------------------------------
    def set_flow_rules(self, rules: Sequence[FlowRule]) -> None:
        drained = ([], [])
        try:
            with self._flush_lock:
                self._flush_locked(drained)  # decisions for pending ops use the old rules
                with self._lock:
                    findex = FlowIndex(rules, cold_factor=config.cold_factor)
                    if self.mesh is not None:
                        self._replicate_tables(findex)
                    self.flow_index = findex
                    self.flow_dyn = findex.make_dyn_state()
                self.speculative.on_rules_reloaded()
                if self.capture is not None:
                    self.capture.note_rules(
                        "flow",
                        [r.to_dict() for r in rules],
                        from_sketch=any(
                            getattr(r, "from_sketch", False) for r in rules
                        ),
                    )
        finally:
            self._post_flush(drained)
    def set_degrade_rules(self, rules: Sequence[DegradeRule]) -> None:
        """Breaker state is NOT carried across reloads — the reference
        builds fresh CircuitBreaker objects per load (DegradeRuleManager)."""
        drained = ([], [])
        try:
            with self._flush_lock:
                self._flush_locked(drained)
                with self._lock:
                    self.degrade_index = DegradeIndex(rules)
                    if self.mesh is not None:
                        self._replicate_tables(self.degrade_index)
                    self.degrade_dyn = self.degrade_index.make_dyn_state()
                    self._reset_breaker_mirror()
                self.speculative.on_rules_reloaded()
                if self.capture is not None:
                    self.capture.note_rules(
                        "degrade", [r.to_dict() for r in rules]
                    )
        finally:
            self._post_flush(drained)
    def set_param_rules(self, by_resource: Dict[str, List[ParamFlowRule]]) -> None:
        """Param caches are rebuilt on reload, like
        ParamFlowRuleManager clearing ParameterMetric for changed rules."""
        drained = ([], [])
        try:
            with self._flush_lock:
                self._flush_locked(drained)
                with self._lock:
                    pindex = ParamIndex(by_resource, sketch_tier=self.sketch)
                    self.param_index = pindex
                    self.param_dyn = make_param_state(8)
                self.speculative.on_rules_reloaded()
                if self.capture is not None:
                    rows = [
                        r.to_dict() for rs in by_resource.values() for r in rs
                    ]
                    self.capture.note_rules(
                        "param",
                        rows,
                        from_sketch=any(
                            getattr(r, "from_sketch", False)
                            for rs in by_resource.values()
                            for r in rs
                        ),
                    )
        finally:
            self._post_flush(drained)
    def set_system_config(self, cfg) -> None:
        drained = ([], [])
        try:
            with self._flush_lock:
                self._flush_locked(drained)
                with self._lock:
                    self.system_config = (
                        cfg if cfg is not None and cfg.any_enabled else None
                    )
                    if self.system_config is not None and (
                        self.system_config.highest_system_load >= 0
                        or self.system_config.highest_cpu_usage >= 0
                    ):
                        system_sampler.start()
                if self.capture is not None:
                    from sentinel_tpu.runtime.capture import _system_to_dict

                    self.capture.note_rules(
                        "system", _system_to_dict(self.system_config)
                    )
        finally:
            self._post_flush(drained)
    def set_authority_rules(self, by_resource: Dict[str, AuthorityRule]) -> None:
        drained = ([], [])
        try:
            with self._flush_lock:
                self._flush_locked(drained)
                with self._lock:
                    self.authority_rules = dict(by_resource)
                if self.capture is not None:
                    self.capture.note_rules(
                        "authority",
                        {res: r.to_dict() for res, r in by_resource.items()},
                    )
        finally:
            self._post_flush(drained)
    def _system_device(self) -> SystemDevice:
        cfg = self.system_config
        inf = float("inf")

        def thr(v):
            return float(v) if v is not None and v >= 0 else inf

        if cfg is None:
            return SystemDevice(
                qps=jnp.float32(inf),
                max_thread=jnp.float32(inf),
                max_rt=jnp.float32(inf),
                load_threshold=jnp.float32(-1.0),
                cpu_threshold=jnp.float32(-1.0),
                cur_load=jnp.float32(-1.0),
                cur_cpu=jnp.float32(-1.0),
            )
        cur_load, cur_cpu = system_sampler.read()
        return SystemDevice(
            qps=jnp.float32(thr(cfg.qps)),
            max_thread=jnp.float32(thr(cfg.max_thread)),
            max_rt=jnp.float32(thr(cfg.max_rt)),
            load_threshold=jnp.float32(cfg.highest_system_load),
            cpu_threshold=jnp.float32(cfg.highest_cpu_usage),
            cur_load=jnp.float32(cur_load),
            cur_cpu=jnp.float32(cur_cpu),
        )

    # ------------------------------------------------------------------
    # op submission
    # ------------------------------------------------------------------
    def resolve_entry_rows(
        self, resource: str, context_name: str, origin: str, entry_type: C.EntryType
    ) -> Optional[Tuple[int, int, int, int]]:
        """The NodeSelectorSlot/ClusterBuilderSlot work: rows for the
        default node, cluster node, origin node and global entry node.
        Returns None above the resource cap (pass-through, like
        CtSph.lookProcessChain returning null). Memoized — rows are
        stable once interned; this is the submit hot path. The over-cap
        None is NOT cached: past the cap the registry deliberately
        stops allocating, and caching per unique name would reintroduce
        unbounded growth on exactly the path the cap bounds."""
        key = (resource, context_name, origin, entry_type)
        hit = self._rows_cache.get(key)
        if hit is not None:
            return hit
        crow = self.nodes.cluster_row(resource)
        if crow is None:
            return None
        drow = self.nodes.default_row(resource, context_name)
        orow = self.nodes.origin_row(resource, origin) if origin else None
        erow = self.nodes.entry_node_row if entry_type == C.EntryType.IN else None
        rows = (
            drow if drow is not None else -1,
            crow,
            orow if orow is not None else -1,
            erow if erow is not None else -1,
        )
        self._rows_cache[key] = rows
        return rows

    def submit_entry(
        self,
        resource: str,
        context_name: str = C.CONTEXT_DEFAULT_NAME,
        origin: str = "",
        acquire: int = 1,
        entry_type: C.EntryType = C.EntryType.OUT,
        prio: bool = False,
        ts: Optional[int] = None,
        args: Sequence[object] = (),
        speculate: bool = False,
    ) -> Optional[_EntryOp]:
        """Enqueue an entry op; returns None for pass-through (over cap
        or the global switch being off). ``speculate`` (entry_sync's
        path) asks the speculative tier for an immediate host verdict
        — served while the op is still thread-private, so by the time
        any flush can settle it the speculative verdict is already in
        place and the drain reconciles instead of racing it."""
        if not self.enabled:
            return None
        if self.ingest.armed:
            cause = self.ingest.check_entry(1)
            if cause is not None:
                return self._shed_entry(
                    resource, context_name, origin, acquire, cause
                )
        # Slot resolution happens here against the current tables; if a
        # rule reload swaps any index before this op flushes, the flush
        # re-resolves it against the snapshot it will actually be
        # encoded with (see _flush_locked) — the op records which
        # indexes produced its gids for that check. Submission itself
        # never retries: a retry would re-run the cluster token RPC and
        # double-acquire the global budget.
        with self._lock:
            findex = self.flow_index
            cluster_gids = findex.cluster_gids
            op = self._resolve_entry_locked(
                findex, self.degrade_index, self.param_index,
                resource, context_name, origin, acquire, entry_type, prio,
                ts, tuple(args),
            )
        sk = self.sketch
        if op is None:
            # Over-cap pass-through: the ONE key class the encode path
            # never sees — the sketch tier tracks it anyway (O(1)
            # device state), and a promotion later grants the dense
            # row the cap refused (runtime/sketch.py).
            if sk.armed:
                if sk.cold_armed and sk.cold_blocked(
                    resource, findex, self.param_index
                ):
                    return self._blocked_cold(
                        resource, context_name, origin, acquire
                    )
                sk.note_unrouted(resource, acquire)
            return None
        if sk.cold_armed and sk.cold_blocked(
            resource, findex, self.param_index
        ):
            # Routed but unconfigured (no rule of any kind): the cold
            # ceiling is its ONLY protection — blocked ops are never
            # enqueued, exactly like a valve shed.
            return self._blocked_cold(resource, context_name, origin, acquire)
        if (
            sk.cold_armed
            and op.args
            and self.param_index.sketch_idx_by_resource
            and sk.cold_value_blocked(resource, self.param_index, op.args)
        ):
            # VALUE-grade ceiling: an unpromoted sketch-mode value over
            # its admit-by-estimate ceiling blocks the op — the only
            # protection a cold value has before promotion grants it a
            # dense row (runtime/sketch.py cold_value_blocked).
            return self._blocked_cold(
                resource, context_name, origin, acquire,
                limit_type="cold_value",
            )
        # Trace tag OUTSIDE the lock: the stamp (RNG draw, clock read,
        # contextvar get) doesn't depend on the index snapshot, and the
        # submit path's critical section is the throughput ceiling.
        if self.admission_trace.enabled:
            op.trace = self.admission_trace.make_tag()
        # Cluster-mode rules consult the token service OUTSIDE the engine
        # lock (it may be a network RPC — FlowRuleChecker.passClusterCheck
        # crossing to the token server, FlowRuleChecker.java:168-230).
        if cluster_gids and any(gid in cluster_gids for gid, _ in op.slots):
            self._apply_cluster_checks(op, cluster_gids)
        if op.p_slots and any(
            s.rule is not None and s.rule.cluster_mode for s in op.p_slots
        ):
            self._apply_cluster_param_checks(op)
        if speculate and self.speculative.enabled:
            # Before the append: the op must not be visible to a
            # concurrent flush until its speculative verdict (if any)
            # is installed, or a fill could settle it with a device
            # verdict that try_admit then silently overwrites — an
            # unreconciled mismatch that leaks the concurrency gauge.
            self.speculative.try_admit(op, self.clock.now_ms())
        with self._lock:
            self._entries.append(op)
            over = len(self._entries) >= self.max_batch
        if over:
            self.flush()  # flush-on-size: the pending buffer is bounded
        return op

    def _refused_entry(
        self, resource: str, context_name: str, origin: str, acquire: int,
        reason: int, limit_type: str, provenance: str,
        count_shed: bool,
    ) -> _EntryOp:
        """The ONE home of the never-enqueued refused-entry contract
        (valve sheds AND sketch cold-ceiling blocks): the caller sees
        the same op/verdict surface as any blocked entry, with full
        provenance — a trace record, a block-log row under the
        reason's exception name, nothing on the device and nothing
        queued, so no gauge ever charges. ``count_shed`` routes the
        refusal into the per-resource provenance ledger's shed column
        (the valve's refusals are load shedding; the cold ceiling's
        are policy and stay out of that column)."""
        op = _EntryOp(
            resource=resource, ts=self.clock.now_ms(), acquire=acquire,
            rows=(-1, -1, -1, -1), slots=[],
            context_name=context_name, origin=origin,
        )
        op.verdict = Verdict(
            admitted=False, reason=reason, wait_ms=0,
            blocked_rule=None, limit_type=limit_type,
        )
        tracer = self.admission_trace
        if tracer.enabled:
            tracer.record_admission(
                tracer.make_tag(), resource, origin, context_name,
                False, reason, -1, time.perf_counter(),
                provenance=provenance,
            )
        self.block_log.log_blocked(
            resource, reason, origin=origin, count=acquire
        )
        if count_shed and self.resource_metrics.enabled:
            self.resource_metrics.note(op.ts, resource, shed=acquire)
        return op

    def _shed_entry(
        self, resource: str, context_name: str, origin: str, acquire: int,
        cause: str,
    ) -> _EntryOp:
        """Never-enqueued BLOCK_SHED verdict (runtime/ingest.py tripped
        at submit). Exits/traces are never shed."""
        return self._refused_entry(
            resource, context_name, origin, acquire,
            reason=E.BLOCK_SHED, limit_type=cause, provenance="shed",
            count_shed=True,
        )

    def _blocked_cold(
        self, resource: str, context_name: str, origin: str, acquire: int,
        limit_type: str = "cold",
    ) -> _EntryOp:
        """Never-enqueued sketch cold-ceiling verdict (runtime/
        sketch.py ``cold_blocked``/``cold_value_blocked``; counting
        happened there). ``limit_type`` distinguishes the resource
        ceiling ("cold") from the value ceiling ("cold_value")."""
        return self._refused_entry(
            resource, context_name, origin, acquire,
            reason=E.BLOCK_SKETCH, limit_type=limit_type,
            provenance="sketch_cold", count_shed=False,
        )

    def _refused_bulk(
        self, resource: str, n: int, context_name: str, origin: str,
        acquire, reason: int, provenance: str, count_shed: bool,
    ) -> BulkOp:
        """Bulk analog of :meth:`_refused_entry`: dense all-refused
        arrays, never enqueued (array verdicts carry no limit_type —
        the reason code is the whole attribution, as before)."""
        acq_col = self._bulk_col(acquire, n, 1)
        g = BulkOp(
            resource=resource, n=n,
            ts=np.full(n, self.clock.now_ms(), dtype=np.int32),
            acquire=acq_col, rows=(-1, -1, -1, -1), slots=[], d_gids=[],
            auth_ok=True, context_name=context_name, origin=origin,
        )
        g.admitted = np.zeros(n, dtype=bool)
        g.reason = np.full(n, reason, dtype=np.int32)
        g.wait_ms = np.zeros(n, dtype=np.int32)
        tracer = self.admission_trace
        if tracer.enabled:
            tracer.record_bulk(
                tracer.make_tag(), resource, origin, context_name,
                g._admitted, g._reason, -1, time.perf_counter(),
                provenance=provenance,
            )
        self.block_log.log_blocked(
            resource, reason, origin=origin, count=int(acq_col.sum())
        )
        if count_shed and self.resource_metrics.enabled:
            self.resource_metrics.note(
                int(g.ts[0]), resource, shed=int(acq_col.sum())
            )
        return g

    def _blocked_cold_bulk(
        self, resource: str, n: int, context_name: str, origin: str, acquire
    ) -> BulkOp:
        return self._refused_bulk(
            resource, n, context_name, origin, acquire,
            reason=E.BLOCK_SKETCH, provenance="sketch_cold",
            count_shed=False,
        )

    def _shed_bulk(
        self, resource: str, n: int, context_name: str, origin: str,
        acquire, cause: str,
    ) -> BulkOp:
        return self._refused_bulk(
            resource, n, context_name, origin, acquire,
            reason=E.BLOCK_SHED, provenance="shed", count_shed=True,
        )

    def _resolve_entry_locked(
        self, findex, dindex, pindex, resource, context_name, origin,
        acquire, entry_type, prio, ts, args,
    ) -> Optional[_EntryOp]:
        """Build one resolved (NOT yet enqueued) op against the given
        index snapshot. Caller holds ``self._lock``. The single source
        of resolution truth for submit_entry AND the submit_many fast
        path — any divergence between the two would make semantics
        depend on which path a request happens to take."""
        from sentinel_tpu.rules.authority_manager import AuthorityRuleManager

        rows = self.resolve_entry_rows(resource, context_name, origin, entry_type)
        if rows is None:
            return None
        slots = findex.resolve_slots(resource, context_name, origin, self.nodes)
        auth_ok = True
        arule = self.authority_rules.get(resource)
        if arule is not None:
            auth_ok = AuthorityRuleManager.passes(arule, origin)
        p_slots: List[ParamSlotInfo] = []
        if args and pindex.has_rules():
            p_slots = pindex.slots_for(resource, args)
        return _EntryOp(
            resource=resource,
            ts=self.clock.now_ms() if ts is None else ts,
            acquire=acquire,
            rows=rows,
            slots=slots,
            d_gids=dindex.gids_for(resource),
            p_slots=p_slots,
            auth_ok=auth_ok,
            prio=prio,
            context_name=context_name,
            origin=origin,
            args=args,
            src=(findex, dindex, pindex),
        )

    def submit_many(self, requests: Sequence[Dict]) -> List[Optional[_EntryOp]]:
        """Deferred-mode batch submission: enqueue many entries without
        flushing; verdicts appear on the returned ops after ``flush()``
        (None entries are over-cap pass-throughs). Each request is a
        kwargs dict for :meth:`submit_entry` (``{"resource": ...}`` at
        minimum). Reaching ``max_batch`` triggers an automatic flush of
        the ops queued so far — their verdicts are then already filled.

        This is the public high-throughput path (round-1 #7): the
        batched analog of firing many ``SphU.entry`` calls whose
        decisions tolerate one flush of latency, like the reference's
        cluster token client (FlowRuleChecker.passClusterCheck crossing
        to the token server, FlowRuleChecker.java:168-230).

        Resolution for the whole batch happens under ONE lock
        acquisition (two per op otherwise — measurable at 100k+ ops/s).
        The moment a request needs the token service (cluster-mode flow
        or param rules — RPCs must run OUTSIDE the lock) or the pending
        buffer hits max_batch, the fast path hands the REMAINING
        requests to :meth:`submit_entry`, preserving arrival order
        exactly (already-appended ops stay; the rest append in request
        order).
        """
        if not self.enabled:
            return [None] * len(requests)
        if self.ingest.armed:
            # Whole-batch shed only when the queue is ALREADY saturated
            # or the deadline is unmeetable — a large batch on an idle
            # engine must not shed (flush-on-size drains the queue
            # mid-batch, so only the live depth matters); the fast loop
            # below breaks out at the bound and the per-op fallback
            # path sheds exactly the overflow.
            cause = self.ingest.check_entry(1)
            if cause is not None:
                return [
                    self._shed_entry(
                        req.get("resource", ""),
                        req.get("context_name", C.CONTEXT_DEFAULT_NAME),
                        req.get("origin", ""),
                        req.get("acquire", 1),
                        cause,
                    )
                    for req in requests
                ]
        if self.sketch.cold_armed:
            # The cold-key ceiling must see every resource, and its
            # estimate read takes the sketch lock — route the batch
            # through the per-op path (the ceiling is an opt-in
            # approximate mode; the lock-amortized fast loop stays the
            # default).
            return [self.submit_entry(**req) for req in requests]
        out: List[Optional[_EntryOp]] = []
        resume_at = 0
        over = False
        # Cluster deferral (PR 16): from the first cluster-needing op
        # onward, resolved ops are NOT appended inline — their token
        # RPCs run outside the lock as ONE batched call, then the tail
        # appends in request order (preserving _entries order exactly).
        # Ingest-bounded engines keep the pre-batch per-op remainder:
        # the valve's per-op shed accounting is load-bearing there.
        defer_ok = not (self.ingest.armed and self.ingest.max_pending)
        tail: List[Tuple[_EntryOp, bool]] = []  # (op, needs_cluster)
        with self._lock:
            findex = self.flow_index
            dindex = self.degrade_index
            pindex = self.param_index
            cluster_gids = findex.cluster_gids
            for i, req in enumerate(requests):
                if not req.keys() <= _SUBMIT_ENTRY_KEYS:
                    # Unknown kwargs must raise like submit_entry(**req)
                    # would — hand this one (and the rest) to it.
                    resume_at = i
                    break
                op = self._resolve_entry_locked(
                    findex, dindex, pindex,
                    req["resource"],
                    req.get("context_name", C.CONTEXT_DEFAULT_NAME),
                    req.get("origin", ""),
                    req.get("acquire", 1),
                    req.get("entry_type", C.EntryType.OUT),
                    req.get("prio", False),
                    req.get("ts"),
                    tuple(req.get("args", ())),
                )
                if op is None:
                    if self.sketch.armed:
                        self.sketch.note_unrouted(
                            req["resource"], req.get("acquire", 1)
                        )
                    out.append(None)
                    resume_at = i + 1
                    continue
                needs_cluster = (
                    cluster_gids
                    and any(gid in cluster_gids for gid, _ in op.slots)
                ) or any(
                    s.rule is not None and s.rule.cluster_mode for s in op.p_slots
                )
                if needs_cluster and not defer_ok:
                    # Token-service RPCs happen outside the lock: the
                    # resolved op is DISCARDED (it holds no state) and
                    # this request re-resolves through submit_entry.
                    resume_at = i
                    break
                if needs_cluster or tail:
                    out.append(op)
                    tail.append((op, bool(needs_cluster)))
                    resume_at = i + 1
                    if len(self._entries) + len(tail) >= self.max_batch:
                        over = True
                        break
                    continue
                if (
                    self.ingest.armed
                    and self.ingest.max_pending
                    and len(self._entries) + 1 > self.ingest.max_pending
                ):
                    # Ingest bound hit mid-batch: the resolved op is
                    # discarded (it holds no state) and the remainder
                    # routes through submit_entry, whose valve sheds
                    # per op.
                    resume_at = i
                    break
                self._entries.append(op)
                out.append(op)
                resume_at = i + 1
                if len(self._entries) >= self.max_batch:
                    over = True
                    break
        # Trace tags OUTSIDE the lock (see submit_entry) and BEFORE the
        # flush-on-size below, so the flush's verdict fill consumes
        # them. A concurrent flush racing this window may fill first
        # and miss the tag — best-effort sampling, never a wrong record.
        tracer = self.admission_trace
        if tracer.enabled:
            for op in out:
                if op is not None:
                    op.trace = tracer.make_tag()
        if tail:
            # ONE batched token RPC for the whole tail's cluster needs
            # (outside the lock), then append in request order.
            pending = [(op, cluster_gids) for op, needs in tail if needs]
            if pending:
                self._apply_cluster_checks_bulk(pending)
            with self._lock:
                for op, _needs in tail:
                    self._entries.append(op)
                if len(self._entries) >= self.max_batch:
                    over = True
        if over:
            self.flush()  # flush-on-size, same as submit_entry
        # Remainder (unknown-kwargs request onward, ingest-bounded
        # cluster op, or post-flush): the per-op path keeps
        # RPC-outside-lock + flush-on-size semantics and appends in
        # request order.
        for req in requests[resume_at:]:
            out.append(self.submit_entry(**req))
        return out

    @staticmethod
    def _cluster_token_service():
        """The active token service for this node's cluster role:
        remote client (TokenClientProvider) or the embedded server's
        in-process service — FlowRuleChecker.pickClusterService
        (FlowRuleChecker.java:232-241)."""
        from sentinel_tpu.cluster.state import (
            ClusterStateManager,
            EmbeddedClusterTokenServerProvider,
            TokenClientProvider,
        )

        if ClusterStateManager.is_client():
            return TokenClientProvider.get_client()
        if ClusterStateManager.is_server():
            server = EmbeddedClusterTokenServerProvider.get_server()
            return getattr(server, "service", server)
        return None

    def _apply_cluster_checks(
        self,
        op: _EntryOp,
        cluster_gids,
        service=_SENTINEL,
        prefetched: Optional[Dict[int, object]] = None,
        wait: Optional[List[int]] = None,
    ) -> None:
        """applyTokenResult (FlowRuleChecker.java:207-230): OK → pass
        (drop the local slot), SHOULD_WAIT → pace then pass, BLOCKED →
        block, anything else → fallback to local checking when the rule
        allows it, else pass.

        ``prefetched`` (the bulk seam) maps gid → TokenResult already
        obtained in a batched RPC, so no per-op round trip happens
        here; THREAD-grade held tokens always acquire per op.
        ``wait`` is a shared one-cell accumulator of SHOULD_WAIT
        milliseconds — when None (per-op callers) this op settles its
        own wait before returning; the bulk driver passes one cell for
        the whole op batch and settles once, so waits bound by
        cluster.wait.cap.ms instead of sleeping per op back-to-back."""
        from sentinel_tpu.models import constants as _C

        if service is _SENTINEL:
            service = self._cluster_token_service()
        own_wait = wait is None
        if own_wait:
            wait = [0]
        kept = []
        decided = set()
        for gid, crow in op.slots:
            rule = cluster_gids.get(gid)
            if rule is None:
                kept.append((gid, crow))
                continue
            cc = rule.cluster_config
            if service is None:
                if cc.fallback_to_local_when_fail:
                    kept.append((gid, crow))
                continue
            if rule.grade == C.FLOW_GRADE_THREAD:
                # Cluster concurrency: a HELD token (acquire/release)
                # rather than a windowed QPS grant —
                # ConcurrentClusterFlowChecker.acquireConcurrentToken.
                try:
                    result = service.request_concurrent_token(cc.flow_id, op.acquire)
                except Exception:
                    result = None
                status = (
                    result.status if result is not None else _C.TokenResultStatus.FAIL
                )
                if status == _C.TokenResultStatus.OK:
                    op.cluster_tokens.append((service, result.token_id))
                    decided.add(cc.flow_id)
                    continue
                if status == _C.TokenResultStatus.BLOCKED:
                    op.cluster_blocked_rule = rule
                    decided.add(cc.flow_id)
                    continue
                if cc.fallback_to_local_when_fail:
                    kept.append((gid, crow))
                continue
            if prefetched is not None and gid in prefetched:
                result = prefetched[gid]
            else:
                try:
                    result = service.request_token(cc.flow_id, op.acquire, op.prio)
                except Exception:
                    result = None
            status = result.status if result is not None else _C.TokenResultStatus.FAIL
            if status == _C.TokenResultStatus.OK:
                decided.add(cc.flow_id)
                continue  # token granted: rule passes
            if status == _C.TokenResultStatus.SHOULD_WAIT:
                wait[0] += int(result.wait_in_ms)
                decided.add(cc.flow_id)
                continue
            if status == _C.TokenResultStatus.BLOCKED:
                op.cluster_blocked_rule = rule
                decided.add(cc.flow_id)
                continue
            # FAIL / NO_RULE_EXISTS / TOO_MANY_REQUEST / BAD_REQUEST ...
            if cc.fallback_to_local_when_fail:
                kept.append((gid, crow))
        op.slots = kept
        op.token_decided_flow_ids = op.token_decided_flow_ids | frozenset(decided)
        if own_wait:
            self._settle_cluster_wait(wait)

    def _settle_cluster_wait(self, wait: List[int]) -> None:
        """Pay the accumulated SHOULD_WAIT pacing ONCE, bounded by
        sentinel.tpu.cluster.wait.cap.ms (overflow is forfeited — a
        pathological batch must not stall the submit path for the sum
        of its per-op waits), counted in cluster_wait_ms telemetry."""
        total = wait[0]
        if total <= 0:
            return
        cap = config.get_int(config.CLUSTER_WAIT_CAP_MS, 1000)
        slept = min(total, cap) if cap > 0 else total
        if slept > 0:
            self.clock.sleep_ms(slept)
        if self.telemetry.enabled:
            self.telemetry.note_cluster_wait(slept)

    @staticmethod
    def _cluster_param_groups(op: _EntryOp) -> Dict[int, Tuple[object, List[str]]]:
        """flow_id → (rule, values) for the op's cluster-mode QPS param
        slots (the unit of a request_param_token call)."""
        groups: Dict[int, Tuple[object, List[str]]] = {}
        for s in op.p_slots:
            if _is_cluster_param_slot(s):
                fid = int(s.rule.cluster_config.flow_id)
                if fid not in groups:
                    groups[fid] = (s.rule, [])
                groups[fid][1].append(s.value_key)
        return groups

    def _apply_cluster_checks_bulk(self, pending: List[Tuple[_EntryOp, Dict]]) -> None:
        """The bulk seam: resolve every cluster verdict of an op batch
        with ONE batched token RPC per frame kind (flow + param),
        issued OUTSIDE the engine lock, then apply per-op results
        through the same mapping as the per-op path. SHOULD_WAIT
        pacing accumulates across the batch and settles once, bounded
        (the per-op path slept serially per op). THREAD-grade held
        tokens stay per-op inside _apply_cluster_checks — a held token
        needs its own token_id lifecycle."""
        service = self._cluster_token_service()
        wait = [0]
        flow_rows: List[Tuple[int, int, bool]] = []
        flow_refs: List[Tuple[int, int]] = []  # (pending idx, gid)
        param_rows: List[Tuple[int, int, List[str]]] = []
        param_refs: List[Tuple[int, int]] = []  # (pending idx, flow_id)
        if service is not None:
            for oi, (op, gids) in enumerate(pending):
                for gid, _crow in op.slots:
                    rule = gids.get(gid)
                    if rule is None or rule.grade == C.FLOW_GRADE_THREAD:
                        continue
                    flow_rows.append(
                        (int(rule.cluster_config.flow_id), op.acquire, op.prio)
                    )
                    flow_refs.append((oi, gid))
                for fid, (_rule, values) in self._cluster_param_groups(op).items():
                    param_rows.append((fid, op.acquire, values))
                    param_refs.append((oi, fid))
        flow_pre: List[Dict[int, object]] = [{} for _ in pending]
        param_pre: List[Dict[int, object]] = [{} for _ in pending]
        if flow_rows:
            try:
                results = service.request_tokens_batch(flow_rows)
            except Exception:
                results = [None] * len(flow_rows)
            for (oi, gid), r in zip(flow_refs, results):
                flow_pre[oi][gid] = r
        if param_rows:
            try:
                presults = service.request_param_tokens_batch(param_rows)
            except Exception:
                presults = [None] * len(param_rows)
            for (oi, fid), r in zip(param_refs, presults):
                param_pre[oi][fid] = r
        for oi, (op, gids) in enumerate(pending):
            if gids and any(gid in gids for gid, _ in op.slots):
                self._apply_cluster_checks(
                    op, gids, service=service,
                    prefetched=flow_pre[oi], wait=wait,
                )
            if op.p_slots and any(
                s.rule is not None and s.rule.cluster_mode for s in op.p_slots
            ):
                self._apply_cluster_param_checks(
                    op, service=service, prefetched=param_pre[oi]
                )
        self._settle_cluster_wait(wait)

    def _apply_cluster_param_checks(
        self,
        op: _EntryOp,
        service=_SENTINEL,
        prefetched: Optional[Dict[int, object]] = None,
    ) -> None:
        """Cluster-mode hot-param admission (ParamFlowChecker.passCheck
        cluster branch, ParamFlowChecker.java:46-80): QPS-grade rules
        with ``cluster_mode`` consult the token server per entry with
        the entry's extracted param values
        (ClusterParamFlowChecker.acquireClusterToken on the server side,
        ClusterParamFlowChecker.java:40-100); THREAD-grade stays local
        like the reference. OK → drop the local slots (token granted),
        BLOCKED → block the op, FAIL/no-service → fallback to local
        checking when the rule allows it, else pass. ``prefetched``
        (the bulk seam) maps flow_id → TokenResult from a batched RPC."""
        from sentinel_tpu.models import constants as _C

        groups = self._cluster_param_groups(op)
        if not groups:
            return
        if service is _SENTINEL:
            service = self._cluster_token_service()
        decided = set()
        fallback_fids = set()
        for fid, (rule, values) in groups.items():
            cc = rule.cluster_config
            if service is None:
                if cc.fallback_to_local_when_fail:
                    fallback_fids.add(fid)
                else:
                    decided.add(fid)
                continue
            if prefetched is not None and fid in prefetched:
                result = prefetched[fid]
            else:
                try:
                    result = service.request_param_token(fid, op.acquire, values)
                except Exception:
                    result = None
            status = result.status if result is not None else _C.TokenResultStatus.FAIL
            if status == _C.TokenResultStatus.OK:
                decided.add(fid)
            elif status == _C.TokenResultStatus.BLOCKED:
                op.cluster_blocked_rule = rule
                decided.add(fid)
            elif cc.fallback_to_local_when_fail:
                fallback_fids.add(fid)
            else:
                decided.add(fid)
        # Token-decided (and non-fallback failed) rules must not also be
        # checked locally; fallback rules keep their local slots.
        op.p_slots = [
            s
            for s in op.p_slots
            if not _is_cluster_param_slot(s)
            or int(s.rule.cluster_config.flow_id) in fallback_fids
        ]
        op.token_decided_flow_ids = op.token_decided_flow_ids | frozenset(decided)

    def submit_exit(
        self,
        rows: Tuple[int, int, int, int],
        rt: int,
        count: int = 1,
        err: int = 0,
        ts: Optional[int] = None,
        resource: Optional[str] = None,
        param_rows: Sequence[int] = (),
        cluster_tokens: Sequence[Tuple[object, int]] = (),
        speculative: Optional[bool] = None,
    ) -> None:
        """StatisticSlot.exit: success + RT + thread release (+exception).

        ``speculative`` marks whether the exiting entry's admit was
        charged to the host mirror (None = unknown, treated as yes for
        the mirror release): the tier's live THREAD counter counts its
        own speculative admits AND degraded-fill admits on a persistent
        mirror, so pass False only for entries known to be
        device-decided (verdict.speculative and verdict.degraded both
        False) — a device-path entry's exit must not decrement it.

        ``resource`` routes the completion to the resource's circuit
        breakers (DegradeSlot.exit → onRequestComplete), resolved against
        the degrade rules active at exit time, like the reference.
        ``param_rows`` are per-value thread-gauge rows to release.
        ``cluster_tokens`` are held cluster concurrency tokens
        (``op.cluster_tokens`` from the admitted entry) — deferred-mode
        callers must pass them here (or call
        :func:`release_cluster_tokens` themselves) or the global
        concurrency gauge stays pinned until the server's
        resourceTimeout sweep.
        """
        with self._lock:
            dindex = self.degrade_index
            d_gids = dindex.gids_for(resource) if resource is not None else []
            op = _ExitOp(
                ts=self.clock.now_ms() if ts is None else ts,
                rows=rows,
                count=count,
                rt=min(int(rt), config.statistic_max_rt),
                err=err,
                thr=-1,
                d_gids=d_gids,
                p_rows=list(param_rows),
                resource=resource,
                src_dindex=dindex if resource is not None else None,
            )
            self._exits.append(op)
            over = len(self._exits) >= self.max_batch
        if cluster_tokens:
            release_cluster_tokens(cluster_tokens)
        spec = self.speculative
        if spec.enabled:
            # The live THREAD mirror releases synchronously — host
            # concurrency must track real callers, not settle lag.
            # Entries known to be device-decided (speculative=False)
            # were never counted by the mirror, so they don't release
            # it either; the counter clamps at zero regardless. The
            # rows/rt/count ride along for the host system gate's
            # global concurrency + RT window (inbound rows only).
            if resource is not None and speculative is not False:
                # op.rt, not the caller's raw rt: the device clamps at
                # statistic_max_rt, and the host RT window must see the
                # same sample or one outlier rt blows the avg-RT gate.
                spec.on_exit(resource, 1, rows=rows, rt=op.rt, count=count,
                             now_ms=op.ts)
            self._spec_maybe_settle()
        if over:
            self.flush()

    @staticmethod
    def _bulk_col(v, n: int, default: int) -> np.ndarray:
        """Broadcast a scalar / validate an array into an int32 [n]
        column. Always a fresh OWNED buffer: the engine mutates these in
        place (RT clamp, epoch rebase), and aliasing a caller's array —
        or one caller's array shared across groups — would corrupt it."""
        if v is None:
            return np.full(n, default, dtype=np.int32)
        src = np.asarray(v)
        if src.dtype.kind not in "iub":
            # np.array(v, int32) would silently truncate 1.9 -> 1; a
            # float ts/acquire column is a caller bug that must fail as
            # loudly as a shape mismatch does.
            raise TypeError(
                f"bulk column dtype {src.dtype} is not integral; "
                "pass int values (ms timestamps, counts)"
            )
        info = np.iinfo(np.int32)
        if src.size and (src.min() < info.min or src.max() > info.max):
            # astype would silently wrap (absolute epoch-ms is the
            # classic case — the engine clock is relative int32 ms).
            raise OverflowError(
                "bulk column value out of int32 range; pass relative-ms "
                "timestamps (engine clock), not absolute epoch ms"
            )
        a = src.astype(np.int32, copy=True)
        if a.ndim == 0:
            return np.full(n, int(a), dtype=np.int32)
        if a.shape != (n,):
            raise ValueError(f"bulk column shape {a.shape} != ({n},)")
        return a

    @staticmethod
    def _bulk_param_cols(
        pindex: ParamIndex, resource: str, args_column: Sequence
    ) -> List[_BulkParamCols]:
        """Resolve a per-entry args column to per-rule columns
        (ParamIndex.bulk_cols: distinct values intern once, per-request
        assignment is a numpy gather). QPS grade only: THREAD-grade
        needs per-entry exit bookkeeping, cluster-mode needs a token RPC
        per entry, and collection values need per-entry expansion — all
        three raise toward :meth:`submit_many`. ``args_column`` is
        either per-entry args tuples or an :class:`ArgsColumns` of
        pre-split value columns (the tuple-free adapter path)."""
        for _, r in pindex.by_resource.get(resource, ()):
            if r.grade == C.FLOW_GRADE_THREAD:
                raise ValueError(
                    "submit_bulk: THREAD-grade param rules need per-entry"
                    " exits — use submit_many"
                )
            if r.cluster_mode:
                raise ValueError(
                    "submit_bulk: resource has cluster-mode param rules"
                    " (the token-service RPC is per entry) — use submit_many"
                )
        cols = pindex.bulk_cols(resource, args_column)
        if cols is None:
            raise ValueError(
                "submit_bulk: collection param values expand per entry —"
                " use submit_many"
            )
        return [
            _BulkParamCols(rule=r, valid=valid, prow=prow, token_count=tc,
                           cost_ms=cost)
            for r, valid, prow, tc, cost in cols
        ]

    def submit_bulk(
        self,
        resource: str,
        n: int,
        ts=None,
        acquire=1,
        context_name: str = C.CONTEXT_DEFAULT_NAME,
        origin: str = "",
        entry_type: C.EntryType = C.EntryType.OUT,
        args_column: Optional[Sequence] = None,
    ) -> Optional[BulkOp]:
        """Enqueue ``n`` entries on one resource as a single columnar
        group — the high-throughput path: slot resolution happens once
        for the group, encoding is numpy slicing, and verdicts come
        back as arrays on the returned :class:`BulkOp` after
        ``flush()``. ``ts``/``acquire`` may be scalars or [n] arrays.

        ``args_column`` (length ``n``, one args tuple per entry — e.g. a
        column of client IPs) enables QPS-grade hot-param rules on this
        path: distinct values intern once and each entry gets its own
        per-value verdict, the columnar ParamFlowChecker analog.

        Not supported on this path (use :meth:`submit_entry` /
        :meth:`submit_many`): prioritized (occupy) entries, THREAD-grade
        param rules, and cluster-mode rules (those need per-entry token
        verdicts — raises ``ValueError``; submit_many resolves a whole
        batch's verdicts with one batched token RPC).
        Returns None for pass-through (over the resource cap or the
        global switch off), like :meth:`submit_entry`.
        """
        if not self.enabled:
            return None
        # The submit's own work is the ``sentinel.submit`` phase; the
        # flush a full batch triggers runs after it, as its sibling.
        seq = self._flush_seq + 1
        with self.telemetry.phase("sentinel.submit", flush=seq, n=n):
            if n < 1:
                raise ValueError("submit_bulk: n must be >= 1")
            if n > self.max_batch:
                raise ValueError(
                    f"submit_bulk: n={n} exceeds max_batch={self.max_batch}; split the group"
                )
            if self.ingest.armed:
                cause = self.ingest.check_bulk(n)
                if cause is not None:
                    return self._shed_bulk(
                        resource, n, context_name, origin, acquire, cause
                    )
            sk = self.sketch
            if sk.cold_armed and sk.cold_blocked(
                resource, self.flow_index, self.param_index, n=n
            ):
                # Cold-key ceiling (runtime/sketch.py): covers both the
                # over-cap and the routed-but-unconfigured group classes
                # before any state is touched.
                return self._blocked_cold_bulk(
                    resource, n, context_name, origin, acquire
                )
            if (
                sk.cold_armed
                and args_column is not None
                and self.param_index.sketch_idx_by_resource
            ):
                # VALUE-grade cold ceiling over the group's args column: a
                # fully-blocked group refuses dense (never enqueued); a
                # PARTIALLY blocked group needs per-row verdicts, which is
                # per-entry routing — decline like the other bulk-refusing
                # rule classes so the columnar spine's ValueError fallback
                # re-routes through submit_entry on the same flush.
                vmask = sk.cold_value_mask(
                    resource, self.param_index, args_column, n
                )
                if vmask is not None:
                    if bool(vmask.all()):
                        # Row-weighted, matching cold_blocked's bulk count.
                        sk.note_cold_value_rows(n)
                        return self._refused_bulk(
                            resource, n, context_name, origin, acquire,
                            reason=E.BLOCK_SKETCH, provenance="sketch_cold",
                            count_shed=False,
                        )
                    raise ValueError(
                        "submit_bulk: sketch cold-value ceiling needs "
                        "per-entry verdicts on this group — use submit_many"
                    )
            with self._lock:
                findex = self.flow_index
                dindex = self.degrade_index
                rows = self.resolve_entry_rows(resource, context_name, origin, entry_type)
                if rows is None:
                    if self.sketch.armed:
                        acq = self._bulk_col(acquire, n, 1)
                        self.sketch.note_unrouted(resource, int(acq.sum()))
                    return None
                slots = findex.resolve_slots(resource, context_name, origin, self.nodes)
                if findex.cluster_gids and any(
                    gid in findex.cluster_gids for gid, _ in slots
                ):
                    raise ValueError(
                        "submit_bulk: resource has cluster-mode flow rules "
                        "(token verdicts are per entry) — use submit_many, "
                        "which resolves them with one batched token RPC"
                    )
                auth_ok = True
                arule = self.authority_rules.get(resource)
                if arule is not None:
                    from sentinel_tpu.rules.authority_manager import AuthorityRuleManager

                    auth_ok = AuthorityRuleManager.passes(arule, origin)
                p_cols: List[_BulkParamCols] = []
                if args_column is not None:
                    if len(args_column) != n:
                        raise ValueError(
                            f"submit_bulk: args_column length {len(args_column)}"
                            f" != n={n}"
                        )
                    if self.param_index.has_rules():
                        with self.telemetry.phase(
                            "sentinel.submit.param", flush=seq, n=n
                        ):
                            p_cols = self._bulk_param_cols(
                                self.param_index, resource, args_column
                            )
                now = self.clock.now_ms()
                op = BulkOp(
                    resource=resource,
                    n=n,
                    ts=self._bulk_col(ts, n, now),
                    acquire=self._bulk_col(acquire, n, 1),
                    rows=rows,
                    slots=slots,
                    d_gids=dindex.gids_for(resource),
                    auth_ok=auth_ok,
                    context_name=context_name,
                    origin=origin,
                    src=(findex, dindex, self.param_index),
                    args_column=args_column,
                    p_cols=p_cols,
                )
            # One group-level trace tag, stamped outside the lock (see
            # submit_entry) while the group is still thread-private.
            if self.admission_trace.enabled:
                op.trace = self.admission_trace.make_tag()
            spec = self.speculative
            speculated = False
            if spec.enabled:
                # Immediate speculative array verdicts BEFORE the append:
                # the group still rides the flush below for settlement +
                # reconcile, and it must not be visible to a concurrent
                # flush until the speculative arrays are installed (a fill
                # settling it first would be silently overwritten — an
                # unreconciled mismatch).
                speculated = spec.try_admit_bulk(op, self.clock.now_ms())
            with self._lock:
                self._bulk_entries.append(op)
                self._bulk_pending_n += n
                over = len(self._entries) + self._bulk_pending_n >= self.max_batch
        if over:
            self.flush()
        elif speculated:
            self._spec_maybe_settle()
        return op

    def submit_exit_bulk(
        self,
        rows: Tuple[int, int, int, int],
        n: int,
        rt=0,
        count=1,
        err=0,
        ts=None,
        resource: Optional[str] = None,
        speculative: Optional[bool] = None,
    ) -> None:
        """Columnar exits: ``n`` completions on one node-row set in one
        group (success + RT + thread release; breaker completions when
        ``resource`` is given). Scalars broadcast; arrays are per-exit.

        ``speculative`` follows :meth:`submit_exit`: None (unknown) is
        treated as yes for the speculative tier's live THREAD mirror —
        admit_bulk charged the mirror one per admitted row, so the
        exits must release it synchronously or bulk THREAD headroom
        ratchets down until the fast tier wrongly blocks everything.
        Pass False for groups known to be device-decided.
        """
        with self.telemetry.phase(
            "sentinel.submit", flush=self._flush_seq + 1, n=n
        ):
            if n < 1:
                raise ValueError("submit_exit_bulk: n must be >= 1")
            if n > self.max_batch:
                raise ValueError(
                    f"submit_exit_bulk: n={n} exceeds max_batch={self.max_batch}; split the group"
                )
            with self._lock:
                dindex = self.degrade_index
                now = self.clock.now_ms()
                rt_col = self._bulk_col(rt, n, 0)
                np.minimum(rt_col, config.statistic_max_rt, out=rt_col)
                op = _BulkExitOp(
                    rows=rows,
                    n=n,
                    ts=self._bulk_col(ts, n, now),
                    count=self._bulk_col(count, n, 1),
                    rt=rt_col,
                    err=self._bulk_col(err, n, 0),
                    thr=-1,
                    d_gids=dindex.gids_for(resource) if resource is not None else [],
                    resource=resource,
                    src_dindex=dindex if resource is not None else None,
                )
                self._bulk_exits.append(op)
                self._bulk_exit_pending_n += n
                over = len(self._exits) + self._bulk_exit_pending_n >= self.max_batch
            spec = self.speculative
            if spec.enabled and resource is not None and speculative is not False:
                # Bulk analog of submit_exit's synchronous mirror release
                # (the counter clamps at zero for device-decided groups
                # whose admits were never mirror-charged).
                spec.on_exit(
                    resource, n, rows=rows, rt=int(op.rt.sum()),
                    count=int(op.count.sum()), now_ms=now,
                    min_rt=int(op.rt.min()),
                )
        if spec.enabled:
            self._spec_maybe_settle()
        if over:
            self.flush()

    def _submit_gauge_comp(self, rows: Tuple[int, int, int, int], thr: int) -> None:
        """Enqueue one thread-gauge compensation op (±thr at ``rows``)
        from the speculative reconciler: a speculatively-admitted
        caller the device blocked IS running (+1 now, its exit's −1
        comes later); a speculatively-blocked one the device admitted
        never ran (−1, no exit will follow). count/rt/err are all 0 —
        the kernel's min-RT sample is gated on count>0, so the
        compensation touches ONLY the concurrency gauge."""
        if thr == 0:
            return
        op = _ExitOp(ts=self.clock.now_ms(), rows=rows, count=0, rt=0,
                     err=0, thr=int(thr))
        with self._lock:
            self._exits.append(op)

    def submit_trace(
        self, rows: Tuple[int, int, int, int], count: int = 1, ts: Optional[int] = None
    ) -> None:
        """Tracer-style direct exception recording (no thread/success)."""
        op = _ExitOp(
            ts=self.clock.now_ms() if ts is None else ts,
            rows=rows,
            count=0,
            rt=0,
            err=count,
            thr=0,
        )
        with self._lock:
            self._exits.append(op)
            over = len(self._exits) >= self.max_batch
        if over:
            self.flush()

    # ------------------------------------------------------------------
    # flushing
    # ------------------------------------------------------------------
    # Rebase when less than ~2 days of int32-ms headroom remain.
    REBASE_HEADROOM_MS = 2 * 24 * 3600 * 1000

    def _maybe_rebase(self) -> None:
        """Shift the relative-ms epoch forward before int32 overflow.

        Device timestamps are int32 ms since the clock epoch (see
        utils/clock.py); after ~22 days the epoch is re-anchored and all
        stored window starts / shaping timestamps shift accordingly.
        Runs under the engine lock from flush().
        """
        clock = self.clock
        if not isinstance(clock, SystemClock):
            return
        if clock.rebase_headroom_ms() > self.REBASE_HEADROOM_MS:
            return
        offset = clock.rebase()
        if offset <= 0:
            return
        self._apply_rebase(offset)

    def _apply_rebase(self, offset: int) -> None:
        """Shift every stored absolute-ms tensor by ``offset``. Every
        dyn-state family holding timestamps must appear here — a missed
        one wedges after the ~22-day rebase (e.g. an OPEN breaker whose
        next_retry lands 22 days in the future).

        ``offset`` must be a multiple of SystemClock.REBASE_GRANULARITY_MS
        (rebase() guarantees it): window bucket indices are
        (ts // window_len) % n, so an unaligned shift would remap or
        reset every live bucket.
        """
        assert offset % SystemClock.REBASE_GRANULARITY_MS == 0, (
            f"rebase offset {offset} not aligned to window grids"
        )

        self.stats, self.flow_dyn, self.degrade_dyn, self.param_dyn = (
            self._shift_states(
                self.stats, self.flow_dyn, self.degrade_dyn, self.param_dyn,
                offset,
            )
        )
        for op in self._entries:
            op.ts = max(op.ts - offset, 0)
        for op in self._exits:
            op.ts = max(op.ts - offset, 0)
        for g in self._bulk_entries:
            np.maximum(g.ts - offset, 0, out=g.ts)
        for g in self._bulk_exits:
            np.maximum(g.ts - offset, 0, out=g.ts)
        if self.sketch.armed:
            self.sketch.on_rebase(offset)

    def _shift_states(self, stats, flow_dyn, degrade_dyn, param_dyn, offset):
        """Shift every absolute-ms tensor in one state family set by
        ``offset`` — the single home of the ``shift_ws`` timestamp
        machinery, shared by the ~22-day epoch rebase
        (:meth:`_apply_rebase`) and the failover checkpoint restore
        (runtime/failover.py re-bases a checkpoint captured before a
        rebase into the current epoch)."""

        def shift_ws(ws, floor):
            return jnp.maximum(ws - jnp.int32(offset), jnp.int32(floor))

        stats = stats._replace(
            second=stats.second._replace(
                window_start=shift_ws(stats.second.window_start, _ncfg.SECOND_CFG.empty_ws)
            ),
            minute=stats.minute._replace(
                window_start=shift_ws(stats.minute.window_start, MINUTE_CFG.empty_ws)
            ),
            future_ws=shift_ws(stats.future_ws, _ncfg.SECOND_CFG.empty_ws),
        )
        flow_dyn = flow_dyn._replace(
            latest_passed_time=shift_ws(flow_dyn.latest_passed_time, -(10**9)),
            last_filled_time=shift_ws(flow_dyn.last_filled_time, -(10**9)),
        )
        # Breakers: an OPEN breaker's retry deadline and the current
        # window anchor are absolute ms and must shift too — otherwise a
        # rebase leaves next_retry ~epoch-width in the future (resource
        # stuck OPEN with no probes) and every exit looks older than ws.
        # A breaker's statIntervalMs is per-rule and need not divide the
        # rebase granularity, so the shifted ws is floor-aligned to each
        # rule's own grid (exits compute aligned = ts - ts % interval;
        # an off-grid ws would drop or wedge the live window). This can
        # stretch the in-progress window by < interval once per ~22
        # days — counts are kept, never lost.
        ws_floor = -(10**9)
        iv = jnp.maximum(self.degrade_index.device.interval_ms, 1)
        ws_shifted = shift_ws(degrade_dyn.ws, ws_floor)
        ws_aligned = jnp.where(
            ws_shifted > jnp.int32(ws_floor), ws_shifted - ws_shifted % iv, ws_shifted
        )
        degrade_dyn = degrade_dyn._replace(
            next_retry=shift_ws(degrade_dyn.next_retry, ws_floor),
            ws=ws_aligned,
        )
        # Hot-param token buckets / pacers (PARAM_NEVER marks "no state
        # yet" and must stay put).
        from sentinel_tpu.rules.param_table import PARAM_NEVER

        param_dyn = param_dyn._replace(
            last_add=shift_ws(param_dyn.last_add, PARAM_NEVER),
            latest=shift_ws(param_dyn.latest, PARAM_NEVER),
        )
        return stats, flow_dyn, degrade_dyn, param_dyn

    def _ensure_capacity(self) -> None:
        need = len(self.nodes)
        if need > self.stats.n_rows:
            self.stats = grow_stats(self.stats, _pad_pow2(need))
        pneed = self.param_index.n_rows
        if pneed > self.param_dyn.tokens.shape[0]:
            self.param_dyn = grow_param_state(self.param_dyn, _pad_pow2(pneed))

    def _encode_param(
        self,
        entries: List[_EntryOp],
        exits: List[_ExitOp],
        pindex: ParamIndex,
        bulk: Sequence[BulkOp] = (),
        staging: Optional[List[Tuple[tuple, tuple]]] = None,
    ) -> Tuple[Optional[ParamBatch], int, int]:
        """Encode hot-param slots plus the host-known rounds bound (max
        items per value row, pow2-bucketed; 0 → scan fallback) and the
        item count. Bulk
        groups' p_cols ride the same item stream as numpy slice
        assignments (no per-request Python), indexed into the flat row
        space after the singles (the same offsets the main encode gives
        them)."""
        items = []
        for i, op in enumerate(entries):
            for ps in op.p_slots:
                items.append((i, op.ts, op.acquire, ps))
        bulk_cols: List[Tuple[int, BulkOp, _BulkParamCols, int]] = []
        n_bulk_items = 0
        off_b = len(entries)
        for g in bulk:
            for pc in g.p_cols:
                cnt = int(pc.valid.sum())
                if cnt:
                    bulk_cols.append((off_b, g, pc, cnt))
                    n_bulk_items += cnt
            off_b += g.n
        exit_rows = [r for op in exits for r in op.p_rows]
        resets = pindex.take_resets()
        if not items and not n_bulk_items and not exit_rows and not resets:
            return None, 1, 0
        n_items = len(items) + n_bulk_items
        s = _pad_pow2(max(1, n_items), 8)
        sx = _pad_pow2(max(1, len(exit_rows)), 8)
        q = _pad_pow2(max(1, len(resets)), 8)
        pkey = ("p", s, sx, q)

        def _build_p():
            # One np.empty per unpacked name below, same order — valid,
            # prow, eidx, ts, acquire, grade, behavior, token_count,
            # burst, duration_ms, maxq, cost_ms, xr, rs.
            return (
                np.empty(s, dtype=bool), np.empty(s, dtype=np.int32),
                np.empty(s, dtype=np.int32), np.empty(s, dtype=np.int32),
                np.empty(s, dtype=np.int32), np.empty(s, dtype=np.int32),
                np.empty(s, dtype=np.int32), np.empty(s, dtype=np.int32),
                np.empty(s, dtype=np.int32), np.empty(s, dtype=np.int32),
                np.empty(s, dtype=np.int32), np.empty(s, dtype=np.int32),
                np.empty(sx, dtype=np.int32), np.empty(q, dtype=np.int32),
            )

        pbufs = self._arena.take(pkey, _build_p) if self._arena else _build_p()
        (valid, prow, eidx, ts, acquire, grade, behavior, token_count,
         burst, duration_ms, maxq, cost_ms, xr, rs) = pbufs
        valid.fill(False)
        prow.fill(0)
        eidx.fill(0)
        ts.fill(0)
        acquire.fill(1)
        grade.fill(0)
        behavior.fill(0)
        token_count.fill(0)
        burst.fill(0)
        duration_ms.fill(1)
        maxq.fill(0)
        cost_ms.fill(0)
        for a, (i, t, acq, ps) in enumerate(items):
            valid[a] = True
            prow[a] = ps.prow
            eidx[a] = i
            ts[a] = t
            acquire[a] = acq
            grade[a] = ps.grade
            behavior[a] = ps.behavior
            token_count[a] = ps.token_count
            burst[a] = ps.burst
            duration_ms[a] = ps.duration_ms
            maxq[a] = ps.maxq
            cost_ms[a] = ps.cost_ms
        a = len(items)
        for off, g, pc, cnt in bulk_cols:
            sl = slice(a, a + cnt)
            m = pc.valid
            r = pc.rule
            valid[sl] = True
            prow[sl] = pc.prow[m]
            eidx[sl] = off + np.nonzero(m)[0].astype(np.int32)
            ts[sl] = g.ts[m]
            acquire[sl] = g.acquire[m]
            grade[sl] = r.grade
            behavior[sl] = r.control_behavior
            token_count[sl] = pc.token_count[m]
            burst[sl] = int(r.burst_count)
            # Exactly the singles path's ParamSlotInfo.duration_ms (the
            # kernel clamps to >=1 itself) — a host-side clamp here
            # would break submit_many parity for duration 0.
            duration_ms[sl] = int(r.duration_in_sec) * 1000
            maxq[sl] = int(r.max_queueing_time_ms)
            cost_ms[sl] = pc.cost_ms[m]
            a += cnt
        xr.fill(-1)
        xr[: len(exit_rows)] = exit_rows
        rs.fill(-1)
        rs[: len(resets)] = resets
        pb = ParamBatch(
            valid=jnp.asarray(valid),
            prow=jnp.asarray(prow),
            eidx=jnp.asarray(eidx),
            ts=jnp.asarray(ts),
            acquire=jnp.asarray(acquire),
            grade=jnp.asarray(grade),
            behavior=jnp.asarray(behavior),
            token_count=jnp.asarray(token_count),
            burst=jnp.asarray(burst),
            duration_ms=jnp.asarray(duration_ms),
            maxq=jnp.asarray(maxq),
            cost_ms=jnp.asarray(cost_ms),
            reset_rows=jnp.asarray(rs),
            exit_rows=jnp.asarray(xr),
        )
        rounds = self._param_rounds_for(
            prow[:n_items], grade[:n_items], behavior[:n_items],
            ts[:n_items], acquire[:n_items],
        )
        if n_items:
            if self.param_force_path is not None:
                # Measurement seam (tools/k2probe.py --seed-out, path-
                # pinning tests): "scan" substitutes the rounds bound
                # the memo's scan arm would have computed for an
                # ELIGIBLE batch; "closed" keeps the closed-form pick.
                # Ineligible batches (rounds > -1 already) stay on
                # their correctness-mandated scan either way.
                if self.param_force_path == "scan" and rounds <= -1:
                    rounds = _rounds_bucket(prow[:n_items])
            elif rounds <= -1 and self.autotune.param_active:
                # Closed-form-ELIGIBLE batch: the autotuner's shape-
                # bucketed cost memo arbitrates closed-form vs the
                # rounds/scan family (eligibility above is correctness;
                # this is purely a cost call). The scan-side rounds
                # bound is only computed when the memo actually picks
                # it.
                rounds = self.autotune.pick_param_rounds(
                    n_items, -rounds, rounds,
                    lambda: _rounds_bucket(prow[:n_items]),
                )
            tele = self.telemetry
            if tele.enabled:
                tele.note_param_path(rounds <= -1)
        # Pool return is deferred to the caller's post-fetch give_all —
        # the ParamBatch may alias these buffers zero-copy.
        if self._arena is not None and staging is not None:
            staging.append((pkey, pbufs))
        return pb, rounds, n_items

    @staticmethod
    def _param_rounds_for(prow, grade, behavior, ts, acquire) -> int:
        """Host-known param execution mode: a negative value selects
        the closed-form rank path (every item QPS-grade DEFAULT with
        one acquire — any per-value multiplicity in O(sort)); −1 for
        single-ts batches, −S for mixed-timestamp batches with at most
        S (pow2-bucketed, ≤ PARAM_CLOSED_MAX_SEGMENTS) distinct
        timestamps per value row — realistic gateway windows straddling
        a window edge. Otherwise the pow2 rounds bound, with 0 = the
        sequential-scan fallback."""
        n = prow.shape[0]
        if (
            n > 0
            and (grade == C.FLOW_GRADE_QPS).all()
            and (behavior == C.CONTROL_BEHAVIOR_DEFAULT).all()
            and acquire.min() == acquire.max()
            # acquire<1 admits unconditionally in the recurrence
            # (tokens − 0 ≥ 0); the rank math has no such case.
            and acquire.min() >= 1
        ):
            if ts.min() == ts.max():
                return -1
            # Max distinct timestamps per value row: unique (row, ts)
            # pairs grouped by row. One combined int64 key keeps this a
            # single O(n log n) pass, same cost class as _rounds_bucket.
            key = (prow.astype(np.int64) << 32) | (
                ts.astype(np.int64) & 0xFFFFFFFF
            )
            pairs = np.unique(key)
            segs = int(np.unique(pairs >> 32, return_counts=True)[1].max())
            if segs <= PARAM_CLOSED_MAX_SEGMENTS:
                return -(1 << (segs - 1).bit_length()) if segs > 1 else -1
        return _rounds_bucket(prow)

    def start_auto_flush(self, interval_ms: Optional[float] = None) -> None:
        """Background flusher for deferred mode: pending ops are
        decided within ~``interval_ms`` (config
        ``sentinel.tpu.flush.interval.ms``, default 2) even when no
        caller invokes :meth:`flush` — submit-and-await callers (async
        entries, fire-and-forget adapters) get bounded decision latency
        the way the reference's cluster client bounds its RPC wait.
        The thread is a daemon and survives :meth:`reset`. Calling
        again while running is a no-op UNLESS an explicit
        ``interval_ms`` is given — then the flusher restarts at the new
        cadence (silently dropping a requested interval would leave the
        caller believing it took effect).
        """
        # Clamp: a zero/negative interval (bad config) must not turn
        # the daemon into a busy-spin hammering the locks.
        requested = (
            None if interval_ms is None else max(interval_ms / 1000.0, 1e-4)
        )
        while True:
            with self._lock:
                if self._auto_flush_thread is None:
                    self._start_auto_flush_locked(requested)
                    return
                if requested is None or self._auto_flush_interval_s == requested:
                    return  # a flusher at an acceptable cadence runs
            # Running at a different cadence than the explicit request:
            # restart and re-check — losing a restart race to a caller
            # with a DIFFERENT interval must loop until OUR cadence (or
            # a matching one) is in effect, not silently return. The
            # stop/join happens outside the lock: the flusher thread
            # takes it, so joining while holding it would deadlock.
            self.stop_auto_flush()

    def _start_auto_flush_locked(self, requested: Optional[float]) -> None:
        """Create + start the flusher thread. Caller holds ``_lock``
        and has verified no flusher is running."""
        iv = (
            requested
            if requested is not None
            else max(config.get_float(config.FLUSH_INTERVAL_MS, 2.0) / 1000.0, 1e-4)
        )
        self._auto_flush_interval_s = iv
        stop = threading.Event()
        self._auto_flush_stop = stop

        def _loop() -> None:
            from sentinel_tpu.utils.record_log import record_log

            failures = 0
            while not stop.wait(
                iv if failures == 0 else min(1.0, iv * 2**failures)
            ):
                try:
                    if self.has_pending():
                        self.flush()
                    failures = 0
                except Exception:
                    # Backoff to ≤1 Hz and log only the streak's
                    # first failure — at a 2 ms period a persistent
                    # device error would otherwise churn the record
                    # log with ~500 tracebacks/second.
                    if failures == 0:
                        record_log.error(
                            "[Engine] auto-flush failed", exc_info=True
                        )
                    failures = min(failures + 1, 16)

        t = threading.Thread(target=_loop, name="sentinel-auto-flush", daemon=True)
        self._auto_flush_thread = t
        t.start()

    def stop_auto_flush(self, join_timeout_s: float = 5.0) -> None:
        with self._lock:
            t, stop = self._auto_flush_thread, self._auto_flush_stop
            self._auto_flush_thread = None
            self._auto_flush_stop = None
        if t is not None and stop is not None:
            stop.set()
            t.join(timeout=join_timeout_s)
            if t.is_alive():
                # The flusher is stuck (most likely inside a wedged
                # device call). Pretending the shutdown was clean hides
                # a leaked live thread — warn and mark the engine dirty
                # so operators/tests can assert on it.
                from sentinel_tpu.utils.record_log import record_log

                self.closed_dirty = True
                record_log.warn(
                    "[Engine] auto-flush thread did not stop within "
                    "%.1fs; a live thread leaked (closed_dirty=True)",
                    join_timeout_s,
                )

    def close(self) -> None:
        """Graceful quiesce: stop the auto-flusher, decide anything
        still queued, and settle in-flight async dispatches. Idempotent
        and non-destructive — the engine stays usable afterwards (the
        reference has no analog; its counters live for the JVM's
        lifetime, while an embedded library needs an orderly stop).
        A synchronous flush() settles earlier flush_async dispatches
        itself; the trailing drain() covers the pipelined flush (depth
        > 0), which deliberately leaves up to ``pipeline_depth``
        dispatches in flight."""
        # The ipc plane first: its drainer submits into this engine,
        # and closing it publishes the CLOSED health word so worker
        # processes fail over to the policy snapshot instead of
        # stranding on their verdict waits.
        if self.ipc_plane is not None:
            self.ipc_plane.close()
        # The window next: its flusher thread calls flush() itself,
        # and its final window's waiters must be served, not stranded.
        self.ingest_window.close()
        self.stop_auto_flush()
        self.flush()
        self.drain()
        if self.speculative.enabled:
            # The final drift window has no later traffic to roll it
            # closed — fold it so its drift reaches the histogram.
            self.speculative.flush_window()
        if self.gossip is not None:
            self.gossip.stop()
        self.failover.close()
        if self.capture is not None:
            self.capture.close()

    @property
    def last_flush_host_ms(self) -> Dict[str, float]:
        """Host-side breakdown of the most recent non-empty flush, a
        read-only view over its chunks' flight-recorder spans
        (``FlushSpan``), summed: ``encode_ms`` (staging, shaping and
        param encode), ``dispatch_ms`` (the kernel dispatch alone — the
        host-blocking cost of a pipelined flush), ``kernel_ms``
        (dispatch + the chunks' own device→host fetches; a deferred
        flush counts dispatch only until its fetch materializes),
        ``fill_ms`` (verdict fill from the fetched arrays) and
        ``drain_ms`` (coalesced in-flight fetches that landed since the
        flush began — they may belong to earlier dispatches). With
        ``sentinel.tpu.telemetry.enabled`` off no span is recorded and
        every key reads 0. A snapshot copy, safe to hold across later
        flushes."""
        return self.telemetry.last_flush_ms()

    def _note_drain_ms(self, ms: float) -> None:
        """Record deferred-fetch time: the drain histogram and the
        newest flush's ``drain_ms``. Runs from drain/materialize
        threads outside the flush lock; a drain landing just after a
        new flush swapped its buffers counts toward the new breakdown —
        benign for diagnostics."""
        if self.telemetry.enabled:
            self.telemetry.note_drain(ms)
        if self.ingest.armed:
            self.ingest.note_settle_ms(ms)

    @property
    def pipeline_depth(self) -> int:
        """Max dispatched-but-unfetched flushes ``flush()`` keeps in
        flight (sentinel.tpu.host.pipeline.depth). 0 = synchronous.
        Counted in dispatched chunks — one per flush unless a backlog
        beyond ``max_batch`` splits a flush (see _flush_pipelined)."""
        return self._pipeline_depth

    @pipeline_depth.setter
    def pipeline_depth(self, depth: int) -> None:
        self._pipeline_depth = max(0, int(depth))
        self._resize_arena()

    def set_depth(self, depth: int, drain: bool = True) -> None:
        """Runtime-safe pipeline-depth change (what the autotuner
        uses). RAISING the bound is always safe — the setter re-sizes
        the arena and the next flush simply trims less. LOWERING it
        with in-flight flushes outstanding must settle the excess
        FIRST: every dispatched-but-unfetched flush pins arena staging
        and a FIFO settle slot sized for the OLD bound, so shrinking
        the bound under them would leave the queue deeper than the
        depth contract (and the occupancy accounting) promises until
        some later flush happens to trim it. ``drain=True`` (default)
        drains the queue down to the new bound before the shrink; the
        bare property setter remains the raise/startup path."""
        depth = max(0, int(depth))
        if drain and depth < self._pipeline_depth:
            self._drain_pending(keep=depth)
        self.pipeline_depth = depth

    @property
    def max_inflight(self) -> int:
        """Max flush_async dispatches in flight before the oldest fetch
        is forced (sentinel.tpu.flush.max.inflight). Like
        pipeline_depth, raising it re-sizes the arena — every in-flight
        flush pins a staging set per shape key."""
        return self._max_inflight

    @max_inflight.setter
    def max_inflight(self, n: int) -> None:
        self._max_inflight = max(0, int(n))
        self._resize_arena()

    def _resize_arena(self) -> None:
        """The ONE home of the arena sizing rule: every in-flight flush
        (pipelined or flush_async) pins one staging set per shape key,
        so the pool must cover the deeper of the two bounds plus the
        flush being encoded."""
        if self._arena is not None:
            self._arena.ensure_per_key(
                max(self._pipeline_depth, self._max_inflight) + 1
            )

    def pipeline_stats(self, reset: bool = False) -> Dict[str, float]:
        """Flush-pipeline occupancy counters: ``dispatches``
        (dispatching deferred flushes since the last reset) and
        ``mean_inflight`` (average in-flight queue depth sampled once
        per dispatching flush AFTER its queue trim — the depth that
        actually overlaps the next flush's host work; a saturated
        depth-K pipeline samples exactly K). Occupancy relative to a
        target depth K is ``mean_inflight / K`` (0..1)."""
        with self._pending_lock:
            n = self._pipe_dispatches
            mean = (self._pipe_inflight_sum / n) if n else 0.0
            if reset:
                self._pipe_dispatches = 0
                self._pipe_inflight_sum = 0
        return {"dispatches": float(n), "mean_inflight": mean}

    def has_pending(self) -> bool:
        """True when ops are queued for the next flush (submission
        buffers non-empty). Callers that flush opportunistically — the
        auto-flusher, adapters with ``flush=True`` — use this to skip
        an empty flush: at pipeline depth > 0 an empty flush settles
        the WHOLE in-flight queue (the trailing-flush contract), which
        would silently de-pipeline a window whose flush-on-size
        already dispatched it."""
        with self._lock:
            return bool(
                self._entries or self._exits
                or self._bulk_entries or self._bulk_exits
            )

    def _next_flush_seq(self) -> int:
        """Advance the monotonic flush sequence (caller holds
        ``_flush_lock`` — dispatches and probes are serialized on it)."""
        self._flush_seq += 1
        return self._flush_seq

    @property
    def flush_seq(self) -> int:
        """The last assigned flush sequence number (one per dispatched
        chunk and per failover probe flush) — what the fault injector
        keys on."""
        return self._flush_seq

    def _fetch_refs(self, refs, seqs: Sequence[int], span=None):
        """The ONE chokepoint for device→host result fetches: the
        deterministic fault injector fires here (keyed by flush seq),
        and with failover armed the blocking ``jax.device_get`` runs on
        a watchdog waiter thread bounded by
        ``sentinel.tpu.failover.fetch.timeout.ms`` — a wedged fetch
        raises :class:`~sentinel_tpu.runtime.failover.DeviceFetchTimeout`
        instead of stranding the caller (and everyone behind the flush
        lock) forever. The ``sentinel.fetch`` phase (the host waiting
        for the kernel, then the copy) is opened on the calling thread,
        around the watchdog wait, and lands on ``span.fetch_ms``."""
        faults = self.faults
        fo = self.failover
        with self.telemetry.phase(
            "sentinel.fetch", span, "fetch_ms",
            flush=seqs[0] if seqs else -1, chunks=len(seqs),
        ):
            if fo.armed:
                def _run():
                    if faults is not None:
                        faults.on_fetch(seqs)
                    return jax.device_get(refs)

                return fo.watched(_run, "device fetch", seqs)
            if faults is not None:
                faults.on_fetch(seqs)
            return jax.device_get(refs)

    def _quarantine_pending(self) -> None:
        """Quarantine the whole in-flight queue (failover trip): every
        dispatched-but-unfetched record's ops get policy verdicts from
        the host fallback instead of a device fetch that would fail —
        or hang — again."""
        while True:
            with self._pending_lock:
                if not self._pending_fetches:
                    return
                rec = self._pending_fetches.popleft()
            rec.quarantine()

    def _flush_degraded(self) -> List[_EntryOp]:
        """The DEGRADED flush: swap the pending buffers and fill every
        verdict from the host fallback admitter — no device contact at
        all. Serialized on the flush lock like a real flush, so a
        concurrent recovery can't interleave — and rechecked under the
        lock: a recovery that completed while this caller queued means
        these ops deserve real device verdicts, not stale policy
        fills."""
        fo = self.failover
        drained: Optional[Tuple[List[_EntryOp], List[tuple]]] = None
        with self._flush_lock:
            if not fo.healthy:
                with self._lock:
                    entries, self._entries = self._entries, []
                    exits, self._exits = self._exits, []
                    bulk_e, self._bulk_entries = self._bulk_entries, []
                    bulk_x, self._bulk_exits = self._bulk_exits, []
                    self._bulk_pending_n = 0
                    self._bulk_exit_pending_n = 0
                if not entries and not exits and not bulk_e and not bulk_x:
                    return []
                if self.sketch.armed:
                    # Device sketch unreachable: the key stream folds
                    # into the tier's host space-saving mirror so the
                    # controller keeps seeing heavy hitters while
                    # DEGRADED (runtime/sketch.py).
                    self.sketch.fold_host_chunk(
                        entries, bulk_e, self.flow_index, self.param_index,
                        self.clock.now_ms(),
                    )
                items = fo.fill_degraded(entries, exits, bulk_e, bulk_x)
                drained = (entries, items)
        if drained is None:
            # Recovered while we queued behind the flush lock.
            return self.flush()
        self._post_flush(drained)  # block-log IO outside the flush lock
        return drained[0]

    def flush(self) -> List[_EntryOp]:
        """Encode + run the kernel for all pending ops; fills verdicts.

        With ``pipeline_depth == 0`` (default) the flush is fully
        synchronous: earlier deferred dispatches settle first, then
        this flush's device→host fetch completes before returning.
        With ``pipeline_depth > 0`` the flush is PIPELINED: it
        dispatches without fetching and only settles the in-flight
        queue down to at most ``pipeline_depth`` outstanding flushes
        (see :meth:`_flush_pipelined`) — observable semantics are
        unchanged because verdicts materialize lazily (FIFO) on first
        access.

        With failover armed and the engine DEGRADED, the flush never
        touches the device: verdicts come from the host fallback
        admitter, and an automatic recovery attempt (restore + probe
        flushes) runs first when the retry gap has elapsed
        (runtime/failover.py).

        The submission lock is held only to swap the pending buffers and
        snapshot the rule indexes; encoding, kernel dispatch and the
        device→host fetch happen outside it, so other threads keep
        submitting while a device round-trip is in flight. Concurrent
        flushes serialize on the flush lock; a caller whose ops were
        drained by another thread's flush returns with the verdicts
        already filled (the other flush cannot release the lock before
        filling them).

        The call is the ``sentinel.flush`` profiler step, numbered by
        the flush seq its first chunk is expected to dispatch under
        (another thread's concurrent dispatch can take that seq first).
        """
        seq = self._flush_seq + 1
        with self.telemetry.flush_step(seq):
            return self._flush(seq)

    def _flush(self, seq: int) -> List[_EntryOp]:
        w = self.ingest_window
        if w.armed and w._exit_buf:
            # Window-batched completions waiting for their columnar
            # ride join THIS flush — "after flush()+drain() everything
            # submitted has settled" keeps holding with the window on.
            w._drain_exits()
        fo = self.failover
        if fo.armed and not fo.healthy:
            if fo.recovery_due(self.clock.now_ms()):
                fo.try_recover()
            if not fo.healthy:
                return self._flush_degraded()
        if self.sketch.armed and self.sketch.pending_actions:
            # Queued sketch promotions/demotions (flow-rule rebuilds,
            # param row releases) land at flush entry, OUTSIDE the
            # flush lock — "promoted within a bounded number of
            # flushes" is this line (runtime/sketch.py).
            self.sketch.apply_actions()
        depth = self._pipeline_depth
        if depth > 0:
            return self._flush_pipelined(depth)
        # Earlier deferred dispatches materialize first (FIFO), so
        # "after flush() every previously submitted op has a verdict"
        # keeps holding in pipelined use.
        tele = self.telemetry
        with tele.phase("sentinel.flush.drain", flush=seq):
            self.drain()
        if fo.armed and not fo.healthy:
            # The drain tripped failover: serve the new ops from policy.
            return self._flush_degraded()
        drained: Tuple[List[_EntryOp], List[tuple]] = ([], [])
        try:
            with self._flush_lock:
                self._flush_locked(drained)
        finally:
            with tele.phase("sentinel.post_flush", flush=seq):
                self._post_flush(drained)
        return drained[0]

    def _flush_pipelined(self, depth: int) -> List[_EntryOp]:
        """The depth-K flush: encode + dispatch the pending ops WITHOUT
        waiting for device results, then settle the in-flight queue
        FIFO down to at most ``depth`` dispatched-but-unfetched
        flushes. Host encode of flush N+1 thus overlaps device
        execution of flush N, and device state chains donation-safely
        from one flush into the next with no host round-trip in
        between (the kernel outputs of flush N — stats/dyn states —
        are the inputs of flush N+1 directly). Verdicts of a
        still-in-flight flush materialize lazily on first access, at
        the queue trim of a later flush, or at ``drain()`` — always
        oldest-first, and via one coalesced device fetch per drain.
        Arena staging buffers of every in-flight flush stay pinned
        until its fetch lands (the zero-copy ``jnp.asarray`` hazard
        spans the whole queue — see _EncodeArena).

        An EMPTY flush (nothing new dispatched) settles the queue
        completely instead: a trailing flush() after a burst must not
        strand the last ``depth`` flushes' post work (block-log
        records, cluster-token releases) until close()/reset() or the
        next traffic — fire-and-forget callers never read verdicts.

        The depth bound counts queued _PendingChunk records, i.e. dispatched
        chunks — one per flush except when a backlog exceeds
        ``max_batch`` and one flush splits into several chunks, in
        which case the trim settles the flush's own earliest chunks
        (degrading toward sync for exactly those oversized windows)."""
        return self._dispatch_deferred(keep_dispatched=depth, keep_empty=0)

    def _dispatch_deferred(
        self, keep_dispatched: int, keep_empty: int
    ) -> List[_EntryOp]:
        """Shared deferred-dispatch body of :meth:`flush_async` and
        :meth:`_flush_pipelined`: encode + dispatch without fetching,
        then trim the in-flight queue to ``keep_dispatched`` (or
        ``keep_empty`` when this call dispatched nothing) and record
        one occupancy sample per dispatching flush."""
        drained: Tuple[List[_EntryOp], List[tuple]] = ([], [])
        try:
            with self._flush_lock:
                dispatched = self._flush_locked(drained, defer=True)
        except BaseException:
            # Still bound the queue, but never let a drain error mask
            # the dispatch failure being raised.
            try:
                self._drain_pending(keep=keep_dispatched)
            except BaseException:
                pass
            raise
        with self.telemetry.phase("sentinel.flush.drain", flush=self._flush_seq):
            self._drain_pending(
                keep=keep_dispatched if dispatched else keep_empty
            )
        if dispatched:
            self._sample_occupancy()
        return drained[0]

    def flush_async(self) -> List[_EntryOp]:
        """Encode + dispatch all pending ops WITHOUT waiting for device
        results — the pipelined flush.

        ``flush()`` dispatches the kernel and then blocks on the
        device→host fetch, which serializes every flush behind a full
        round-trip. ``flush_async`` returns
        as soon as the kernel is dispatched: JAX's async dispatch then
        overlaps this flush's device work (and its fetch latency) with
        the host encode of the next one. Results materialize lazily —
        on first access of any op's ``verdict`` / bulk group's
        ``admitted``, at the next ``flush()`` or ``drain()``, or when
        more than ``max_inflight`` async flushes are outstanding
        (bounding device memory held by unfetched results). Block-log
        writes and cluster-token releases for a chunk ride with its
        materialization.
        """
        fo = self.failover
        if fo.armed and not fo.healthy:
            # Degraded: no device dispatch to defer — policy verdicts
            # fill synchronously (recovery attempts stay on flush()).
            return self._flush_degraded()
        if self.sketch.armed and self.sketch.pending_actions:
            self.sketch.apply_actions()
        return self._dispatch_deferred(
            keep_dispatched=self._max_inflight, keep_empty=self._max_inflight
        )

    def _sample_occupancy(self) -> None:
        """One occupancy sample per dispatching flush, AFTER the queue
        trim: the in-flight depth that actually overlaps the next
        flush's host work. At steady state a fully-occupied pipeline
        samples exactly ``pipeline_depth`` (occupancy 1.0)."""
        with self._pending_lock:
            self._pipe_dispatches += 1
            self._pipe_inflight_sum += len(self._pending_fetches)

    def drain(self) -> None:
        """Materialize every outstanding flush_async fetch (device→host)
        and run its post work. After drain(), every op from earlier
        flush_async calls has its verdict filled."""
        self._drain_pending()

    def _drain_pending(
        self, upto: Optional[_PendingChunk] = None, keep: int = 0
    ) -> None:
        """Materialize queued async fetches oldest-first: through
        ``upto`` (inclusive) when given, else until at most ``keep``
        remain. The records to settle are popped in one scoop under
        the deque lock, their device results fetched with ONE
        coalesced ``jax.device_get`` (each separate fetch costs a full
        device round-trip), and each record's
        verdict fill + post work then runs outside the deque lock on
        the record's own lock, so concurrent dispatchers never stall
        behind a fetch. A failed batch fetch falls back to per-record
        fetches so errors attribute to the records that actually
        failed; the first failure is re-raised after the drain
        finishes (later records still materialize — one wedged fetch
        must not strand the queue)."""
        first_err: Optional[BaseException] = None
        while True:
            recs: List[_PendingChunk] = []
            with self._pending_lock:
                if upto is not None:
                    if not upto._done and upto in self._pending_fetches:
                        while self._pending_fetches:
                            rec = self._pending_fetches.popleft()
                            recs.append(rec)
                            if rec is upto:
                                break
                else:
                    while len(self._pending_fetches) > keep:
                        recs.append(self._pending_fetches.popleft())
            if not recs:
                break
            # Snapshot each record's device refs (skipping records a
            # concurrent caller already materialized OR is busy
            # materializing — blocking here would stall the whole
            # coalesced fetch behind that record's device round-trip
            # and post-work callbacks; materialize(None) below waits
            # on exactly the busy ones after the batch fetch) and
            # fetch them all in one batched device_get.
            batch_refs: List[Optional[tuple]] = []
            batch_seqs: List[int] = []
            for rec in recs:
                if rec._lock.acquire(blocking=False):
                    try:
                        batch_refs.append(None if rec._done else rec._refs)
                        if not rec._done:
                            batch_seqs.append(rec._seq)
                    finally:
                        rec._lock.release()
                else:
                    batch_refs.append(None)
            fetched = None
            to_fetch = [r for r in batch_refs if r is not None]
            if to_fetch:
                try:
                    t0 = time.perf_counter()
                    fetched = self._fetch_refs(to_fetch, batch_seqs)
                    self._note_drain_ms((time.perf_counter() - t0) * 1e3)
                except BaseException as exc:
                    fetched = None
                    fo = self.failover
                    if fo.armed:
                        # Device fault/timeout with failover armed: go
                        # DEGRADED now — materialize(None) below then
                        # quarantines each record (policy verdicts, no
                        # per-record re-fetch of a dead device).
                        fo.trip("fetch", exc, batch_seqs)
                    elif self.telemetry.enabled:
                        # Per-record fallback below attributes the
                        # failure to the record(s) that actually
                        # caused it.
                        self.telemetry.note_fallback(1)
                        for rec in recs:
                            # Local bind: a concurrent materialize()
                            # (verdict read on another thread) nulls
                            # rec._span under the record's lock, which
                            # this thread does not hold.
                            span = rec._span
                            if span is not None:
                                span.fallbacks += 1
            it = iter(fetched) if fetched is not None else None
            for rec, refs in zip(recs, batch_refs):
                got = next(it) if (it is not None and refs is not None) else None
                try:
                    rec.materialize(got)
                except BaseException as exc:
                    if first_err is None:
                        first_err = exc
            if upto is not None and recs[-1] is upto:
                break
        if upto is not None:
            # Another thread may have popped it mid-drain: block on the
            # record itself until it is done (and see its error, if any).
            try:
                upto.materialize()
            except BaseException as exc:
                if first_err is None:
                    first_err = exc
        if first_err is not None:
            raise first_err
        # Self-tuning control plane (runtime/autotune.py): the decision
        # tick rides the drain path — once per settled queue, off the
        # submit hot path, rate-limited inside maybe_tick. getattr: the
        # constructor itself never drains, but belt over suspenders for
        # subclasses that might.
        at = getattr(self, "autotune", None)
        if at is not None and at.enabled:
            at.maybe_tick(self.clock.now_ms())

    def _flush_locked(
        self,
        out: Optional[Tuple[List[_EntryOp], List[tuple]]] = None,
        defer: bool = False,
    ) -> int:
        """Drain + process pending ops; returns the number of chunks
        THIS call dispatched (0 = the flush was empty — callers must
        not infer that from shared counters, which concurrent flushes
        also advance). ``out`` (entries, blocked_items)
        is filled IN PLACE chunk by chunk so the caller's finally still
        delivers completed chunks' block-log records and token releases
        if a later chunk's kernel raises. Each chunk's record settles
        right after its dispatch, here under the flush lock — or, with
        ``defer``, it is queued for ``_drain_pending`` (out[1] stays
        empty; post work rides with materialization)."""
        out = out if out is not None else ([], [])
        n_chunks = [0]

        def _chunk(entries_c, exits_c, bulk_c, bulk_x_c, findex, dindex,
                   pindex, auth_rules) -> None:
            rec = self._run_chunk(
                entries_c, exits_c, bulk_c, bulk_x_c, findex, dindex, pindex,
                auth_rules, defer=defer,
            )
            if not defer:
                out[1].extend(rec.settle())
            elif rec._refs is None:
                # Served from policy (nothing to fetch): settle and
                # post now instead of queueing.
                rec.materialize()
            else:
                for op in rec._entries:
                    op._pending = rec
                for g in rec._bulk:
                    g._pending = rec
                with self._pending_lock:
                    self._pending_fetches.append(rec)
            out[0].extend(entries_c)
            n_chunks[0] += 1
        tele = self.telemetry
        swap = tele.phase("sentinel.flush.swap", flush=self._flush_seq + 1)
        with swap, self._lock:
            self._maybe_rebase()
            entries, self._entries = self._entries, []
            exits, self._exits = self._exits, []
            bulk_e, self._bulk_entries = self._bulk_entries, []
            bulk_x, self._bulk_exits = self._bulk_exits, []
            self._bulk_pending_n = 0
            self._bulk_exit_pending_n = 0
            if not entries and not exits and not bulk_e and not bulk_x:
                # An empty flush keeps the previous breakdown — a
                # flush-on-size inside submit followed by an explicit
                # no-op flush() must not zero the numbers just taken.
                return 0
            self._ensure_capacity()
            findex = self.flow_index
            dindex = self.degrade_index
            pindex = self.param_index
            auth_rules = self.authority_rules
            # Ops resolved against superseded tables (a reload swapped
            # an index between their submit and this flush — including
            # submits that landed while the reload's own drain-flush was
            # in the kernel) are re-resolved against this snapshot, so
            # gids always match the device tables they are checked with.
            cur = (findex, dindex, pindex)
            for op in entries:
                if op.src is not None and op.src != cur:
                    # Slots the token server already decided (granted or
                    # BLOCKED at submit time) must not reappear as local
                    # slots — that would double-check a granted token
                    # against the local window; re-running the RPC would
                    # double-acquire the global budget. Everything else
                    # (kept fallback slots, rules that became
                    # cluster-mode after submit) stays locally enforced.
                    def _decided(gid: int) -> bool:
                        rule = findex.cluster_gids.get(gid)
                        return (
                            rule is not None
                            and rule.cluster_config.flow_id
                            in op.token_decided_flow_ids
                        )

                    op.slots = [
                        s
                        for s in findex.resolve_slots(
                            op.resource, op.context_name, op.origin, self.nodes
                        )
                        if not _decided(s[0])
                    ]
                    op.d_gids = dindex.gids_for(op.resource)

                    def _param_decided(s) -> bool:
                        r = s.rule
                        return (
                            r is not None
                            and r.cluster_mode
                            and r.cluster_config is not None
                            and r.cluster_config.flow_id
                            in op.token_decided_flow_ids
                        )

                    op.p_slots = [
                        s
                        for s in (
                            pindex.slots_for(op.resource, op.args)
                            if op.args and pindex.has_rules()
                            else []
                        )
                        if not _param_decided(s)
                    ]
                    op.src = cur
            for x in exits:
                if x.resource is not None and x.src_dindex is not None and x.src_dindex is not dindex:
                    x.d_gids = dindex.gids_for(x.resource)
                    x.src_dindex = dindex
            for g in bulk_e:
                if g.src is not None and g.src != cur:
                    # Bulk groups never hold token-service verdicts
                    # (cluster rules are rejected at submit), so the
                    # re-resolve is a plain slot refresh; a rule that
                    # became cluster-mode after submit stays locally
                    # enforced for this group.
                    g.slots = findex.resolve_slots(
                        g.resource, g.context_name, g.origin, self.nodes
                    )
                    g.d_gids = dindex.gids_for(g.resource)
                    if g.args_column is not None and pindex.has_rules():
                        # Param prows are index-scoped: re-intern the
                        # column against the new snapshot. A rule that
                        # became THREAD/cluster after submit degrades to
                        # dropping the group's param slots rather than
                        # raising mid-flush.
                        try:
                            g.p_cols = self._bulk_param_cols(
                                pindex, g.resource, g.args_column
                            )
                        except ValueError:
                            g.p_cols = []
                    else:
                        g.p_cols = []
                    g.src = cur
            for gx in bulk_x:
                if gx.resource is not None and gx.src_dindex is not None and gx.src_dindex is not dindex:
                    gx.d_gids = dindex.gids_for(gx.resource)
                    gx.src_dindex = dindex
        if tele.enabled:
            tele.begin_flush(swap.ms)
        # One kernel launch per max_batch slice: bounds device memory
        # for the padded batch regardless of how much queued up.
        mb = max(self.max_batch, 1)
        n_bulk = sum(g.n for g in bulk_e)
        m_bulk = sum(g.n for g in bulk_x)
        if len(entries) + n_bulk <= mb and len(exits) + m_bulk <= mb:
            # Everything fits one kernel call — singles and bulk share
            # one flush, so ALL exits (incl. bulk-exit groups) apply
            # before ALL admissions, exactly like the unbatched path.
            _chunk(entries, exits, bulk_e, bulk_x, findex, dindex, pindex,
                   auth_rules)
            return n_chunks[0]
        # Oversized backlog: singles chunks, then packed bulk chunks.
        # Exits in a later chunk are not visible to earlier chunks'
        # admissions — the same caveat the singles chunk split already
        # has at this size.
        for off in range(0, max(len(entries), len(exits)), mb):
            _chunk(
                entries[off : off + mb],
                exits[off : off + mb],
                [],
                [],
                findex,
                dindex,
                pindex,
                auth_rules,
            )
        # Bulk groups ride in their own chunks, greedy-packed to the
        # same max_batch bound (each group's n ≤ max_batch is enforced
        # at submit).
        def _pack(groups):
            chunks, cur_c, cur_n = [], [], 0
            for g in groups:
                if cur_c and cur_n + g.n > mb:
                    chunks.append(cur_c)
                    cur_c, cur_n = [], 0
                cur_c.append(g)
                cur_n += g.n
            if cur_c:
                chunks.append(cur_c)
            return chunks
        be_chunks = _pack(bulk_e)
        bx_chunks = _pack(bulk_x)
        for i in range(max(len(be_chunks), len(bx_chunks))):
            _chunk(
                [],
                [],
                be_chunks[i] if i < len(be_chunks) else [],
                bx_chunks[i] if i < len(bx_chunks) else [],
                findex,
                dindex,
                pindex,
                auth_rules,
            )
        return n_chunks[0]

    def _post_flush(self, drained: Tuple[List[_EntryOp], List[tuple]]) -> None:
        """Work that must happen after a flush but OUTSIDE the flush
        lock (disk IO and release RPCs must not stall concurrent
        flush()/entry_sync callers): write the flush's blocked verdicts
        to the block log, and hand back concurrency tokens of entries
        that were ultimately blocked (the reference's
        releaseConcurrentToken on abort)."""
        entries, blocked_items = drained
        if blocked_items:
            self.block_log.log_batch(blocked_items)
        self.block_log.maybe_flush()
        for op in entries:
            if op.cluster_tokens and op.verdict is not None and not op.verdict.admitted:
                release_cluster_tokens(op.cluster_tokens)
                op.cluster_tokens = []

    def _run_chunk(
        self,
        entries: List[_EntryOp],
        exits: List[_ExitOp],
        bulk: List[BulkOp],
        bulk_exits: List[_BulkExitOp],
        findex: FlowIndex,
        dindex: DegradeIndex,
        pindex: ParamIndex,
        auth_rules: Dict[str, AuthorityRule],
        defer: bool = False,
    ) -> _PendingChunk:
        """Encode one chunk and dispatch the kernel; returns the chunk's
        record, whose ``settle`` fetches and fills the verdicts.
        ``defer`` marks a record that will be queued rather than settled
        before the next dispatch: the device state riding its fetch is
        then copied. Runs under
        the flush lock only — the indexes are the snapshot taken when
        the pending buffers were swapped; _flush_locked re-resolved any
        op whose submit-time tables were superseded by a reload.

        Bulk groups (``bulk`` / ``bulk_exits``) occupy contiguous row
        ranges after the singles and are encoded with numpy slicing —
        no per-entry Python work anywhere on their path."""
        fo = self.failover
        if fo.armed and not fo.healthy:
            # An earlier chunk of this same flush tripped failover:
            # don't touch the device again — the record (no flush seq)
            # settles from policy. Capture sees the chunk here (seq -1).
            cap = self.capture
            cap_tok = (
                cap.note_chunk(entries, exits, bulk, bulk_exits,
                               self.clock.now_ms(), -1)
                if cap is not None
                else None
            )
            return _PendingChunk(self, entries, exits, bulk, bulk_exits,
                                 cap_tok=cap_tok)
        # ---- custom processor slots (SPI-assembled chain head) ----
        # A registered slot's veto blocks the entry before every device
        # stage — accounted like a first-slot BlockException (the block
        # scatter shares the authority channel; attribution is kept
        # host-side on the op). Bulk groups run the check once per
        # DISTINCT acquire value (the only per-entry field a slot can
        # see on this path) and veto exactly the matching entries.
        from sentinel_tpu.core.slots import SlotChainRegistry, SlotEntryContext

        if SlotChainRegistry.slots():
            for op in entries:
                if not op.custom_checked:
                    op.custom_veto = SlotChainRegistry.check_entry(
                        SlotEntryContext(
                            op.resource, op.context_name, op.origin,
                            op.acquire, op.prio, op.args,
                        )
                    )
                    op.custom_checked = True
            for g in bulk:
                SlotChainRegistry.check_bulk_entry(g)
        # Flight recorder: one span per dispatched chunk, opened at
        # encode start so every phase below lands on it. Disabled →
        # tele and span are None and the phases only mark the profiler.
        tele = self.telemetry if self.telemetry.enabled else None
        if tele is not None and self._arena is not None:
            arena_h0, arena_m0 = self._arena.hits, self._arena.misses
        else:
            arena_h0 = arena_m0 = 0
        n_bulk = sum(g.n for g in bulk)
        m_bulk = sum(g.n for g in bulk_exits)
        # One flush sequence number per dispatched chunk — the fault
        # injector's key, the checkpoint cadence counter and the
        # ``flush`` arg of this chunk's phase spans.
        seq = self._next_flush_seq()
        span = None
        if tele is not None:
            with self._pending_lock:
                inflight = len(self._pending_fetches)
            span = tele.begin_span(
                t0=time.perf_counter(), depth=self._pipeline_depth,
                inflight=inflight, n_entries=len(entries),
                n_exits=len(exits), n_bulk=n_bulk, n_bulk_exits=m_bulk,
                deferred=defer, now_rel_ms=self.clock.now_ms(),
            )
        phase = self.telemetry.phase
        with phase("sentinel.encode", span, "encode_ms", flush=seq):
            with phase("sentinel.encode.staging", flush=seq) as ph:
                batch, staging, k, kd, now_host, runs = self._encode_staging(
                    entries, exits, bulk, bulk_exits, n_bulk, m_bulk
                )
                ph.set(runs=runs[0], runs_padded=runs[1], x_runs=runs[2],
                       x_runs_padded=runs[3])
                sysdev = self._system_device()
            with phase("sentinel.encode.shaping", span, "shaping_encode_ms",
                       flush=seq) as ph:
                shaping, sh_rounds, sh_items = self._encode_shaping(
                    entries, bulk, k, findex
                )
                sh_padded = 0 if shaping is None else shaping.valid.shape[0]
                ph.set(items=sh_items, padded=sh_padded, rounds=sh_rounds)
            with phase("sentinel.encode.param", span, "param_encode_ms",
                       flush=seq) as ph:
                param, p_rounds, p_items = self._encode_param(
                    entries, exits, pindex, bulk, staging
                )
                ph.set(items=p_items, rounds=p_rounds)
            # Param-path cost attribution: consume the pick _encode_param
            # made for THIS chunk immediately (flushes serialize on the
            # flush lock) — consuming here, before any fault-path early
            # return, means a pick can never leak onto a later chunk's
            # span. It lands on the span after dispatch.
            at = self.autotune
            param_pick = at.take_pending_pick() if at.enabled else None
            # Statistics sketch tier (runtime/sketch.py): aggregate this
            # chunk's key-id stream and schedule the once-per-window decay
            # — the fold itself runs inside the kernel, chained on the
            # donated SketchState exactly like the stats windows.
            tier = self.sketch
            sk_batch = None
            sk_decay = False
            if tier.armed and self.mesh is None:
                sk_ids, sk_w = tier.encode_chunk(entries, bulk, findex, pindex)
                sk_decay = tier.decay_due(now_host)
                sk_batch = SketchBatch(
                    ids=jnp.asarray(sk_ids), w=jnp.asarray(sk_w)
                )
            occ_ms = config.occupy_timeout_ms
            common = (
                self.stats,
                findex.device,
                self.flow_dyn,
                dindex.device,
                self.degrade_dyn,
                self.param_dyn,
                sysdev,
                batch,
            )
            # Host-known stage specializations (exact — each skipped stage's
            # masks would be all-pass): plain DEFAULT-flow traffic compiles
            # to a much leaner kernel than the fully-general one.
            flags = dict(
                with_occupy=any(op.prio for op in entries),
                with_system=self.system_config is not None,
                with_degrade=bool(dindex.rules),
                with_exits=bool(exits) or bool(bulk_exits),
                shaping_rounds=sh_rounds,
                param_rounds=p_rounds,
                # Device-side blocked-resource top-K fold (0 when telemetry
                # is off — the fold then compiles away entirely).
                blk_topk=self._blk_topk_k,
                sketch_decay=sk_decay,
                # Keys the jit cache on the live window geometry so a
                # retune_second_window with unchanged shapes (interval-only
                # change) cannot hit a stale-constant entry.
                win_key=_ncfg.SECOND_CFG,
            )
        if span is not None:
            span.shaping_items = sh_items
            span.shaping_padded = sh_padded
            span.shaping_rounds = sh_rounds
            span.param_items = p_items
            span.groups = len(bulk)
            (span.runs, span.runs_padded, span.x_runs,
             span.x_runs_padded) = runs
        # Flight recorder: spill the chunk's inputs BEFORE dispatch (a
        # dispatch fault must not lose the traffic that caused it); the
        # verdicts follow from the fill path via the one-shot token.
        cap = self.capture
        cap_tok = (
            cap.note_chunk(entries, exits, bulk, bulk_exits, now_host, seq)
            if cap is not None
            else None
        )

        def _dispatch():
            if self.faults is not None:
                self.faults.on_dispatch(seq)
            if self._sharded_fns is not None:
                # Mesh mode: one global batch sharded over the chips;
                # shaping/param item batches (global coordinates) ride
                # replicated into the globally-ordered scans. The
                # sketch tier stays single-chip for now (sk_batch is
                # None on the mesh path) — the sharded kernels return
                # the 5-tuple shape and None rides through.
                fn = self._sharded_fn_for(
                    shaping is not None, param is not None, sh_rounds, p_rounds
                )
                extra = tuple(b for b in (shaping, param) if b is not None)
                st, fdyn, ddyn2, pdyn2, res = fn(*common, *extra)
                return st, fdyn, ddyn2, pdyn2, None, res
            skw = dict(skstate=tier.dev_state, sk=sk_batch) if sk_batch is not None else {}
            if shaping is None and param is None:
                return flush_step_jit(*common, occupy_timeout_ms=occ_ms, **skw, **flags)
            if param is None:
                return flush_step_shaping_jit(*common, shaping, occupy_timeout_ms=occ_ms, **skw, **flags)
            if shaping is None:
                return flush_step_param_jit(*common, param, occupy_timeout_ms=occ_ms, **skw, **flags)
            return flush_step_full_jit(*common, shaping, param, occupy_timeout_ms=occ_ms, **skw, **flags)

        try:
            # The phase sits on this thread, around the watchdog wait,
            # so the interval stays on the flushing thread's line.
            with phase("sentinel.dispatch", span, "dispatch_ms", flush=seq):
                if fo.armed:
                    # Watchdog-bounded dispatch: a wedged compile/dispatch
                    # trips failover instead of stranding every submitter.
                    out = fo.watched(_dispatch, "kernel dispatch", (seq,))
                else:
                    out = _dispatch()
        except BaseException as exc:
            if not fo.armed:
                raise
            # The dispatch faulted: the device states may or may not
            # have been consumed (donation) — either way the chain is
            # unrecoverable without a restore. The record has no
            # results, so it settles from policy; staging drops to GC
            # (the computation may still read it zero-copy if it did
            # start).
            fo.trip("dispatch", exc, seq)
            return _PendingChunk(self, entries, exits, bulk, bulk_exits,
                                 span=span, seq=seq, cap_tok=cap_tok)
        (
            self.stats, self.flow_dyn, self.degrade_dyn, self.param_dyn,
            new_skstate, result,
        ) = out
        if new_skstate is not None:
            # The donated sketch chain advances under the flush lock,
            # exactly like the other dyn states.
            tier.dev_state = new_skstate

        if tele is not None:
            if self._arena is not None:
                span.arena_hits = self._arena.hits - arena_h0
                span.arena_misses = self._arena.misses - arena_m0
                tele.note_arena(span.arena_hits, span.arena_misses)
            # Intern-cache activity since the previous span (the
            # resolution itself happens at submit time, so the delta is
            # attributed to the flush that drains those submissions).
            ph = getattr(pindex, "cache_hits", 0)
            pm = getattr(pindex, "cache_misses", 0)
            seen_ref, h0, m0 = self._tele_intern_seen
            if seen_ref is None or seen_ref() is not pindex:
                h0 = m0 = 0  # index rebuilt (reload) — counters reset
            span.intern_hits = max(0, ph - h0)
            span.intern_misses = max(0, pm - m0)
            self._tele_intern_seen = (weakref.ref(pindex), ph, pm)

        if span is not None and param_pick is not None:
            # The autotuner folds the settled span's dispatch+settle
            # cost into its memo at the next tick.
            span.param_bucket, span.param_path = param_pick

        # Device state that rides this chunk's fetch: a queued chunk
        # must copy it, because the next flush donates these buffers
        # into its kernel, which deletes them before a deferred
        # device_get runs ("Array has been deleted"). Depth 0 settles
        # before the next dispatch and keeps zero-copy references.
        def snap(tree):
            return jax.tree_util.tree_map(jnp.copy, tree) if defer else tree

        # Opt-in breaker state-change observers: capture THIS chunk's
        # post-flush state (tagged with epoch+seq — dispatches are
        # serialized under _flush_lock, so seq follows dispatch order)
        # so the possibly-deferred fill can diff it against the host
        # mirror in the same device fetch. A flush dispatched with NO
        # observers leaves the mirror stale — mark it so the next
        # observed fill resyncs silently instead of reporting old
        # transitions as new.
        from sentinel_tpu.rules import breaker_events

        # The speculative tier counts as a standing breaker observer:
        # its mirror reads (HostFallbackAdmitter._breaker_open) must see
        # every flip, so the post-flush breaker state rides EVERY
        # flush's coalesced fetch while the tier is on (fire_transitions
        # is a no-op walk when no user observers are registered).
        # The capture journal also rides as a standing observer: its
        # postmortem freeze fires off breaker openings, so the
        # post-flush state must travel with every captured flush.
        if (
            breaker_events.has_observers()
            or self.speculative.enabled
            or cap is not None
        ):
            self._breaker_seq += 1
            breaker_tag = (self._breaker_epoch, self._breaker_seq)
            breaker = snap(self.degrade_dyn.state)
        else:
            breaker_tag = breaker = None
            with self._breaker_mirror_lock:
                self._breaker_mirror_valid = False
                # Also fence out older in-flight deferred fills: a fill
                # dispatched BEFORE this unobserved flush would
                # otherwise land later, set the mirror valid again with
                # pre-gap state, and make the next observed diff report
                # THIS flush's transitions — breaking the "first
                # observed flush resyncs silently" contract. Advancing
                # applied_seq to the current seq makes the seq guard
                # drop them.
                self._breaker_applied_seq = self._breaker_seq

        # Speculative shaping-mirror reconcile: the settled pacer /
        # warm-up dyn columns ride the SAME coalesced fetch whenever
        # the tier serves shaped resources — the host mirror re-anchors
        # to device truth at every drain for free.
        spec_tier = self.speculative
        shaping = None
        if (
            spec_tier.enabled
            and spec_tier.mirror.shaping_enabled
            and findex.shaping_gids
            and self.mesh is None
        ):
            fd = self.flow_dyn
            shaping = snap((fd.latest_passed_time, fd.stored_tokens,
                            fd.last_filled_time))

        # Sketch candidate table: rides the same coalesced fetch.
        sketch = None
        if new_skstate is not None:
            sketch = snap((new_skstate.cand_ids, new_skstate.cand_cnt))

        # Admission-trace flush linkage: the deciding flush-span seq
        # (TelemetryBus ids) — -1 when the flight recorder is off.
        flush_seq = span.flush_id if span is not None else -1

        # Host checkpoint (failover): every N flushes the fresh device
        # states ride the SAME coalesced result fetch to the host as
        # the last-good restore point — no extra round-trip.
        ckpt_meta = ckpt = None
        if fo.armed and fo.checkpoint_due(seq):
            # The sketch tier joins the checkpoint (PR 15): its keys are
            # stable CRC ids, so the table restores position-independent
            # — an engine trip (or a hot-restarted process loading the
            # durable spill) keeps heavy-hitter protection instead of
            # silently resetting it.
            ckpt = snap((self.stats, self.flow_dyn, self.degrade_dyn,
                         self.param_dyn, new_skstate))
            ckpt_meta = fo.begin_checkpoint(
                seq, now_host, findex, dindex, pindex
            )

        def _fill(got: _ChunkResults) -> Tuple[List[tuple], int]:
            if ckpt_meta is not None:
                fo.store_checkpoint(ckpt_meta, got.ckpt)
            res = self._fill_results(
                got, entries, exits, bulk, bulk_exits, findex, dindex,
                auth_rules, k, kd, breaker_tag=breaker_tag,
                flush_seq=flush_seq,
            )
            if cap_tok is not None:
                cap.note_verdicts(cap_tok, entries, bulk)
            return res

        refs = _ChunkResults(
            result.admitted, result.reason, result.slot_ok, result.wait_ms,
            result.sys_type, result.dslot_ok,
            blk=(
                None if result.blk_rows is None
                else (result.blk_rows, result.blk_weight)
            ),
            breaker=breaker, shaping=shaping, sketch=sketch, ckpt=ckpt,
        )
        if defer and span is not None:
            tele.dispatch_done(span)
        return _PendingChunk(
            self, entries, exits, bulk, bulk_exits, refs=refs, fill=_fill,
            staging=staging, span=span, seq=seq, cap_tok=cap_tok,
            deferred=defer,
        )

    def _encode_staging(
        self,
        entries: List[_EntryOp],
        exits: List[_ExitOp],
        bulk: List[BulkOp],
        bulk_exits: List[_BulkExitOp],
        n_bulk: int,
        m_bulk: int,
    ) -> Tuple[FlushBatch, List[Tuple[tuple, tuple]], int, int, int,
               Tuple[int, int, int, int]]:
        """Entry and exit staging of one chunk: the padded host columns
        (arena-pooled), the run tables and their FlushBatch device
        transfers. Returns (batch, staging buffers to pool after the
        fetch, slot width k, degrade width kd, the flush's engine-clock
        now, (entry runs, their table length, exit runs, theirs))."""
        # Pow2 padding is shard-divisible on any power-of-two mesh once
        # raised to at least n_shards (enable_mesh enforces pow2).
        n = max(_pad_pow2(len(entries) + n_bulk, 8), self._n_shards)
        m = max(_pad_pow2(len(exits) + m_bulk, 8), self._n_shards)
        k = _pad_pow2(
            max(
                1,
                max((len(op.slots) for op in entries), default=1),
                max((len(g.slots) for g in bulk), default=1),
            ),
            1,
        )
        kd = _pad_pow2(
            max(
                1,
                max((len(op.d_gids) for op in entries), default=1),
                max((len(op.d_gids) for op in exits), default=1),
                max((len(g.d_gids) for g in bulk), default=1),
                max((len(g.d_gids) for g in bulk_exits), default=1),
            ),
            1,
        )

        # Entry staging buffers ride the arena (reused across flushes
        # for repeated (n, k, kd) shapes — the steady state); pooled
        # buffers hold the previous chunk's data, so every field is
        # reset to its encode default here, exactly what the fresh
        # np.zeros/np.full builds used to produce.
        ekey = ("e", n, k, kd)

        def _build_e():
            return (
                np.empty(n, dtype=bool), np.empty(n, dtype=np.int32),
                np.empty(n, dtype=np.int32), np.empty((n, 4), dtype=np.int32),
                np.empty((n, k), dtype=np.int32), np.empty((n, k), dtype=np.int32),
                np.empty(n, dtype=bool), np.empty(n, dtype=bool),
                np.empty(n, dtype=bool), np.empty((n, kd), dtype=np.int32),
            )

        ebufs = self._arena.take(ekey, _build_e) if self._arena else _build_e()
        (e_valid, e_ts, e_acquire, e_rows, e_gid, e_crow, e_prio, e_auth,
         e_cluster, e_dgid) = ebufs
        e_valid.fill(False)
        e_ts.fill(0)
        e_acquire.fill(1)
        e_rows.fill(-1)
        e_gid.fill(-1)
        e_crow.fill(-1)
        e_prio.fill(False)
        e_auth.fill(True)
        e_cluster.fill(True)
        e_dgid.fill(-1)
        ne = len(entries)
        if ne:
            # Flat fields fill via one C-level assignment per column
            # (a per-op per-field Python loop costs ~3× more); only the
            # ragged slot/dgid columns keep the nested loop.
            e_valid[:ne] = True
            e_ts[:ne] = [op.ts for op in entries]
            e_acquire[:ne] = [op.acquire for op in entries]
            e_rows[:ne] = [op.rows for op in entries]
            e_prio[:ne] = [op.prio for op in entries]
            e_auth[:ne] = [
                op.auth_ok and op.custom_veto is None for op in entries
            ]
            e_cluster[:ne] = [op.cluster_blocked_rule is None for op in entries]
            for i, op in enumerate(entries):
                for j, (gid, crow) in enumerate(op.slots[:k]):
                    e_gid[i, j] = gid
                    e_crow[i, j] = crow
                for j, dg in enumerate(op.d_gids[:kd]):
                    e_dgid[i, j] = dg
        off_b = len(entries)
        for g in bulk:
            sl = slice(off_b, off_b + g.n)
            e_valid[sl] = True
            e_ts[sl] = g.ts
            e_acquire[sl] = g.acquire
            e_rows[sl] = g.rows
            for j, (gid, crow) in enumerate(g.slots[:k]):
                e_gid[sl, j] = gid
                e_crow[sl, j] = crow
            for j, dg in enumerate(g.d_gids[:kd]):
                e_dgid[sl, j] = dg
            if g.custom_veto_mask is not None:
                e_auth[sl] = g.auth_ok & ~g.custom_veto_mask
            else:
                e_auth[sl] = g.auth_ok
            off_b += g.n

        xkey = ("x", m, kd)

        def _build_x():
            return (
                np.empty(m, dtype=bool), np.empty(m, dtype=np.int32),
                np.empty(m, dtype=np.int32), np.empty((m, 4), dtype=np.int32),
                np.empty(m, dtype=np.int32), np.empty(m, dtype=np.int32),
                np.empty(m, dtype=np.int32), np.empty((m, kd), dtype=np.int32),
            )

        xbufs = self._arena.take(xkey, _build_x) if self._arena else _build_x()
        x_valid, x_ts, x_count, x_rows, x_rt, x_err, x_thr, x_dgid = xbufs
        x_valid.fill(False)
        x_ts.fill(0)
        x_count.fill(0)
        x_rows.fill(-1)
        x_rt.fill(0)
        x_err.fill(0)
        x_thr.fill(0)
        x_dgid.fill(-1)
        nx = len(exits)
        if nx:
            x_valid[:nx] = True
            x_ts[:nx] = [op.ts for op in exits]
            x_count[:nx] = [op.count for op in exits]
            x_rows[:nx] = [op.rows for op in exits]
            x_rt[:nx] = [op.rt for op in exits]
            x_err[:nx] = [op.err for op in exits]
            x_thr[:nx] = [op.thr for op in exits]
            for i, op in enumerate(exits):
                for j, dg in enumerate(op.d_gids[:kd]):
                    x_dgid[i, j] = dg
        off_x = len(exits)
        for g in bulk_exits:
            sl = slice(off_x, off_x + g.n)
            x_valid[sl] = True
            x_ts[sl] = g.ts
            x_count[sl] = g.count
            x_rows[sl] = g.rows
            x_rt[sl] = g.rt
            x_err[sl] = g.err
            x_thr[sl] = g.thr
            for j, dg in enumerate(g.d_gids[:kd]):
                x_dgid[sl, j] = dg
            off_x += g.n

        e_runs, n_runs = _run_table(
            e_rows, e_ts, len(entries) + n_bulk, self._n_shards
        )
        x_runs, nx_runs = _run_table(x_rows, x_ts, nx + m_bulk, self._n_shards)
        now_host = self.clock.now_ms()
        batch = FlushBatch(
            now=jnp.int32(now_host),
            e_valid=jnp.asarray(e_valid),
            e_ts=jnp.asarray(e_ts),
            e_acquire=jnp.asarray(e_acquire),
            e_rows=jnp.asarray(e_rows),
            e_rule_gid=jnp.asarray(e_gid),
            e_check_row=jnp.asarray(e_crow),
            e_prio=jnp.asarray(e_prio),
            e_auth_ok=jnp.asarray(e_auth),
            e_cluster_ok=jnp.asarray(e_cluster),
            e_dgid=jnp.asarray(e_dgid),
            x_valid=jnp.asarray(x_valid),
            x_ts=jnp.asarray(x_ts),
            x_count=jnp.asarray(x_count),
            x_rows=jnp.asarray(x_rows),
            x_rt=jnp.asarray(x_rt),
            x_err=jnp.asarray(x_err),
            x_thr=jnp.asarray(x_thr),
            x_dgid=jnp.asarray(x_dgid),
            e_run_end=jnp.asarray(e_runs),
            x_run_end=jnp.asarray(x_runs),
        )
        # Staging buffers go back to the arena only after this chunk's
        # results are fetched — jnp.asarray may have zero-copied them
        # into the dispatched computation (see _EncodeArena).
        staging: List[Tuple[tuple, tuple]] = []
        if self._arena is not None:
            staging.append((ekey, ebufs))
            staging.append((xkey, xbufs))
        runs = (n_runs, e_runs.shape[0], nx_runs, x_runs.shape[0])
        return batch, staging, k, kd, now_host, runs

    def _reset_breaker_mirror(self) -> None:
        """Fresh all-CLOSED mirror + a new epoch: deferred fetches
        captured before a rule reload/reset must never diff (or fire)
        against the rebuilt rule world."""
        with self._breaker_mirror_lock:
            self._breaker_state_host = np.zeros(
                self.degrade_dyn.state.shape[0], dtype=np.int32
            )
            self._breaker_epoch += 1
            self._breaker_seq = 0
            self._breaker_applied_seq = 0
            self._breaker_mirror_valid = True

    def _apply_breaker_snapshot(self, epoch, seq, new_state, dindex) -> None:
        """Ordered, epoch-guarded mirror update + observer dispatch.
        Out-of-order deferred fills apply newest-wins: a snapshot older
        than one already applied is dropped (firing it after a newer
        state would time-travel); after an unobserved gap the first
        snapshot resyncs silently."""
        from sentinel_tpu.rules import breaker_events

        with self._breaker_mirror_lock:
            if epoch != self._breaker_epoch or seq <= self._breaker_applied_seq:
                return
            prev = self._breaker_state_host
            if new_state.shape != prev.shape:
                return
            fire = self._breaker_mirror_valid and not np.array_equal(
                new_state, prev
            )
            self._breaker_state_host = new_state
            self._breaker_applied_seq = seq
            self._breaker_mirror_valid = True
        if fire:
            breaker_events.fire_transitions(prev, new_state, dindex)
            cap = self.capture
            if cap is not None and np.any((new_state == 1) & (prev != 1)):
                # A breaker OPENED: pin the traffic that tripped it.
                opened = [
                    r.resource
                    for gid in np.nonzero((new_state == 1) & (prev != 1))[0]
                    if (r := dindex.rule_of_gid(int(gid))) is not None
                ]
                cap.note_breaker_open(opened)

    def _fold_blocked_sketch(self, rows, weights) -> None:
        """Resolve one fetched device top-K (cluster rows → resource
        names) and fold it into the telemetry sketch. Weight 0 rows are
        padding from top_k over an under-full batch."""
        if not self.telemetry.enabled:
            return
        pairs: List[Tuple[str, int]] = []
        n_keys = len(self.nodes)
        for row, w in zip(
            np.asarray(rows).tolist(), np.asarray(weights).tolist()
        ):
            if w <= 0 or not (0 <= row < n_keys):
                continue
            key = self.nodes.key_of(int(row))
            # Node keys are "<kind>:<name>" (metrics/nodes.NodeKind).
            pairs.append((key.partition(":")[2] or key, int(w)))
        self.telemetry.fold_blocked_topk(pairs)

    def _fold_blocked_recount(
        self,
        entries: List[_EntryOp],
        bulk_blocked: Sequence[Tuple[str, int]],
    ) -> None:
        """Host-side exact recount of one chunk's blocked weight per
        resource, folded into the telemetry sketch — the fallback for
        flush paths whose kernel lacks the device top-K fold (the
        sharded mesh flush). Verdicts must already be filled;
        ``bulk_blocked`` is each bulk group's (resource, blocked
        weight)."""
        agg: Dict[str, int] = {}
        for op in entries:
            v = op._verdict
            if v is not None and not v.admitted:
                agg[op.resource] = agg.get(op.resource, 0) + op.acquire
        for resource, w in bulk_blocked:
            if w:
                agg[resource] = agg.get(resource, 0) + w
        self.telemetry.fold_blocked_topk(
            sorted(agg.items(), key=lambda kv: kv[1], reverse=True)[
                : self._blk_topk_k
            ]
        )

    def _fill_results(
        self,
        got,
        entries: List[_EntryOp],
        exits: List[_ExitOp],
        bulk: List[BulkOp],
        bulk_exits: List[_BulkExitOp],
        findex: FlowIndex,
        dindex: DegradeIndex,
        auth_rules: Dict[str, AuthorityRule],
        k: int,
        kd: int,
        breaker_tag: Optional[Tuple[int, int]] = None,
        flush_seq: int = -1,
    ) -> Tuple[List[tuple], int]:
        """Verdict fill for one dispatched chunk from its ALREADY
        FETCHED results; returns the chunk's blocked-verdict block-log
        items and the number of bulk groups left on the speculative
        reconcile path. ``breaker_tag`` is the (epoch, seq) of
        ``got.breaker``. Runs from the chunk record's settle
        (``_PendingChunk.settle``)."""
        admitted, reason, slot_ok = got.admitted, got.reason, got.slot_ok
        wait_ms, sys_type, dslot_ok = got.wait_ms, got.sys_type, got.dslot_ok
        if got.blk is not None:
            self._fold_blocked_sketch(*got.blk)
        if breaker_tag is not None:
            self._apply_breaker_snapshot(
                breaker_tag[0], breaker_tag[1],
                np.asarray(got.breaker, dtype=np.int32).reshape(-1), dindex,
            )
        if got.shaping is not None:
            # Settled shaping dyn columns: re-anchor the host pacer /
            # warm-up mirrors to device truth (the per-drain
            # reconciliation contract of the shaping fast tier).
            self.speculative.reconcile_shaping(
                findex, *(np.asarray(c) for c in got.shaping)
            )
        if got.sketch is not None:
            # Settled sketch candidate table: the promotion/demotion
            # controller evaluates at every drain (runtime/sketch.py).
            self.sketch.on_drain(
                *(np.asarray(c, dtype=np.int32) for c in got.sketch),
                self.clock.now_ms(),
            )
        # One verdict-materialization timestamp for every admission in
        # the chunk (they all settle together; per-op clocks would add
        # a syscall per row for no attribution gain).
        tracer = self.admission_trace
        trace_end = time.perf_counter()
        spec_tier = self.speculative if self.speculative.enabled else None
        # Chunk-local accumulator for the per-resource ledger's single
        # speculative serve notes — flushed in ONE locked call below
        # (metrics/provenance.py write-cadence contract).
        serve_acc: Optional[Dict[Tuple[int, str], list]] = (
            {} if spec_tier is not None and self.resource_metrics.enabled
            else None
        )
        for i, op in enumerate(entries):
            blocked_rule = None
            limit_type = ""
            slot_name = ""
            r = int(reason[i])
            if not admitted[i]:
                if op.custom_veto is not None:
                    slot, veto = op.custom_veto
                    r = E.BLOCK_CUSTOM
                    blocked_rule = veto if veto is not True else None
                    slot_name = getattr(slot, "name", "") or type(slot).__name__
                elif r == E.BLOCK_AUTHORITY:
                    blocked_rule = auth_rules.get(op.resource)
                elif r == E.BLOCK_SYSTEM:
                    limit_type = SYS_TYPE_NAMES.get(int(sys_type[i]), "")
                elif r == E.BLOCK_FLOW:
                    if op.cluster_blocked_rule is not None:
                        blocked_rule = op.cluster_blocked_rule
                        if isinstance(blocked_rule, ParamFlowRule):
                            # A token-server param verdict surfaces as
                            # ParamFlowException, not FlowException
                            # (ParamFlowChecker cluster branch).
                            r = E.BLOCK_PARAM
                    else:
                        for j, (gid, _) in enumerate(op.slots[:k]):
                            if not slot_ok[i, j]:
                                blocked_rule = findex.rule_of_gid(gid)
                                break
                elif r == E.BLOCK_PARAM:
                    blocked_rule = op.p_slots[0].rule if op.p_slots else None
                elif r == E.BLOCK_DEGRADE:
                    for j, dg in enumerate(op.d_gids[:kd]):
                        if not dslot_ok[i, j]:
                            blocked_rule = dindex.rule_of_gid(dg)
                            break
            sv = Verdict(
                admitted=bool(admitted[i]),
                reason=r,
                wait_ms=int(wait_ms[i]),
                blocked_rule=blocked_rule,
                limit_type=limit_type,
                slot_name=slot_name,
            )
            spec_v = op._verdict
            if (
                spec_tier is not None
                and spec_v is not None
                and spec_v.speculative
            ):
                # Settlement of a speculatively-decided op: the caller
                # already acted on the host verdict, so it STAYS the
                # caller-visible one; the device verdict reconciles the
                # mirrors (bucket clamps, gauge compensation, drift
                # accounting) and stamps the trace provenance.
                if serve_acc is not None:
                    key = (op.ts // 1000 * 1000, op.resource)
                    ent = serve_acc.get(key)
                    if ent is None:
                        ent = serve_acc[key] = [0, 0]
                    ent[0] += op.acquire
                    if spec_v.degraded:
                        ent[1] += op.acquire
                match = spec_tier.reconcile_entry(op, spec_v, sv)
                op._pending = None
                if op.trace is not None:
                    tracer.record_admission(
                        op.trace, op.resource, op.origin, op.context_name,
                        spec_v.admitted, spec_v.reason, flush_seq,
                        op.spec_end_pc or trace_end,
                        degraded=spec_v.degraded,
                        provenance="speculative", settled_match=match,
                    )
                    op.trace = None
                continue
            op.verdict = sv
            op._pending = None  # drop the chunk backref once filled
            if op.trace is not None:
                tracer.record_admission(
                    op.trace, op.resource, op.origin, op.context_name,
                    bool(admitted[i]), r, flush_seq, trace_end,
                )
                op.trace = None
        if serve_acc:
            self.resource_metrics.note_serves_batch(serve_acc)
        # Bulk groups: one columnar pass over the chunk's bulk rows
        # [lo, hi). One writable copy of each verdict column; every
        # device-decided group's arrays are disjoint views of it, so a
        # caller writing into one group's array touches no other's.
        lo = len(entries)
        sizes = np.array([g.n for g in bulk], dtype=np.int64)
        ends = np.cumsum(sizes)
        hi = lo + int(sizes.sum())
        adm_c = np.array(admitted[lo:hi])
        rsn_c = np.array(reason[lo:hi], dtype=np.int32)
        wait_c = np.array(wait_ms[lo:hi])
        n_spec = 0
        # Traced groups' rows of the caller-visible columns, recorded in
        # one call once the columns hold every group's verdicts.
        traced: List[tuple] = []
        for g, a, b in zip(bulk, (ends - sizes).tolist(), ends.tolist()):
            if spec_tier is not None and g.spec_admitted is not None:
                # Speculatively-decided group: reconcile against the
                # settled device arrays; the caller-visible results
                # stay the speculative ones (see the singles branch).
                spec_tier.reconcile_bulk(
                    g, adm_c[a:b].copy(), rsn_c[a:b].copy(),
                    dev_slot_ok=np.asarray(slot_ok[lo + a:lo + b]),
                    dev_sys_type=np.asarray(sys_type[lo + a:lo + b]),
                )
                # The block log and the trace read the caller-visible
                # verdicts.
                adm_c[a:b] = g._admitted
                rsn_c[a:b] = g._reason
                g._pending = None
                if g.trace is not None:
                    traced.append((a, b, g.trace, g.resource, g.origin,
                                   g.context_name, g.spec_degraded,
                                   "speculative"))
                    g.trace = None
                n_spec += 1
                continue
            g._admitted = adm = adm_c[a:b]
            g._reason = reasons = rsn_c[a:b]
            if g.custom_veto_mask is not None:
                reasons[~adm & g.custom_veto_mask] = E.BLOCK_CUSTOM
            g._wait_ms = wait_c[a:b]
            g._pending = None  # drop the chunk backref once filled
            if g.trace is not None:
                traced.append((a, b, g.trace, g.resource, g.origin,
                               g.context_name, False, ""))
                g.trace = None
        tracer.record_bulk_groups(traced, adm_c, rsn_c, flush_seq, trace_end)
        acq_c = np.concatenate(
            [g.acquire for g in bulk] or [np.zeros(0, dtype=np.int32)]
        )
        t_grp, t_rsn, t_col, t_w, unattr = _tally_blocked(
            adm_c, rsn_c, acq_c, sizes,
            np.asarray(slot_ok)[lo:hi], np.asarray(dslot_ok)[lo:hi],
            np.array([bool(g.slots) for g in bulk], dtype=bool),
            np.array([bool(g.d_gids) for g in bulk], dtype=bool),
        )

        if got.blk is None and self._blk_topk_k > 0:
            # Kernel paths without the device fold (the sharded mesh
            # flush) still feed the blocked top-K: recount blocked
            # weight host-side from the verdicts just filled — exact,
            # and the data is already on the host.
            grp_w = np.bincount(t_grp, weights=t_w, minlength=len(bulk))
            self._fold_blocked_recount(
                entries,
                [(g.resource, w) for g, w in
                 zip(bulk, grp_w.astype(np.int64).tolist())],
            )

        # ---- block log + metric-extension callbacks ----
        # LogSlot (order −8000) writing sentinel-block.log, and the
        # StatisticSlot entry/exit callback registry (MetricEntryCallback
        # / MetricExitCallback), delivered per flush.
        from sentinel_tpu.metrics.extension import MetricExtensionProvider

        exts = MetricExtensionProvider.get_extensions()
        blocked_items = []
        for op in entries:
            v = op.verdict
            if v is None:
                continue
            if v.admitted:
                if exts:
                    MetricExtensionProvider.on_pass(op.resource, op.acquire, op.args)
            else:
                exc_name = E.exc_name_for_code(v.reason)
                limit_app = getattr(v.blocked_rule, "limit_app", None) or "default"
                blocked_items.append(
                    (op.resource, exc_name, limit_app, op.origin, op.acquire)
                )
                if exts:
                    # Extensions receive a real BlockError (the contract
                    # mirrors the reference's BlockException argument).
                    err = E.error_for_verdict(
                        v.reason, op.resource, limit_type=v.limit_type,
                        slot_name=v.slot_name, rule=v.blocked_rule,
                    )
                    MetricExtensionProvider.on_blocked(
                        op.resource, op.acquire, op.origin, err, op.args
                    )
        # Bulk groups: one block-log item per key of the tally (the
        # block log counts per (resource, exc, limitApp, origin) key, so
        # summed items per key are exact). Flow/degrade blocks attribute
        # the blocking rule's limitApp like the singles path — first
        # failing slot per entry. A flow/degrade row with no failing
        # slot is a speculative block the DEVICE passed (mirror and
        # device picked different individuals — structural under
        # drift): it sums under "default", logged only when nonzero.
        for gi, r, c, w in zip(
            t_grp.tolist(), t_rsn.tolist(), t_col.tolist(), t_w.tolist()
        ):
            g = bulk[gi]
            rule = None
            if c == unattr:
                if not w and (
                    (r == E.BLOCK_FLOW and g.slots)
                    or (r == E.BLOCK_DEGRADE and g.d_gids)
                ):
                    continue
                if r == E.BLOCK_AUTHORITY:
                    rule = auth_rules.get(g.resource)
            elif r == E.BLOCK_FLOW:
                if c < len(g.slots):
                    rule = findex.rule_of_gid(g.slots[c][0])
            elif c < len(g.d_gids):
                rule = dindex.rule_of_gid(g.d_gids[c])
            blocked_items.append((
                g.resource, E.exc_name_for_code(r),
                getattr(rule, "limit_app", None) or "default", g.origin, w,
            ))
        if exts and bulk:
            # Aggregated extension calls, per group in submit order: its
            # blocks per reason ascending (the tally's order), then its
            # passes.
            blocked_w: Dict[int, Dict[int, int]] = {}
            for gi, r, w in zip(t_grp.tolist(), t_rsn.tolist(), t_w.tolist()):
                per_r = blocked_w.setdefault(gi, {})
                per_r[r] = per_r.get(r, 0) + w
            adm_g = np.repeat(np.arange(len(bulk)), sizes)[adm_c]
            n_pass = np.bincount(adm_g, minlength=len(bulk)).tolist()
            w_pass = np.bincount(
                adm_g, weights=acq_c[adm_c], minlength=len(bulk)
            ).astype(np.int64).tolist()
            for gi, g in enumerate(bulk):
                for r, w in blocked_w.get(gi, {}).items():
                    MetricExtensionProvider.on_blocked(
                        g.resource, w, g.origin,
                        E.error_for_verdict(r, g.resource), (),
                    )
                if n_pass[gi]:
                    MetricExtensionProvider.on_pass(g.resource, w_pass[gi], ())
        if exts:
            for x in exits:
                if x.resource is not None and x.thr < 0:
                    MetricExtensionProvider.on_complete(x.resource, x.rt, x.count, x.err)
            for gx in bulk_exits:
                if gx.resource is not None and gx.thr < 0:
                    MetricExtensionProvider.on_complete(
                        gx.resource, _weighted_rt(gx), int(gx.count.sum()),
                        int(gx.err.sum()),
                    )
        from sentinel_tpu.core.slots import SlotChainRegistry

        if SlotChainRegistry.slots():
            for x in exits:
                if x.resource is not None and x.thr < 0:
                    SlotChainRegistry.on_exit(x.resource, x.rt, x.count, x.err)
            for gx in bulk_exits:
                if gx.resource is not None and gx.thr < 0:
                    SlotChainRegistry.on_exit(
                        gx.resource, _weighted_rt(gx), int(gx.count.sum()),
                        int(gx.err.sum()),
                    )
        return blocked_items, n_spec

    def _encode_shaping(
        self, entries: List[_EntryOp], bulk: List[BulkOp], k: int, findex: FlowIndex
    ) -> Tuple[Optional[ShapingBatch], int, int]:
        """Gather (entry, slot) pairs governed by shaping controllers
        into the compact arrays the pacer recurrence consumes, plus the
        host-known rounds bound (max items per rule, pow2-bucketed; 0 →
        scan fallback) and the item count. (None, 1, 0) when the batch
        touches no shaping rules (the fast path). Bulk groups contribute column blocks (an
        item per group entry per shaping slot) without per-entry
        Python."""
        sg = findex.shaping_gids
        if not sg:
            return None, 1, 0
        items = []
        for i, op in enumerate(entries):
            for j, (gid, crow) in enumerate(op.slots[:k]):
                if gid in sg:
                    items.append((i * k + j, gid, crow, i, op.ts, op.acquire))
        cols: List[Tuple[np.ndarray, ...]] = []
        if items:
            arr = np.asarray(
                [(fp, g, r, i, t, a) for fp, g, r, i, t, a in items], dtype=np.int32
            )
            cols.append(
                (arr[:, 0], arr[:, 1], arr[:, 2], arr[:, 3], arr[:, 4], arr[:, 5])
            )
        off = len(entries)
        for g in bulk:
            for j, (gid, crow) in enumerate(g.slots[:k]):
                if gid in sg:
                    ei = np.arange(off, off + g.n, dtype=np.int32)
                    cols.append(
                        (
                            ei * k + j,
                            np.full(g.n, gid, dtype=np.int32),
                            np.full(g.n, crow, dtype=np.int32),
                            ei,
                            g.ts,
                            g.acquire,
                        )
                    )
            off += g.n
        if not cols:
            return None, 1, 0
        flat_pos, gid, row, eidx, ts, acquire = (
            np.concatenate([c[a] for c in cols]) for a in range(6)
        )
        total = flat_pos.shape[0]
        s = _pad_pow2(total, 8)
        pad = s - total

        def _p(a, fill=0):
            return np.pad(a, (0, pad), constant_values=fill) if pad else a

        valid = _p(np.ones(total, dtype=bool))
        return ShapingBatch(
            valid=jnp.asarray(valid),
            gid=jnp.asarray(_p(gid)),
            row=jnp.asarray(_p(row)),
            eidx=jnp.asarray(_p(eidx)),
            flat_pos=jnp.asarray(_p(flat_pos)),
            ts=jnp.asarray(_p(ts)),
            acquire=jnp.asarray(_p(acquire, 1)),
        ), self._shaping_rounds_for(gid, ts, acquire), total

    @staticmethod
    def _shaping_rounds_for(gid, ts, acquire) -> int:
        """Host-known shaping execution mode: −1 selects the closed form
        (every item at one ts with one acquire ≥ 1, any mix of the three
        shaping behaviors — the columnar-bulk shape); otherwise the pow2
        rounds bound (0 = scan), which mixed-timestamp batches keep."""
        if (
            gid.shape[0] > 0
            and ts.min() == ts.max()
            and acquire.min() == acquire.max()
            and acquire.min() >= 1
        ):
            return -1
        return _rounds_bucket(gid)

    def entry_sync(
        self,
        resource: str,
        context_name: str = C.CONTEXT_DEFAULT_NAME,
        origin: str = "",
        acquire: int = 1,
        entry_type: C.EntryType = C.EntryType.OUT,
        prio: bool = False,
        args: Sequence[object] = (),
    ) -> Tuple[Optional[_EntryOp], Verdict]:
        """Submit + flush: synchronous SphU.entry semantics.

        With the speculative tier enabled the verdict comes straight
        from the host mirror (microseconds, tagged
        ``Verdict.speculative``) while the op still rides the flush
        pipeline for authoritative settlement — no blocking device
        round-trip on this path unless the tier declines the op
        (prio/shaping/system semantics) or is suspended by the
        drift valve."""
        op = self.submit_entry(
            resource, context_name, origin, acquire, entry_type, prio,
            args=args, speculate=True,
        )
        if op is None:
            return None, Verdict(True, E.PASS, 0, None)  # over cap: pass-through
        # Speculation ran inside submit_entry BEFORE the op became
        # visible to any flush, so a settle that already landed
        # reconciled against it (and kept it caller-visible) rather
        # than racing it. A non-speculative _verdict here means the
        # tier declined and a flush-on-size settled the op on-device.
        v = op._verdict
        if v is not None and v.reason == E.BLOCK_SHED:
            # The ingest valve shed it at submit: nothing is queued,
            # nothing to flush — the fast distinct verdict IS the
            # contract (runtime/ingest.py).
            return op, v
        if v is not None and v.speculative:
            self._spec_maybe_settle()
            return op, v
        self.flush()
        assert op.verdict is not None
        return op, op.verdict

    def _spec_maybe_settle(self) -> None:
        """Settlement cadence of the speculative fast path: dispatch an
        async settle flush once enough ops are pending (bounding the
        reconciliation lag without a blocking flush per entry), and run
        a full flush when an automatic failover recovery is due — the
        speculative path must not starve recovery just because it never
        blocks on the device."""
        fo = self.failover
        if fo.armed and not fo.healthy:
            if fo.recovery_due(self.clock.now_ms()):
                self.flush()
            return
        spec = self.speculative
        with self._lock:
            if self._auto_flush_thread is not None:
                # The background flusher owns settlement: the admission
                # thread then NEVER pays a device dispatch — the
                # deployment shape behind the sub-100 µs p99 target.
                return
            pending = (
                len(self._entries) + len(self._exits)
                + self._bulk_pending_n + self._bulk_exit_pending_n
            )
        if pending >= spec.flush_batch:
            self.flush_async()

    # ------------------------------------------------------------------
    # reads (command/metric plane; used heavily by tests)
    # ------------------------------------------------------------------
    def _row_stats(self, row: int, now: Optional[int] = None) -> Dict[str, float]:
        # Under the flush lock: a concurrent flush donates self.stats to
        # the kernel, which would invalidate the buffers mid-read.
        with self._flush_lock:
            return self._row_stats_locked(row, now)

    def _all_stats_arrays(self, now: Optional[int] = None):
        """One device round-trip for every row's windowed stats —
        readers that touch many rows (a Prometheus scrape, the metric
        timer) must not pay a full-tensor reduction per row."""
        from sentinel_tpu.metrics.nodes import occupied_in_window, waiting_tokens

        now_i = jnp.int32(self.clock.now_ms() if now is None else now)
        return jax.device_get(
            (
                ma.window_sums(_ncfg.SECOND_CFG, self.stats.second, now_i),
                ma.window_sums(MINUTE_CFG, self.stats.minute, now_i),
                ma.window_min_rt(_ncfg.SECOND_CFG, self.stats.second, now_i),
                self.stats.threads,
                occupied_in_window(self.stats, now_i),
                waiting_tokens(self.stats, now_i),
            )
        )

    def rows_stats(
        self, rows: Sequence[int], now: Optional[int] = None
    ) -> Dict[int, Dict[str, float]]:
        """Stats dicts for many rows with one batched device read."""
        if not rows:
            return {}
        with self._flush_lock:
            arrays = self._all_stats_arrays(now)
        return {row: self._stats_from_arrays(arrays, row) for row in rows}

    def _row_stats_locked(self, row: int, now: Optional[int] = None) -> Dict[str, float]:
        return self._stats_from_arrays(self._all_stats_arrays(now), row)

    @staticmethod
    def _stats_from_arrays(arrays, row: int) -> Dict[str, float]:
        sec_all, minute_all, min_rt_all, threads_all, occ_all, wait_all = arrays
        sec = np.asarray(sec_all[row])
        minute = np.asarray(minute_all[row])
        min_rt = int(min_rt_all[row])
        threads = int(threads_all[row])
        occ_cur = int(occ_all[row])
        waiting = int(wait_all[row])
        interval_sec = _ncfg.SECOND_CFG.interval_ms / 1000.0
        success = int(sec[MetricEvent.SUCCESS])
        rt_sum = int(sec[MetricEvent.RT])
        return {
            # Matured borrowed tokens count as pass, like the reference
            # materialising them into the bucket on reset.
            "pass_qps": (int(sec[MetricEvent.PASS]) + occ_cur) / interval_sec,
            "waiting": waiting,
            "block_qps": sec[MetricEvent.BLOCK] / interval_sec,
            "success_qps": success / interval_sec,
            "exception_qps": sec[MetricEvent.EXCEPTION] / interval_sec,
            # occupiedPassQps reads the minute counter (StatisticNode.
            # java:195-198: rollingCounterInMinute.occupiedPass() / 60).
            "occupied_pass_qps": minute[MetricEvent.OCCUPIED_PASS]
            / (MINUTE_CFG.interval_ms / 1000.0),
            # StatisticNode.avgRt: rt sum / success count (0-safe).
            "avg_rt": (rt_sum / success) if success > 0 else 0.0,
            "min_rt": min_rt,
            "cur_thread_num": threads,
            "total_pass_minute": int(minute[MetricEvent.PASS]),
            "total_block_minute": int(minute[MetricEvent.BLOCK]),
            "total_success_minute": int(minute[MetricEvent.SUCCESS]),
            "total_exception_minute": int(minute[MetricEvent.EXCEPTION]),
        }

    def cluster_node_stats(self, resource: str, flush: bool = True) -> Optional[Dict[str, float]]:
        if flush:
            self.flush()
        row = self.nodes.lookup_cluster_row(resource)
        if row is None:
            return None
        return self._row_stats(row)

    def entry_node_stats(self, flush: bool = True) -> Dict[str, float]:
        if flush:
            self.flush()
        return self._row_stats(self.nodes.entry_node_row)

    def reset(self) -> None:
        # Settle dispatched-but-unfetched flush_async chunks FIRST:
        # discarding them would deadlock readers waiting on their
        # records, and leaving them queued would deliver pre-reset
        # block-log records (or a pre-reset device failure) into the
        # first post-reset flush. A failed settle is logged, not
        # raised — reset must complete regardless.
        try:
            self.drain()
        except Exception:
            from sentinel_tpu.utils.record_log import record_log

            record_log.error(
                "[Engine] settling pre-reset async flushes failed", exc_info=True
            )
        self.failover.reset()
        self.speculative.reset()
        self.ingest.reset()
        self.resource_metrics.reset()
        self.sketch.reset()
        if self.ipc_plane is not None:
            # The plane's live-admission ledgers reference the node
            # rows this reset is about to rebuild — drop them (and
            # re-intern) rather than release stale rows later.
            self.ipc_plane.on_engine_reset()
        with self._flush_lock, self._lock:
            self._entries.clear()
            self._exits.clear()
            self._bulk_entries.clear()
            self._bulk_exits.clear()
            self._bulk_pending_n = 0
            self._bulk_exit_pending_n = 0
            self._rows_cache = {}
            self.nodes.clear()
            self.stats = make_stats(self.stats.n_rows)
            self.flow_index = FlowIndex([], cold_factor=config.cold_factor)
            self.flow_dyn = self.flow_index.make_dyn_state()
            self.degrade_index = DegradeIndex([])
            self.degrade_dyn = self.degrade_index.make_dyn_state()
            self._reset_breaker_mirror()
            self.param_index = ParamIndex({})
            self.param_dyn = make_param_state(8)
            self.system_config = None
            self.authority_rules = {}
