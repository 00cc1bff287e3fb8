"""Admission tracing: per-request verdict provenance + W3C trace context.

PR 3's flight recorder made the *engine* observable (per-flush spans,
histograms, blocked-resource sketch) but left every blocked request
anonymous: an operator could see THAT a resource was being throttled,
not WHY a particular call was — which rule family, decided in which
flush, on behalf of which upstream. This module is the request-level
half of that story, the batched analog of the reference's LogSlot →
EagleEye pipeline with trace identity attached:

* :class:`TraceContext` + :func:`parse_traceparent` /
  ``TraceContext.to_traceparent`` — W3C trace-context
  (https://www.w3.org/TR/trace-context/) parse and render, used by
  every adapter for inbound extraction and by the outbound clients for
  injection, so a block is attributable ACROSS service hops;
* :class:`AdmissionTracer` — an engine-scoped, bounded ring of
  :class:`AdmissionRecord` per-admission provenance records
  (trace/span ids, resource, origin, context name, verdict reason code
  from the flush kernel's ``reason`` tensor, the deciding flush-span
  seq from the PR 3 TelemetryBus, and enqueue→verdict latency), fed by
  ``Engine._fill_results`` at verdict materialization — so records are
  exact for the pipelined (depth-K) flush path too;
* head-based probabilistic sampling plus an **always-sample-blocked**
  mode: the head decision (one ``random()`` per submit, or the inbound
  traceparent's sampled flag, honored as-is) bounds steady-state cost,
  while blocked verdicts are recorded regardless — the same
  bounded-state discipline as the data-plane heavy-hitter work
  (Sivaraman et al., arXiv:1611.04825): keep per-key state only for
  the traffic that matters, decide cheaply for the rest.

Hot-path contract: when ``sentinel.tpu.trace.enabled`` is false the
engine pays exactly one bool read per submit and one ``None`` check
per op at fill; when true, an UNSAMPLED admitted op pays one
``perf_counter`` + one ``random()`` at submit and nothing at fill.
Trace/span ids are minted lazily at RECORD time, never for unsampled
traffic.

Config keys (all ``sentinel.tpu.trace.*``)::

    sentinel.tpu.trace.enabled         default true
    sentinel.tpu.trace.ring            record ring capacity, default 2048
    sentinel.tpu.trace.sample.rate     head sample probability, default 0.01
    sentinel.tpu.trace.sample.blocked  always record blocked, default true
    sentinel.tpu.trace.bulk.cap        rows recorded per bulk group per
                                       class (blocked / sampled), default 4
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from sentinel_tpu.core import errors as E
from sentinel_tpu.core.context import ContextUtil
from sentinel_tpu.metrics.histogram import LatencyHistogram
from sentinel_tpu.utils.config import config

TRACEPARENT_HEADER = "traceparent"
TRACESTATE_HEADER = "tracestate"

_rand = random.Random()
_HEX = "0123456789abcdef"


def new_trace_id() -> str:
    """A random 32-hex-char (128-bit) nonzero W3C trace id."""
    return f"{_rand.getrandbits(128) or 1:032x}"


def new_span_id() -> str:
    """A random 16-hex-char (64-bit) nonzero W3C span id."""
    return f"{_rand.getrandbits(64) or 1:016x}"


def _is_hex(s: str) -> bool:
    return bool(s) and all(c in _HEX for c in s)


@dataclass(frozen=True, slots=True)
class TraceContext:
    """One hop's W3C trace identity: the trace id, the CURRENT span id
    (the parent of any span created under it), the sampled flag, and
    the opaque ``tracestate`` passed through unmodified (the spec's
    vendor list — this library neither reads nor edits it)."""

    trace_id: str
    span_id: str
    sampled: bool = True
    tracestate: str = ""

    def child(self) -> "TraceContext":
        """A child hop: same trace, fresh span id, decision inherited —
        what outbound injection writes on the wire."""
        return TraceContext(self.trace_id, new_span_id(), self.sampled,
                            self.tracestate)

    def to_traceparent(self) -> str:
        return (
            f"00-{self.trace_id}-{self.span_id}-"
            f"{'01' if self.sampled else '00'}"
        )


def parse_traceparent(
    value: Optional[str], tracestate: str = ""
) -> Optional[TraceContext]:
    """Parse a W3C ``traceparent`` header; None on anything invalid
    (the spec says a receiver that cannot parse MUST restart the trace
    — returning None lets the caller do exactly that). Future versions
    (``version != 00``) are accepted as long as the four base fields
    parse, per the spec's forward-compatibility rule; version ``ff``
    is explicitly invalid."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id, flags = parts[0], parts[1], parts[2], parts[3]
    if len(version) != 2 or not _is_hex(version) or version == "ff":
        return None
    if version == "00" and len(parts) != 4:
        return None
    if len(trace_id) != 32 or not _is_hex(trace_id) or trace_id == "0" * 32:
        return None
    if len(span_id) != 16 or not _is_hex(span_id) or span_id == "0" * 16:
        return None
    if len(flags) != 2 or not _is_hex(flags):
        return None
    return TraceContext(
        trace_id=trace_id,
        span_id=span_id,
        sampled=bool(int(flags, 16) & 0x01),
        tracestate=tracestate or "",
    )


def inject_trace_headers(headers, parent: Optional[TraceContext] = None):
    """Outbound W3C injection: write ``traceparent`` (and
    ``tracestate``) for a CHILD span of the ambient trace (or an
    explicit ``parent``) into a mutable header mapping. No ambient
    trace → no-op returning None: outbound guards never mint trace ids
    for untraced calls (the head decision belongs to the inbound edge).
    Returns the injected child context."""
    tc = parent if parent is not None else ContextUtil.get_trace()
    if tc is None:
        return None
    child = tc.child()
    headers[TRACEPARENT_HEADER] = child.to_traceparent()
    if child.tracestate:
        headers[TRACESTATE_HEADER] = child.tracestate
    return child


class TraceTag(NamedTuple):
    """The per-op submit-time stamp (``_EntryOp.trace`` /
    ``BulkOp.trace``): the inbound parent (if any), the head sampling
    decision, and the enqueue ``perf_counter``. Ids are minted at
    record time, so an unsampled tag allocates nothing but this tuple."""

    parent: Optional[TraceContext]
    sampled: bool
    t0: float


@dataclass(slots=True)
class AdmissionRecord:
    """One sampled admission's verdict provenance."""

    trace_id: str
    span_id: str
    parent_span_id: str  # inbound hop's span id ("" when trace-rooted)
    resource: str
    origin: str
    context_name: str
    admitted: bool
    reason: int  # errors.PASS / BLOCK_*
    reason_name: str  # shared errors.BLOCK_EXC_NAMES spelling; "" = pass
    flush_seq: int  # deciding FlushSpan.flush_id (-1: telemetry off)
    t0: float  # perf_counter at enqueue (tracedump timeline)
    latency_ms: float  # enqueue -> verdict materialized
    head_sampled: bool  # False = recorded by the always-blocked mode
    # Verdict provenance: decided by the host fallback admitter while
    # the engine was DEGRADED (reason BLOCK_FAILOVER for policy sheds;
    # degraded ADMITS keep reason PASS but carry this mark).
    degraded: bool = False
    # Tier provenance: "device" (settled on-device, the default),
    # "degraded" (host fallback, device lost), or "speculative" (host
    # fast tier — runtime/speculative.py; the speculative→settled
    # story: ``flush_seq`` names the settling flush and
    # ``settled_match`` whether the device agreed; None = never
    # settled, e.g. quarantined by a device fault).
    provenance: str = "device"
    settled_match: Optional[bool] = None

    def as_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            "resource": self.resource,
            "origin": self.origin,
            "context_name": self.context_name,
            "admitted": self.admitted,
            "reason": self.reason,
            "reason_name": self.reason_name,
            "flush_seq": self.flush_seq,
            "latency_ms": round(self.latency_ms, 4),
            "head_sampled": self.head_sampled,
            "degraded": self.degraded,
            "provenance": self.provenance,
            "settled_match": self.settled_match,
        }


def _make_record(
    tag: TraceTag,
    resource: str,
    origin: str,
    context_name: str,
    admitted: bool,
    reason: int,
    flush_seq: int,
    latency_ms: float,
    degraded: bool,
    provenance: str,
    settled_match: Optional[bool] = None,
) -> AdmissionRecord:
    """One record under the tag's trace (trace-rooted without a parent);
    an empty ``provenance`` reads "degraded" or "device"."""
    parent = tag.parent
    return AdmissionRecord(
        trace_id=parent.trace_id if parent is not None else new_trace_id(),
        span_id=new_span_id(),
        parent_span_id=parent.span_id if parent is not None else "",
        resource=resource,
        origin=origin,
        context_name=context_name,
        admitted=admitted,
        reason=reason,
        reason_name="" if admitted else E.exc_name_for_code(reason),
        flush_seq=int(flush_seq),
        t0=tag.t0,
        latency_ms=latency_ms,
        head_sampled=tag.sampled,
        degraded=degraded,
        provenance=provenance or ("degraded" if degraded else "device"),
        settled_match=settled_match,
    )


def _first_rows(
    mask: np.ndarray,
    starts: np.ndarray,
    stops: np.ndarray,
    want: np.ndarray,
    cap: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """(group, row) of the first ``cap`` set rows of ``mask`` in each
    group's range ``[starts, stops)`` whose ``want`` is set; groups in
    order, rows ascending."""
    hits = np.flatnonzero(mask)
    lo = np.searchsorted(hits, starts)
    n = np.minimum(np.searchsorted(hits, stops) - lo, cap) * want
    take = np.repeat(lo - (np.cumsum(n) - n), n) + np.arange(int(n.sum()))
    return np.repeat(np.arange(starts.size), n), hits[take]


class AdmissionTracer:
    """Engine-scoped sampled admission-trace ring (one per
    :class:`~sentinel_tpu.runtime.engine.Engine`, like the
    TelemetryBus)."""

    def __init__(
        self,
        enabled: Optional[bool] = None,
        ring: Optional[int] = None,
        sample_rate: Optional[float] = None,
        sample_blocked: Optional[bool] = None,
        bulk_cap: Optional[int] = None,
    ) -> None:
        self.enabled = (
            config.get_bool(config.TRACE_ENABLED, True)
            if enabled is None
            else bool(enabled)
        )
        self.ring_size = max(
            1,
            ring if ring is not None else config.get_int(config.TRACE_RING, 2048),
        )
        rate = (
            sample_rate
            if sample_rate is not None
            else config.get_float(config.TRACE_SAMPLE_RATE, 0.01)
        )
        self.sample_rate = min(1.0, max(0.0, float(rate)))
        self.sample_blocked = (
            config.get_bool(config.TRACE_SAMPLE_BLOCKED, True)
            if sample_blocked is None
            else bool(sample_blocked)
        )
        self.bulk_cap = max(
            0,
            bulk_cap
            if bulk_cap is not None
            else config.get_int(config.TRACE_BULK_CAP, 4),
        )
        self._records: "deque[AdmissionRecord]" = deque(maxlen=self.ring_size)
        self._lock = threading.Lock()
        self.counters: Dict[str, int] = {
            "recorded": 0,
            "head_sampled": 0,
            "blocked_sampled": 0,
        }
        # Tagged but neither head- nor blocked-sampled. Kept OUTSIDE
        # the lock: at the default 1% rate this bumps for ~99% of ops
        # on the verdict-fill hot path, and a diagnostic counter does
        # not justify a mutex acquisition per op (int += under the GIL
        # is close enough; exactness is not load-bearing).
        self._skipped = 0
        # Sampled admission enqueue→verdict latencies — the histogram
        # whose `_bucket` series carries the exemplars below, so
        # exemplar values and bucket counts measure the SAME quantity.
        self.hist_latency = LatencyHistogram()
        # Latest exemplar per latency bucket: idx -> (trace_id, ms).
        self._exemplars: Dict[int, Tuple[str, float]] = {}

    # ------------------------------------------------------------------
    # submit hot path
    # ------------------------------------------------------------------
    def make_tag(self) -> TraceTag:
        """The per-submit stamp. An inbound traceparent's sampled flag
        is the head decision (propagated, per W3C); trace-rooted
        admissions sample probabilistically."""
        parent = ContextUtil.get_trace()
        if parent is not None:
            sampled = parent.sampled
        else:
            r = self.sample_rate
            sampled = r >= 1.0 or (r > 0.0 and _rand.random() < r)
        return TraceTag(parent, sampled, time.perf_counter())

    # ------------------------------------------------------------------
    # verdict materialization (engine fill path)
    # ------------------------------------------------------------------
    def record_admission(
        self,
        tag: TraceTag,
        resource: str,
        origin: str,
        context_name: str,
        admitted: bool,
        reason: int,
        flush_seq: int,
        end_pc: float,
        degraded: bool = False,
        provenance: str = "",
        settled_match: Optional[bool] = None,
    ) -> Optional[AdmissionRecord]:
        """Record one settled admission if the tag (or the blocked
        override) selects it; returns the record or None."""
        if not (tag.sampled or (not admitted and self.sample_blocked)):
            self._skipped += 1
            return None
        rec = _make_record(
            tag, resource, origin, context_name, bool(admitted), int(reason),
            flush_seq, max(0.0, (end_pc - tag.t0) * 1e3), degraded,
            provenance, settled_match,
        )
        self.hist_latency.record(rec.latency_ms)
        bucket = self.hist_latency.bucket_of(rec.latency_ms)
        with self._lock:
            self._records.append(rec)
            self.counters["recorded"] += 1
            if tag.sampled:
                self.counters["head_sampled"] += 1
            else:
                self.counters["blocked_sampled"] += 1
            self._exemplars[bucket] = (rec.trace_id, rec.latency_ms)
        return rec

    def record_bulk(
        self,
        tag: TraceTag,
        resource: str,
        origin: str,
        context_name: str,
        admitted,
        reasons,
        flush_seq: int,
        end_pc: float,
        degraded: bool = False,
        provenance: str = "",
    ) -> None:
        """Bounded per-row records for one bulk group (see
        :meth:`record_bulk_groups`)."""
        self.record_bulk_groups(
            [(0, np.asarray(admitted).size, tag, resource, origin,
              context_name, degraded, provenance)],
            admitted, reasons, flush_seq, end_pc,
        )

    def record_bulk_groups(
        self,
        groups: Sequence[tuple],
        admitted,
        reasons,
        flush_seq: int,
        end_pc: float,
    ) -> None:
        """Bounded per-row records for a chunk's bulk groups, from one
        pass over its verdict columns. ``groups`` holds one ``(start,
        stop, tag, resource, origin, context_name, degraded,
        provenance)`` per group, in order, its rows ``[start, stop)`` of
        ``admitted`` / ``reasons``. Each group records up to
        ``bulk_cap`` blocked rows (always-blocked mode, or a sampled
        head tag), then, when its head tag sampled, up to ``bulk_cap``
        admitted rows — never a full walk of a group. Bulk rows have no
        per-request inbound identity, so each record is trace-rooted
        unless the SUBMITTING call carried one (then all rows share its
        trace).

        Only the records the ring keeps are built. The counters, the
        latency histogram and the exemplars count every record, as one
        :meth:`record_admission` per row would: a chunk of thousands of
        blocked groups records more rows than the ring holds."""
        cap = self.bulk_cap
        if cap <= 0 or not groups:
            return
        adm = np.asarray(admitted)
        starts = np.array([gr[0] for gr in groups], dtype=np.intp)
        stops = np.array([gr[1] for gr in groups], dtype=np.intp)
        sampled = np.array([gr[2].sampled for gr in groups], dtype=bool)
        gi_b, rows_b = _first_rows(
            ~adm, starts, stops, sampled | self.sample_blocked, cap
        )
        gi_a, rows_a = _first_rows(adm, starts, stops, sampled, cap)
        gi = np.concatenate((gi_b, gi_a))
        n = gi.size
        if not n:
            return
        # Each group's blocked rows, then its admitted rows (stable).
        order = np.lexsort((np.arange(n) >= gi_b.size, gi))
        gi = gi[order]
        rows = np.concatenate((rows_b, rows_a))[order]
        per_group = np.bincount(gi, minlength=len(groups))
        js = np.flatnonzero(per_group).tolist()
        lat = {j: max(0.0, (end_pc - groups[j][2].t0) * 1e3) for j in js}
        buckets = self.hist_latency.record_runs(
            list(lat.values()), per_group[js].tolist()
        )
        first_kept = max(0, n - self.ring_size)
        kept = []
        for j, i in zip(gi[first_kept:].tolist(), rows[first_kept:].tolist()):
            _, _, tag, resource, origin, ctx, degraded, prov = groups[j]
            kept.append(_make_record(
                tag, resource, origin, ctx, bool(adm[i]), int(reasons[i]),
                flush_seq, lat[j], degraded, prov,
            ))
        # Per bucket, its last record (groups in order, so a later
        # group's last record replaces an earlier one's).
        last = dict(zip(buckets, (np.cumsum(per_group[js]) - 1).tolist()))
        exemplars = {}
        for b, pos in last.items():
            j = int(gi[pos])
            if pos >= first_kept:
                tid = kept[pos - first_kept].trace_id
            else:
                parent = groups[j][2].parent
                tid = parent.trace_id if parent is not None else new_trace_id()
            exemplars[b] = (tid, lat[j])
        n_head = int(sampled[gi].sum())
        with self._lock:
            self._records.extend(kept)
            self.counters["recorded"] += n
            self.counters["head_sampled"] += n_head
            self.counters["blocked_sampled"] += n - n_head
            self._exemplars.update(exemplars)

    # ------------------------------------------------------------------
    # readers
    # ------------------------------------------------------------------
    def records(
        self,
        n: Optional[int] = None,
        resource: Optional[str] = None,
        reason: Optional[int] = None,
    ) -> List[AdmissionRecord]:
        """Ring snapshot, oldest first, optionally filtered by resource
        and/or reason code; ``n`` keeps only the newest n AFTER the
        filters (the ``traces`` command's semantics)."""
        with self._lock:
            out = list(self._records)
        if resource is not None:
            out = [r for r in out if r.resource == resource]
        if reason is not None:
            out = [r for r in out if r.reason == reason]
        if n is not None and n > 0:
            out = out[-n:]
        return out

    def counters_snapshot(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self.counters)
        out["skipped"] = self._skipped
        return out

    def exemplars(self) -> Dict[int, Tuple[str, float]]:
        """Latest (trace_id, latency_ms) exemplar per ``hist_latency``
        bucket — the OpenMetrics exemplar payload for
        ``transport/prometheus.py``."""
        with self._lock:
            return dict(self._exemplars)

    def snapshot(self) -> dict:
        """Config + counters view for the ``traces`` command."""
        return {
            "enabled": self.enabled,
            "ring_size": self.ring_size,
            "sample_rate": self.sample_rate,
            "sample_blocked": self.sample_blocked,
            "bulk_cap": self.bulk_cap,
            "counters": self.counters_snapshot(),
            "latency_ms": self.hist_latency.summary(),
        }

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self._exemplars.clear()
            for k in self.counters:
                self.counters[k] = 0
        self._skipped = 0
        self.hist_latency.reset()
