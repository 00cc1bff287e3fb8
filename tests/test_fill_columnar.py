"""The bulk verdict fill is one columnar pass over a chunk's bulk rows.

``Engine._fill_results`` copies each verdict column once a chunk, hands
every device-decided group disjoint views of the copies, and builds the
block-log items from one tally of the blocked rows (``_tally_blocked``).
These tests hold it to a plain per-group reference of the attribution it
replaced — first failing flow/degrade slot per row, grouped by slot,
unattributed rows under "default" — item for item and in order, with the
metric-extension calls, the blocked top-K recount and every group's
verdict arrays, on seeded random chunks; and, through real flushes, that
the arrays are the device rows, stay disjoint and valid, and come out the
same from the depth-K settle as from depth 0.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import pytest

from sentinel_tpu.core import errors as E
from sentinel_tpu.metrics.extension import MetricExtension, MetricExtensionProvider
from sentinel_tpu.models.rules import AuthorityRule, DegradeRule, FlowRule
from sentinel_tpu.runtime.engine import BulkOp, Engine, _ChunkResults, _EntryOp
from sentinel_tpu.utils.clock import ManualClock

K, KD = 3, 3  # the chunk's flow and degrade slot widths
_BLOCKS = [E.BLOCK_FLOW, E.BLOCK_DEGRADE, E.BLOCK_SYSTEM, E.BLOCK_AUTHORITY,
           E.BLOCK_PARAM]


@dataclass
class _Rule:
    limit_app: str


class _Index:
    """``rule_of_gid`` over a fixed table; unknown gids read None."""

    def __init__(self, apps):
        self._rules = {gid: _Rule(app) for gid, app in apps.items()}

    def rule_of_gid(self, gid):
        return self._rules.get(gid)


class _Recorder(MetricExtension):
    def __init__(self):
        self.events = []

    def add_pass(self, resource, n, *args):
        self.events.append(("pass", resource, n))

    def add_block(self, resource, n, origin, block_error, *args):
        self.events.append(("block", resource, n, origin, type(block_error)))


class _SpecStub:
    """Stands in for the speculative tier: records each bulk reconcile."""

    enabled = True

    def __init__(self):
        self.reconciled = []

    def reconcile_bulk(self, g, dev_admitted, dev_reason, dev_slot_ok=None,
                       dev_sys_type=None):
        self.reconciled.append(
            (g, dev_admitted.copy(), dev_reason.copy(), dev_slot_ok.copy())
        )


def _la(rule) -> str:
    return getattr(rule, "limit_app", None) or "default"


def _reference(got, entries, bulk, expect, findex, dindex, auth_rules):
    """Today's per-group attribution, restated one group at a time:
    block-log items, extension events and the recount's blocked weight
    per resource. ``expect`` holds each group's caller-visible
    (admitted, reason) after the fill."""
    items, events, recount = [], [], {}
    for op in entries:
        v = op.verdict
        if v.admitted:
            events.append(("pass", op.resource, op.acquire))
            continue
        items.append((op.resource, E.exc_name_for_code(v.reason),
                      _la(v.blocked_rule), op.origin, op.acquire))
        events.append(("block", op.resource, op.acquire, op.origin,
                       type(E.error_for_verdict(v.reason, op.resource))))
        recount[op.resource] = recount.get(op.resource, 0) + op.acquire
    off = len(entries)
    for g, (adm, rsn) in zip(bulk, expect):
        rows = slice(off, off + g.n)
        off += g.n
        blocked = ~adm
        w = int(g.acquire[blocked].sum())
        if w:
            recount[g.resource] = recount.get(g.resource, 0) + w
        for r in sorted(set(rsn[blocked].tolist())):
            sel = blocked & (rsn == r)
            acq = g.acquire[sel]
            exc = E.exc_name_for_code(r)
            if r == E.BLOCK_FLOW and g.slots:
                bad = ~np.asarray(got.slot_ok)[rows][sel]
                rule_of = [findex.rule_of_gid(gid) for gid, _ in g.slots]
            elif r == E.BLOCK_DEGRADE and g.d_gids:
                bad = ~np.asarray(got.dslot_ok)[rows][sel]
                rule_of = [dindex.rule_of_gid(gid) for gid in g.d_gids]
            else:
                rule = auth_rules.get(g.resource) if r == E.BLOCK_AUTHORITY else None
                items.append((g.resource, exc, _la(rule), g.origin,
                              int(acq.sum())))
                bad = None
            if bad is not None:
                first = [
                    int(np.flatnonzero(row)[0]) if row.any() else None
                    for row in bad
                ]
                for j in range(bad.shape[1]):
                    mine = np.array([f == j for f in first], dtype=bool)
                    if mine.any():
                        rule = rule_of[j] if j < len(rule_of) else None
                        items.append((g.resource, exc, _la(rule), g.origin,
                                      int(acq[mine].sum())))
                none = np.array([f is None for f in first], dtype=bool)
                if int(acq[none].sum()):
                    items.append((g.resource, exc, "default", g.origin,
                                  int(acq[none].sum())))
            events.append(("block", g.resource, int(acq.sum()), g.origin,
                           type(E.error_for_verdict(r, g.resource))))
        if adm.any():
            events.append(("pass", g.resource, int(g.acquire[adm].sum())))
    return items, events, recount


# Each case: groups, their largest size, single ops, speculative groups,
# acquire range [lo, hi), admit share, custom-veto share.
_CASES = {
    "many_uneven_groups": dict(groups=300, max_n=60, singles=24, spec=0,
                               acq=(1, 2), p_adm=0.5, p_veto=0.1),
    "acquire_above_one": dict(groups=120, max_n=40, singles=8, spec=0,
                              acq=(1, 6), p_adm=0.4, p_veto=0.1),
    "zero_acquire": dict(groups=80, max_n=30, singles=4, spec=0,
                         acq=(0, 3), p_adm=0.3, p_veto=0.0),
    "speculative_groups": dict(groups=60, max_n=30, singles=6, spec=6,
                               acq=(1, 4), p_adm=0.5, p_veto=0.1),
    "few_large_groups": dict(groups=4, max_n=2000, singles=0, spec=0,
                             acq=(1, 3), p_adm=0.2, p_veto=0.0),
    "all_admitted": dict(groups=30, max_n=20, singles=4, spec=0,
                         acq=(1, 3), p_adm=1.0, p_veto=0.0),
}


def _chunk(rng, groups, max_n, singles, spec, acq, p_adm, p_veto):
    """A seeded synthetic fetched chunk: single ops, then bulk groups of
    uneven sizes with 0-3 flow slots and 0-3 breakers each, verdicts,
    reasons and slot matrices drawn at random (a column past a group's
    own slots may fail too, and reads "default")."""
    findex = _Index({gid: ("default", "appA", "appB", "")[gid % 4]
                     for gid in range(12)})  # gids 12.. have no rule
    dindex = _Index({gid: ("default", "appC")[gid % 2] for gid in range(6)})
    auth_rules = {f"r{i}": _Rule(("appA", "default")[i % 2]) for i in range(0, 40, 3)}
    origins = ["", "appA", "appB"]

    def slots_of():
        return [(int(g), 0) for g in rng.choice(16, int(rng.integers(0, K + 1)),
                                                replace=False)]

    def dgids_of():
        return [int(g) for g in rng.choice(8, int(rng.integers(0, KD + 1)),
                                           replace=False)]

    entries = [
        _EntryOp(resource=f"s{i % 5}", ts=0,
                 acquire=int(rng.integers(max(acq[0], 1), acq[1] + 1)),
                 rows=(0, 0, -1, -1), slots=slots_of(), d_gids=dgids_of(),
                 origin=origins[i % 3])
        for i in range(singles)
    ]
    bulk = []
    for i in range(groups):
        n = int(rng.integers(1, max_n + 1))
        g = BulkOp(
            resource=f"r{int(rng.integers(0, 40))}", n=n,
            ts=np.zeros(n, dtype=np.int32),
            acquire=rng.integers(acq[0], acq[1], n).astype(np.int32),
            rows=(0, 0, -1, -1), slots=slots_of(), d_gids=dgids_of(),
            auth_ok=True, context_name="ctx", origin=origins[i % 3],
        )
        if rng.random() < p_veto:
            g.custom_veto_mask = rng.random(n) < 0.3
        bulk.append(g)
    for i in rng.choice(groups, spec, replace=False):
        g = bulk[i]
        g.spec_admitted = rng.random(g.n) < 0.5
        g.admitted = g.spec_admitted.copy()
        g.reason = np.where(g.admitted, 0, rng.choice(_BLOCKS, g.n)).astype(np.int32)
        g.wait_ms = np.zeros(g.n, dtype=np.int32)
    n_rows = singles + sum(g.n for g in bulk) + 7  # padded like a batch
    admitted = rng.random(n_rows) < p_adm
    got = _ChunkResults(
        admitted=admitted,
        reason=np.where(admitted, 0, rng.choice(_BLOCKS, n_rows)).astype(np.int8),
        slot_ok=rng.random((n_rows, K)) < 0.6,
        wait_ms=rng.integers(0, 500, n_rows).astype(np.int32),
        sys_type=rng.integers(0, 4, n_rows).astype(np.int32),
        dslot_ok=rng.random((n_rows, KD)) < 0.6,
    )
    return got, entries, bulk, findex, dindex, auth_rules


def _expected_arrays(got, entries, bulk):
    """Each group's caller-visible (admitted, reason, wait_ms): the
    device rows with the custom veto applied, or a speculative group's
    own arrays, untouched."""
    out, off = [], len(entries)
    for g in bulk:
        rows = slice(off, off + g.n)
        off += g.n
        if g.spec_admitted is not None:
            out.append((g.admitted.copy(), g.reason.copy(), g.wait_ms.copy()))
            continue
        adm = np.array(got.admitted[rows])
        rsn = np.array(got.reason[rows], dtype=np.int32)
        if g.custom_veto_mask is not None:
            rsn[~adm & g.custom_veto_mask] = E.BLOCK_CUSTOM
        out.append((adm, rsn, np.array(got.wait_ms[rows])))
    return out


@pytest.fixture()
def recorder():
    MetricExtensionProvider.clear()
    rec = _Recorder()
    MetricExtensionProvider.register(rec)
    yield rec
    MetricExtensionProvider.clear()


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_fill_matches_per_group_reference(case, seed, recorder, monkeypatch):
    """Block-log items, extension calls, the blocked top-K recount and
    every group's verdict arrays equal the per-group reference."""
    eng = Engine(initial_rows=1024, clock=ManualClock(0))
    spec = _SpecStub()
    monkeypatch.setattr(eng, "speculative", spec)
    folded = []
    monkeypatch.setattr(eng.telemetry, "fold_blocked_topk", folded.append)
    monkeypatch.setattr(eng, "_blk_topk_k", 1000)
    rng = np.random.default_rng(seed)
    got, entries, bulk, findex, dindex, auth_rules = _chunk(rng, **_CASES[case])
    expect = _expected_arrays(got, entries, bulk)

    items, n_spec = eng._fill_results(
        got, entries, [], bulk, [], findex, dindex, auth_rules, K, KD
    )

    for g, (adm, rsn, wait) in zip(bulk, expect):
        np.testing.assert_array_equal(g.admitted, adm)
        np.testing.assert_array_equal(g.reason, rsn)
        np.testing.assert_array_equal(g.wait_ms, wait)
        assert g.reason.dtype == np.int32
    want_items, want_events, want_recount = _reference(
        got, entries, bulk, [(a, r) for a, r, _ in expect], findex, dindex,
        auth_rules,
    )
    assert items == want_items
    assert recorder.events == want_events
    (pairs,) = folded
    assert dict(pairs) == want_recount
    assert [w for _, w in pairs] == sorted(want_recount.values(), reverse=True)
    assert n_spec == _CASES[case]["spec"]
    # A speculative group reconciles against its own device rows.
    spec_groups = [g for g in bulk if g.spec_admitted is not None]
    assert [r[0] for r in spec.reconciled] == spec_groups
    off, starts = len(entries), {}
    for g in bulk:
        starts[id(g)] = off
        off += g.n
    for g, dev_adm, dev_rsn, dev_slot in spec.reconciled:
        rows = slice(starts[id(g)], starts[id(g)] + g.n)
        np.testing.assert_array_equal(dev_adm, got.admitted[rows])
        np.testing.assert_array_equal(dev_rsn, got.reason[rows])
        np.testing.assert_array_equal(dev_slot, got.slot_ok[rows])


def test_group_arrays_are_disjoint_views(monkeypatch):
    """A caller writing into one group's arrays changes no other group's."""
    eng = Engine(initial_rows=1024, clock=ManualClock(0))
    monkeypatch.setattr(eng, "_blk_topk_k", 0)
    got, entries, bulk, findex, dindex, auth_rules = _chunk(
        np.random.default_rng(3), **_CASES["many_uneven_groups"]
    )
    eng._fill_results(got, entries, [], bulk, [], findex, dindex, auth_rules, K, KD)
    before = [(g.admitted.copy(), g.reason.copy(), g.wait_ms.copy()) for g in bulk]
    for g in bulk[::2]:
        g.admitted[:] = ~g.admitted
        g.reason[:] = 99
        g.wait_ms[:] = -1
    for g, (adm, rsn, wait) in list(zip(bulk, before))[1::2]:
        np.testing.assert_array_equal(g.admitted, adm)
        np.testing.assert_array_equal(g.reason, rsn)
        np.testing.assert_array_equal(g.wait_ms, wait)


def _world(eng):
    """Three flow rules on one resource (different limit apps), two
    breakers fed by failing exits, a black-listing authority rule and
    plain resources."""
    eng.set_flow_rules([
        FlowRule(resource="f", count=4),
        FlowRule(resource="f", count=1, limit_app="appA"),
        FlowRule(resource="f", count=6),
        FlowRule(resource="g", count=12),
        FlowRule(resource="h", count=3, limit_app="appB"),
    ])
    eng.set_degrade_rules([
        DegradeRule(resource="d", grade=2, count=2, time_window=10,
                    min_request_amount=1),
        DegradeRule(resource="d", grade=1, count=0.5, time_window=10,
                    min_request_amount=1),
    ])
    eng.set_authority_rules(
        {"a": AuthorityRule(resource="a", limit_app="appB", strategy=1)}
    )


def _drive(depth: int, logged):
    """Four flushes of mixed bulk groups and single ops through one
    engine, each fill checked against the per-group reference; at
    ``depth`` > 0 each flush is queued by ``flush_async`` and settled
    later. Returns the groups, in submit order."""
    clock = ManualClock(1000)
    eng = Engine(initial_rows=1024, clock=clock)
    _world(eng)
    eng.block_log.log_batch = logged.extend
    real = eng._fill_results

    def checked(got, entries, exits, bulk, bulk_exits, findex, dindex,
                auth_rules, *rest, **kw):
        items, n_spec = real(got, entries, exits, bulk, bulk_exits, findex,
                             dindex, auth_rules, *rest, **kw)
        expect = [(g.admitted, g.reason) for g in bulk]
        assert items == _reference(got, entries, bulk, expect, findex,
                                   dindex, auth_rules)[0]
        return items, n_spec

    eng._fill_results = checked
    if depth:
        eng.set_depth(depth)
    rng = np.random.default_rng(11)
    groups = []
    for step in range(4):
        clock.set_ms(1000 + 300 * step)
        t = clock.now_ms()
        for res in ("f", "g", "d", "a", "h", "free"):
            for origin in ("", "appA", "appB"):
                n = int(rng.integers(1, 9))
                g = eng.submit_bulk(res, n, ts=t, origin=origin,
                                    acquire=rng.integers(1, 3, n))
                if g is not None:
                    groups.append(g)
        for res in ("f", "h", "a"):
            eng.submit_entry(res, ts=t, origin="appB")
        eng.flush_async() if depth else eng.flush()
        for g in groups[-18:]:
            if g.resource == "d" and g.admitted_count:
                eng.submit_exit_bulk(g.rows, g.admitted_count, rt=5, err=1,
                                     ts=t, resource="d")
    eng.drain()
    return groups


def test_depth_k_settle_fills_the_same_arrays_as_depth_0():
    """Real flushes log what the per-group reference logs, and the
    queued settle (depth K, flush_async) fills the same verdict arrays
    and block log as the depth-0 flush."""
    log0, log_k = [], []
    g0, gk = _drive(0, log0), _drive(2, log_k)
    assert len(g0) == len(gk) > 0
    for a, b in zip(g0, gk):
        for col in ("admitted", "reason", "wait_ms"):
            np.testing.assert_array_equal(getattr(a, col), getattr(b, col))
    assert log0 == log_k
    names = {item[1] for item in log0}
    assert {"FlowException", "DegradeException", "AuthorityException"} <= names
    assert {item[2] for item in log0 if item[0] == "f"} == {"default", "appA"}


def test_arrays_are_the_device_rows_and_outlive_the_next_flush(monkeypatch):
    """Each group's arrays are its device rows, and stay so after later
    flushes reuse the engine's buffers."""
    clock = ManualClock(1000)
    eng = Engine(initial_rows=1024, clock=clock)
    _world(eng)
    fetched = []
    real = eng._fill_results

    def spy(got, entries, *rest, **kw):
        fetched.append((got, len(entries)))
        return real(got, entries, *rest, **kw)

    monkeypatch.setattr(eng, "_fill_results", spy)
    groups = [eng.submit_bulk(res, 10, ts=1000) for res in ("f", "g", "d", "free")]
    eng.flush()
    (got, lo), = fetched
    kept = []
    for g in groups:
        rows = slice(lo, lo + g.n)
        lo += g.n
        np.testing.assert_array_equal(g.admitted, got.admitted[rows])
        np.testing.assert_array_equal(g.reason, got.reason[rows])
        np.testing.assert_array_equal(g.wait_ms, got.wait_ms[rows])
        kept.append((g.admitted.copy(), g.reason.copy(), g.wait_ms.copy()))
    for step in range(3):
        clock.set_ms(1100 + 100 * step)
        for res in ("f", "g", "d", "free"):
            eng.submit_bulk(res, 10, ts=clock.now_ms())
        eng.flush()
    for g, (adm, rsn, wait) in zip(groups, kept):
        np.testing.assert_array_equal(g.admitted, adm)
        np.testing.assert_array_equal(g.reason, rsn)
        np.testing.assert_array_equal(g.wait_ms, wait)


def _trace_reference(tracer, groups, admitted, reasons, flush_seq, end_pc):
    """Per-group bulk trace recording, one row at a time: the first
    ``bulk_cap`` blocked rows (always-blocked mode or a sampled tag),
    then the first ``bulk_cap`` admitted rows of a sampled tag, each
    through ``record_admission``."""
    cap = tracer.bulk_cap
    if cap <= 0:
        return
    for start, stop, tag, resource, origin, ctx, degraded, prov in groups:
        adm = np.asarray(admitted[start:stop])
        rows = []
        if tracer.sample_blocked or tag.sampled:
            rows.extend(np.flatnonzero(~adm)[:cap].tolist())
        if tag.sampled:
            rows.extend(np.flatnonzero(adm)[:cap].tolist())
        for i in rows:
            tracer.record_admission(
                tag, resource, origin, ctx, bool(adm[i]),
                int(reasons[start + i]), flush_seq, end_pc,
                degraded=degraded, provenance=prov,
            )


# Each case: groups, ring size, bulk cap, always-blocked mode, head
# sample share, share of groups under an inbound trace.
_TRACE_CASES = {
    "ring_holds_all": dict(groups=40, ring=4096, cap=4, blocked=True,
                           p_sampled=0.2, p_parent=0.3),
    "ring_overflows": dict(groups=400, ring=64, cap=4, blocked=True,
                           p_sampled=0.05, p_parent=0.2),
    "sampled_only": dict(groups=200, ring=128, cap=2, blocked=False,
                         p_sampled=0.3, p_parent=0.5),
    "cap_zero": dict(groups=20, ring=64, cap=0, blocked=True,
                     p_sampled=0.5, p_parent=0.0),
}


@pytest.mark.parametrize("case", sorted(_TRACE_CASES))
def test_bulk_trace_matches_per_row_reference(case):
    """One columnar trace call records what one ``record_admission``
    per selected row gives: the ring's records, counters, latency
    histogram (bit for bit) and exemplars."""
    from sentinel_tpu.metrics.admission_trace import (
        AdmissionTracer, TraceContext, TraceTag, new_span_id, new_trace_id,
    )

    c = _TRACE_CASES[case]
    rng = np.random.default_rng(5)
    end_pc = 1000.0
    groups, off = [], int(rng.integers(0, 9))  # single ops lead the chunk
    for i in range(c["groups"]):
        n = int(rng.integers(1, 30))
        parent = (TraceContext(new_trace_id(), new_span_id())
                  if rng.random() < c["p_parent"] else None)
        tag = TraceTag(parent, bool(rng.random() < c["p_sampled"]),
                       end_pc - float(rng.choice([1e-6, 3e-4, 0.002, 0.9])))
        groups.append((off, off + n, tag, f"r{i % 7}", ("", "appA")[i % 2],
                       "ctx", bool(i % 5 == 0), ("", "speculative")[i % 3 == 0]))
        off += n
    admitted = rng.random(off + 3) < 0.4
    reasons = np.where(admitted, 0, rng.choice(_BLOCKS, off + 3)).astype(np.int32)

    def tracer():
        return AdmissionTracer(enabled=True, ring=c["ring"], sample_rate=0.0,
                               sample_blocked=c["blocked"], bulk_cap=c["cap"])

    want, got = tracer(), tracer()
    _trace_reference(want, groups, admitted, reasons, 17, end_pc)
    got.record_bulk_groups(groups, admitted, reasons, 17, end_pc)

    def fields(rec):
        d = rec.as_dict()
        d.pop("span_id")
        if not d["parent_span_id"]:
            d.pop("trace_id")  # trace-rooted: a fresh random id
        return d

    assert [fields(r) for r in got.records()] == [fields(r) for r in want.records()]
    assert got.counters_snapshot() == want.counters_snapshot()
    (cg, sg), (cw, sw) = (got.hist_latency.snapshot_counts(),
                          want.hist_latency.snapshot_counts())
    np.testing.assert_array_equal(cg, cw)
    assert sg == sw
    ex_got, ex_want = got.exemplars(), want.exemplars()
    assert list(ex_got) == list(ex_want)
    assert [ms for _, ms in ex_got.values()] == [ms for _, ms in ex_want.values()]
    if got.counters_snapshot()["recorded"] <= c["ring"]:
        # Every record is in the ring: each exemplar names one of them.
        ring_ids = {r.trace_id for r in got.records()}
        assert all(tid in ring_ids for tid, _ in ex_got.values())
    if case == "ring_overflows":
        assert got.counters_snapshot()["recorded"] > c["ring"]
    if case == "cap_zero":
        assert got.counters_snapshot()["recorded"] == 0
