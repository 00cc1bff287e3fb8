"""tools/tracedump.py: Chrome trace-event export of the flight recorder.

Tier-1-safe validation (ISSUE 3 CI satellite): the emitted JSON is
well-formed trace-event format, same-tid slices never overlap, and a
depth-2 run shows flush N's in-flight (dispatch→settle) window
overlapping flush N+1's encode — the pipelining proof Perfetto
renders."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import tracedump  # noqa: E402


@pytest.fixture(scope="module")
def depth2_trace(tmp_path_factory):
    """One depth-2 demo run shared by the structural checks."""
    eng = tracedump.run_demo(depth=2, flushes=16, rows=64)
    path = tmp_path_factory.mktemp("trace") / "trace.json"
    trace = tracedump.dump(eng, str(path))
    # Round-trip through disk: the file itself must parse.
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f), trace


class TestTraceFormat:
    def test_well_formed_trace_events(self, depth2_trace):
        loaded, emitted = depth2_trace
        assert loaded == emitted
        events = loaded["traceEvents"]
        assert events, "demo run must emit events"
        for e in events:
            assert e["ph"] in ("X", "M", "s", "f")
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
            if e["ph"] == "X" and e.get("cat") == "admission":
                # Request track: one slice per sampled admission.
                assert e["ts"] >= 0.0 and e["dur"] > 0.0
                assert "trace_id" in e["args"] and "flush_seq" in e["args"]
            elif e["ph"] == "X":
                assert e["name"] in (
                    "encode", "staging", "shaping", "param", "dispatch",
                    "fetch", "fill", "inflight",
                )
                assert e["ts"] >= 0.0 and e["dur"] >= 0.0
                assert "flush_id" in e["args"]

    def test_same_tid_slices_do_not_overlap(self, depth2_trace):
        """Slices on one tid never overlap partially: a phase part
        (staging, shaping, param inside encode) nests wholly inside its
        parent, every other pair is disjoint."""
        events = [e for e in depth2_trace[0]["traceEvents"] if e["ph"] == "X"]
        by_tid = {}
        for e in events:
            by_tid.setdefault(e["tid"], []).append((e["ts"], e["ts"] + e["dur"]))
        for tid, spans in by_tid.items():
            # Longest first at a shared start, so a parent precedes the
            # parts it contains.
            spans.sort(key=lambda ab: (ab[0], -ab[1]))
            open_ends = []
            for s0, e0 in spans:
                # 1 µs grace for float rounding at shared boundaries.
                while open_ends and open_ends[-1] <= s0 + 1e-3:
                    open_ends.pop()
                if open_ends:
                    assert e0 <= open_ends[-1] + 1e-3, (tid, (s0, e0), open_ends[-1])
                open_ends.append(e0)

    def test_request_flow_events_link_to_deciding_flush(self, depth2_trace):
        """Acceptance: the dump contains request→flush flow arrows in
        the shape Perfetto accepts — matched s/f pairs (same cat, name,
        id), the start on a request track inside its admission slice,
        the finish on the host track inside the DECIDING flush's
        dispatch slice, and s.ts <= f.ts."""
        events = depth2_trace[0]["traceEvents"]
        starts = {e["id"]: e for e in events if e["ph"] == "s"}
        finishes = {e["id"]: e for e in events if e["ph"] == "f"}
        assert starts, "demo must emit flow arrows"
        assert set(starts) == set(finishes)
        slices = [e for e in events if e["ph"] == "X"]

        def enclosing(tid, ts):
            return [
                e for e in slices
                if e["tid"] == tid and e["ts"] - 1e-3 <= ts <= e["ts"] + e["dur"] + 1e-3
            ]

        for fid, s in starts.items():
            f = finishes[fid]
            assert s["cat"] == f["cat"] == "admission"
            assert s["name"] == f["name"] == "decide"
            assert f["bp"] == "e"
            assert s["ts"] <= f["ts"]
            req = [e for e in enclosing(s["tid"], s["ts"])
                   if e.get("cat") == "admission"]
            assert req, ("flow start must sit inside a request slice", s)
            disp = [e for e in enclosing(f["tid"], f["ts"])
                    if e.get("name") == "dispatch"]
            assert disp, ("flow finish must sit inside a dispatch slice", f)
            # And it is the DECIDING flush's dispatch slice.
            assert any(
                d["args"]["flush_id"] == req[0]["args"]["flush_seq"]
                for d in disp
            )

    def test_blocked_and_admitted_records_present(self, depth2_trace):
        """The demo's tight flow rule blocks part of every window: the
        request track must carry both verdicts, blocked ones named by
        the shared reason mapping."""
        reqs = [
            e for e in depth2_trace[0]["traceEvents"]
            if e["ph"] == "X" and e.get("cat") == "admission"
        ]
        blocked = [e for e in reqs if not e["args"]["admitted"]]
        admitted = [e for e in reqs if e["args"]["admitted"]]
        assert blocked and admitted
        assert all(e["args"]["reason_name"] == "FlowException" for e in blocked)

    def test_depth2_inflight_overlaps_next_encode(self, depth2_trace):
        """The pipelining proof: for most flushes N, the in-flight
        window of N (device exec + fetch) overlaps the encode slice of
        flush N+1 on the host track."""
        events = depth2_trace[0]["traceEvents"]
        encode = {
            e["args"]["flush_id"]: e for e in events if e.get("name") == "encode"
        }
        inflight = [e for e in events if e.get("name") == "inflight"]
        deferred = [e for e in inflight if e["args"]["deferred"]]
        assert deferred, "depth-2 run must have deferred in-flight spans"
        overlaps = 0
        candidates = 0
        for f in deferred:
            nxt = encode.get(f["args"]["flush_id"] + 1)
            if nxt is None:
                continue
            candidates += 1
            if (
                nxt["ts"] < f["ts"] + f["dur"]
                and nxt["ts"] + nxt["dur"] > f["ts"]
            ):
                overlaps += 1
        assert candidates > 0
        # At steady state every pair overlaps; allow pipeline ramp-up
        # and the final drain to miss.
        assert overlaps >= candidates // 2, (overlaps, candidates)

    def test_depth2_uses_parallel_inflight_tracks(self, depth2_trace):
        """A depth-2 pipeline needs >= 2 in-flight tracks: two
        dispatched-but-unfetched flushes coexist, so their windows
        cannot share a tid."""
        events = depth2_trace[0]["traceEvents"]
        tids = {
            e["tid"]
            for e in events
            if e.get("name") == "inflight" and e["args"]["deferred"]
        }
        assert len(tids) >= 2


class TestDumpApi:
    def test_dump_empty_recorder(self, tmp_path):
        from sentinel_tpu.metrics.telemetry import spans_to_trace

        trace = spans_to_trace([])
        assert trace["traceEvents"] == []

    def test_sync_engine_trace(self, manual_clock, engine, tmp_path):
        import sentinel_tpu as st

        st.flow_rule_manager.load_rules([st.FlowRule("td", count=1e9)])
        for _ in range(3):
            engine.submit_entry("td")
            engine.flush()
        trace = tracedump.dump(engine, str(tmp_path / "t.json"))
        names = {e.get("name") for e in trace["traceEvents"]}
        assert {"encode", "dispatch"} <= names
