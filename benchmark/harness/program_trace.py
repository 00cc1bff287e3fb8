"""The program's own phase spans and kernel stage scopes, read from the
window's profiler trace: the engine's ``sentinel.<phase>`` host spans
(``TelemetryBus.phase``) with their thread, args and nesting, and the
device operations of the flush programs with their ``sentinel/<stage>``
scope (``jax.named_scope`` in ``runtime/flush.py``).

On a TPU trace the scope path of an operation is not on its event.  It
sits in the ``op_name`` of the instruction in the program's HLO, which
the trace keeps whole in its ``/host:metadata`` plane, and for some
operations in the ``tf_op`` stat of the device plane's event metadata
(``jit(flush_step_full_jit)/sentinel/param/select_n:``).
``jax.profiler.ProfileData`` shows neither, so :func:`op_stages` reads
both from the file's protobuf wire format itself.  A fusion the compiler
built with no ``op_name`` of its own takes the stage most of its fused
instructions name; an operation with none at all (an async copy or slice
start and done) takes the stage of the operation that ran before it in
the same program.

A program without those spans or scopes gives empty lists here, and the
readers built on this module then report nothing.
"""

from __future__ import annotations

import bisect
import functools
import re
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from harness import trace as tr

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_trace"
SPAN_PREFIX = "sentinel."
WINDOW = "bench.window"
SCOPE = re.compile(r"(?:^|/)sentinel/([A-Za-z_]+)")
SCOPE_STAT = "tf_op"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
PROGRAM_STAT = "program_id"
PROGRAM_ID = re.compile(r"\((\d+)\)$")


@dataclass
class Span:
    name: str
    t0: float  # s, the trace's clock
    t1: float
    thread: str
    args: Dict[str, object]
    children: List[int] = field(default_factory=list)  # indices into ProgramTrace.spans


@dataclass
class ProgramTrace:
    window: Optional[Tuple[float, float]]
    spans: List[Span]  # sentinel.* host spans, by start
    ops: List[Tuple[str, float, float]]  # (stage or "", t0, t1): device ops of flush programs
    busy: List[Tuple[float, float]]  # merged device-busy intervals, one device
    harness: List[Tuple[str, float, float]]  # the harness's bench.* spans


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of one protobuf message in ``buf[lo:hi]``:
    an int for a varint, a (start, end) pair for a length-delimited
    field; fixed-width fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _packed(buf, ab) -> List[int]:
    out, i = [], ab[0]
    while i < ab[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _hlo_stages(buf, lo: int, hi: int) -> Dict[str, str]:
    """Instruction name -> stage of one program's HloProto: the scope in
    the instruction's own ``op_name``, else the stage most of the scoped
    instructions of the computations it calls (a fusion's body) name.
    (HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto instructions = 2, id = 5; HloInstructionProto
    name = 1, metadata = 7 with OpMetadata.op_name = 2,
    called_computation_ids = 38, packed.)"""
    text = lambda ab: bytes(buf[ab[0]:ab[1]]).decode(errors="replace")  # noqa: E731
    module = next((v for f, v in _fields(buf, lo, hi) if f == 1), None)
    if module is None:
        return {}
    own: Dict[str, str] = {}
    calls: Dict[str, List[int]] = {}
    by_comp: Dict[int, List[str]] = {}  # computation id -> its instructions' stages
    for f, comp in _fields(buf, *module):
        if f != 3:
            continue
        cid, stages = 0, []
        for g, v in _fields(buf, *comp):
            if g == 5:
                cid = v
            if g != 2:
                continue
            name, stage, called = "", "", []
            for k, x in _fields(buf, *v):
                if k == 1:
                    name = text(x)
                elif k == 7:
                    op_name = next((text(y) for j, y in _fields(buf, *x) if j == 2), "")
                    m = SCOPE.search(op_name)
                    stage = m.group(1) if m else ""
                elif k == 38:
                    called += _packed(buf, x) if isinstance(x, tuple) else [x]
            stages.append(stage)
            if name:
                own[name] = stage
                if called:
                    calls[name] = called
        by_comp[cid] = stages
    out = {}
    for name, stage in own.items():
        if not stage and name in calls:
            named = Counter(s for c in calls[name] for s in by_comp.get(c, ()) if s)
            stage = named.most_common(1)[0][0] if named else ""
        out[name] = stage
    return out


def op_stages(path: str) -> Dict[str, str]:
    """Device operation name -> its ``sentinel/<stage>`` scope. The
    program's HLO, which the trace keeps in the ``/host:metadata``
    plane (an event metadata per program, ``<jit name>(<program id>)``,
    with the HloProto in its ``Hlo Proto`` stat), names it first
    (:func:`_hlo_stages`); the ``tf_op`` stat of the device plane's event
    metadata next.  (XSpace.planes = 1; XPlane name = 2, event_metadata
    = 4, stat_metadata = 5; XEventMetadata name = 2, stats = 5; an XStat
    names its stat by metadata_id = 1 and holds uint64_value = 3,
    str_value = 5, bytes_value = 6, or ref_value = 7.)"""
    buf = memoryview(Path(path).read_bytes())
    text = lambda ab: bytes(buf[ab[0]:ab[1]]).decode(errors="replace")  # noqa: E731
    hlo: Dict[int, Dict[str, str]] = {}  # program id -> instruction -> stage
    ops: List[Tuple[str, int, str]] = []  # (device op, program id, tf_op stage)
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = text(v)
            elif g == 4:
                metas.extend(md for k, md in _fields(buf, *v) if k == 2)
            elif g == 5:
                md = dict(_fields(buf, *next(x for k, x in _fields(buf, *v) if k == 2)))
                stat_names[md.get(1, 0)] = text(md[2]) if 2 in md else ""
        device = bool(tr.DEVICE_PLANE.match(name))
        if not device and name != METADATA_PLANE:
            continue
        for md in metas:
            op, program, scope = "", 0, ""
            for g, v in _fields(buf, *md):
                if g == 2:
                    op = text(v)
                elif g == 5:
                    st = dict(_fields(buf, *v))
                    kind = stat_names.get(st.get(1))
                    if kind == HLO_STAT and 6 in st:
                        m = PROGRAM_ID.search(op)
                        if m:
                            hlo[int(m.group(1))] = _hlo_stages(buf, *st[6])
                    elif kind == PROGRAM_STAT:
                        program = st.get(3, 0)
                    elif kind == SCOPE_STAT:
                        val = text(st[5]) if 5 in st else stat_names.get(st.get(7), "")
                        m = SCOPE.search(val)
                        scope = m.group(1) if m else ""
            if device:
                ops.append((op, program, scope))
    out: Dict[str, str] = {}
    for op, program, scope in ops:
        stage = hlo.get(program, {}).get(op.split(" ", 1)[0].lstrip("%"), "") or scope
        if stage or op not in out:
            out[op] = stage
    return out


def _nest(spans: List[Span]) -> None:
    """Each span's children, by containment on its thread."""
    by_thread: Dict[str, List[int]] = {}
    for i, s in enumerate(spans):
        by_thread.setdefault(s.thread, []).append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i].t0, -spans[i].t1))
        stack: List[int] = []
        for i in idx:
            while stack and spans[stack[-1]].t1 < spans[i].t1:
                stack.pop()
            if stack:
                spans[stack[-1]].children.append(i)
            stack.append(i)


@functools.lru_cache(maxsize=2)
def load(path: str) -> ProgramTrace:
    """Read ``path`` once per process."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    stages = op_stages(path)
    spans: List[Span] = []
    harness: List[Tuple[str, float, float]] = []
    window = None
    ops: List[Tuple[str, float, float]] = []
    modules: List[Tuple[float, float]] = []
    all_ops: List[Tuple[float, float]] = []
    seen_device = False
    for plane in data.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            if seen_device:  # one device: the cells of this reader run on one chip
                continue
            seen_device = True
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        if "flush_step" in ev.name:
                            t0 = ev.start_ns / 1e9
                            modules.append((t0, t0 + ev.duration_ns / 1e9))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        t0 = ev.start_ns / 1e9
                        t1 = t0 + ev.duration_ns / 1e9
                        all_ops.append((t0, t1))
                        ops.append((stages.get(ev.name, ""), t0, t1))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    t0 = ev.start_ns / 1e9
                    t1 = t0 + ev.duration_ns / 1e9
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, t0, t1, line.name,
                                          dict(ev.stats)))
                    elif ev.name.startswith(tr.SPAN_PREFIX):
                        harness.append((ev.name, t0, t1))
                        if ev.name == WINDOW:
                            window = (t0, t1)
    spans.sort(key=lambda s: (s.t0, -s.t1))
    _nest(spans)
    # Device operations of the flush programs only (those inside a
    # flush_step module's interval); one without a stage takes that of
    # the operation before it in the same module run.
    modules = tr.merge(modules)
    starts = [a for a, _ in modules]
    kept: List[Tuple[str, float, float]] = []
    run, last = -1, ""
    for stage, t0, t1 in sorted(ops, key=lambda o: o[1]):
        i = bisect.bisect_right(starts, t0) - 1
        if i < 0 or t1 > modules[i][1] + 1e-9:
            continue
        if i != run:
            run, last = i, ""
        last = stage or last
        kept.append((last, t0, t1))
    return ProgramTrace(window, spans, kept, tr.merge(all_ops or modules),
                        sorted(harness, key=lambda h: h[1]))


def for_run(run) -> Optional[ProgramTrace]:
    """The traced run's program trace, or None for an untraced run."""
    if getattr(run, "trace", None) is None:
        return None
    path = tr.find_xplane(str(TRACE_DIR))
    return load(path) if path else None


def _in_window(pt: ProgramTrace, name: str) -> List[Span]:
    if pt.window is None:
        return []
    lo, hi = pt.window
    return [s for s in pt.spans if s.name == name and s.t0 >= lo and s.t1 <= hi]


def span_ms_per_flush(pt: ProgramTrace, name: str, flushes: int) -> Optional[float]:
    """Milliseconds of the ``name`` spans inside the window, per flush;
    None where the program has no such span."""
    got = _in_window(pt, name)
    if not got or flushes <= 0:
        return None
    return sum(s.t1 - s.t0 for s in got) / flushes * 1e3


def self_ms(pt: ProgramTrace, name: str) -> List[float]:
    """Each ``name`` span's self time, ms: its duration less the part
    its child spans cover."""
    out = []
    for s in _in_window(pt, name):
        kids = tr.merge([(pt.spans[i].t0, pt.spans[i].t1) for i in s.children])
        out.append((s.t1 - s.t0 - sum(b - a for a, b in kids)) * 1e3)
    return out


def device_s_by_scope(pt: ProgramTrace) -> Dict[str, float]:
    """Device seconds of the flush programs inside the window by stage
    scope ("" where an operation has none); nested operations (a loop's
    body inside the loop) count once."""
    if pt.window is None:
        return {}
    lo, hi = pt.window
    by: Dict[str, List[Tuple[float, float]]] = {}
    for stage, a, b in pt.ops:
        if b > lo and a < hi:
            by.setdefault(stage, []).append((max(a, lo), min(b, hi)))
    return {k: sum(b - a for a, b in tr.merge(v)) for k, v in by.items()}


def scoped_share(pt: ProgramTrace) -> Optional[float]:
    """Share of the flush programs' busy device time, inside the window,
    that lies under a stage scope."""
    if pt.window is None:
        return None
    lo, hi = pt.window
    clip = [(max(a, lo), min(b, hi), s) for s, a, b in pt.ops if b > lo and a < hi]
    all_t = sum(b - a for a, b in tr.merge([(a, b) for a, b, _ in clip]))
    scoped = sum(b - a for a, b in tr.merge([(a, b) for a, b, s in clip if s]))
    return scoped / all_t if all_t > 0 else None


def idle_s_by_open_span(pt: ProgramTrace) -> Dict[str, float]:
    """The device's idle seconds inside the window, each stretch put down
    to the innermost ``sentinel.`` span open over it (the latest opened
    and still open), and to the innermost harness ``bench.`` span where
    no program span is open."""
    if pt.window is None:
        return {}
    lo, hi = pt.window
    busy = [(max(a, lo), min(b, hi)) for a, b in pt.busy if b > lo and a < hi]
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    names = [s.name for s in pt.spans] + [n for n, _, _ in pt.harness]
    n_prog = len(pt.spans)
    marks = sorted(
        [(s.t0, 1, i) for i, s in enumerate(pt.spans)]
        + [(s.t1, 0, i) for i, s in enumerate(pt.spans)]
        + [(a, 1, n_prog + j) for j, (_, a, _) in enumerate(pt.harness)]
        + [(b, 0, n_prog + j) for j, (_, _, b) in enumerate(pt.harness)]
    )
    out: Dict[str, float] = {}
    open_: List[int] = []  # open spans, in the order they opened
    g = 0
    prev = lo
    for t, opens, i in marks + [(hi, 0, -1)]:
        x, y = prev, min(t, hi)
        while g < len(gaps) and gaps[g][1] <= x:
            g += 1
        if y > x and g < len(gaps):
            prog = [k for k in open_ if k < n_prog]
            name = names[(prog or open_)[-1]] if open_ else "none"
            for a, b in gaps[g:]:
                if a >= y:
                    break
                got = min(b, y) - max(a, x)
                if got > 0:
                    out[name] = out.get(name, 0.0) + got
        prev = max(prev, min(t, hi))
        if i < 0:
            break
        if opens:
            open_.append(i)
        elif i in open_:
            open_.remove(i)
    return out
