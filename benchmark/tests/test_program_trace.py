"""The program-trace reduction on a small trace recorded on a v5e: two
consecutive flushes of a traced ``northstar-spread-backlog`` run, with the
engine's ``sentinel.*`` phase spans and the flush kernel's stage scopes.
The file is cut from the run's ``.xplane.pb``: the host's ``sentinel.*``
and ``bench.*`` spans (``bench.window`` cut to the two steps; a submit
span without its args), and the device's module and top-level operation
events (a loop's body operations dropped: the loop's own event covers
them), each operation's ``tf_op`` cut after its stage, and the flush
program's HLO in ``/host:metadata`` cut to the instructions those
operations name and the scoped ``op_name``s of their fused bodies. It is
kept gzipped (the ~7,000 submit spans of a flush make most of it) and
unpacked once."""

from __future__ import annotations

import gzip
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import program_trace as pt
from harness import spec
from harness import trace as tr

DATA = Path(__file__).resolve().parent / "data"
PROGRAM_GZ = DATA / "backlog_v5e_program.xplane.pb.gz"
PARENT = DATA / "backlog_v5e.xplane.pb"  # a program without spans or scopes
READERS = ("engine_submit_ms.backlog", "encode_ms.backlog", "fetch_ms.backlog",
           "fill_ms.backlog", "shaping_device_ms.backlog")
STEPS = 2


@pytest.fixture(scope="module")
def program_path(tmp_path_factory) -> Path:
    path = tmp_path_factory.mktemp("program") / "backlog_v5e_program.xplane.pb"
    path.write_bytes(gzip.decompress(PROGRAM_GZ.read_bytes()))
    return path


@pytest.fixture(scope="module")
def program(program_path):
    return pt.load(str(program_path))


def _run_on(path: Path, tmp_path: Path, monkeypatch, steps: int):
    """A traced run whose trace directory holds ``path``."""
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copy(path, where / "host.xplane.pb")
    monkeypatch.setattr(pt, "TRACE_DIR", tmp_path)
    return SimpleNamespace(trace=tr.load(str(path)),
                           window=SimpleNamespace(steps=[None] * steps))


def _reader(name: str):
    return spec._metric({"name": name, "unit": "ms"}, spec.BENCH_DIR).read


def _direct_ms(path: Path, name: str) -> float:
    """Summed ``name`` event ms inside bench.window, straight from the
    profiler's own reader."""
    from jax.profiler import ProfileData

    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for p in ProfileData.from_file(str(path)).planes
           if p.name.startswith("/host:") for ln in p.lines for e in ln.events]
    (lo, hi), = [(a, b) for n, a, b in evs if n == "bench.window"]
    return sum(b - a for n, a, b in evs if n == name and a >= lo and b <= hi) / 1e6


def test_readers_on_the_program_trace(program_path, tmp_path, monkeypatch):
    run = _run_on(program_path, tmp_path, monkeypatch, STEPS)
    got = {name: _reader(name)(run) for name in READERS}
    for name, span in (("engine_submit_ms.backlog", "sentinel.submit"),
                       ("encode_ms.backlog", "sentinel.encode"),
                       ("fetch_ms.backlog", "sentinel.fetch"),
                       ("fill_ms.backlog", "sentinel.fill")):
        assert got[name] == pytest.approx(_direct_ms(program_path, span) / STEPS, rel=1e-9), name
    # The recorded flushes (TPU v5 lite): ~7,000 submits, the shaping
    # scan behind the fetch, the fill over ~6,000 groups.
    assert 100 < got["engine_submit_ms.backlog"] < 300
    assert 30 < got["encode_ms.backlog"] < 150
    assert 600 < got["fetch_ms.backlog"] < 1000
    assert 100 < got["fill_ms.backlog"] < 400
    kernel_ms = tr.program_s(run.trace, "flush_step")[0] / STEPS * 1e3
    loop_ms = tr.top_ops(run.trace)[0][1] / STEPS * 1e3  # the pacer's %while
    assert tr.top_ops(run.trace)[0][0].startswith("%while")
    assert got["shaping_device_ms.backlog"] <= kernel_ms
    assert got["shaping_device_ms.backlog"] == pytest.approx(loop_ms, rel=0.10)


def test_readers_report_nothing_without_program_spans(tmp_path, monkeypatch):
    run = _run_on(PARENT, tmp_path, monkeypatch, 4)
    assert {name: _reader(name)(run) for name in READERS} == dict.fromkeys(READERS)
    assert _reader("kernel_device_ms.backlog")(run) is not None


def test_untraced_run_reads_nothing():
    run = SimpleNamespace(trace=None, window=SimpleNamespace(steps=[None]))
    assert all(_reader(name)(run) is None for name in READERS)


def test_stage_scopes_cover_the_flush_programs(program, program_path):
    assert pt.scoped_share(program) >= 0.99
    # The pacer's loop carries no tf_op: the program's HLO names it.
    stages = pt.op_stages(str(program_path))
    (loop,) = [op for op in stages if op.startswith("%while")]
    assert stages[loop] == "shaping"
    by = pt.device_s_by_scope(program)
    assert {"shaping", "account", "flow", "exits"} <= set(by)
    lo, hi = program.window
    secs, _ = tr.program_s(tr.load(str(program_path)), "flush_step")
    assert sum(by.values()) == pytest.approx(secs, rel=0.01)


def test_phase_spans_nest_under_the_flush(program):
    lo, hi = program.window
    flushes = [s for s in program.spans if s.name == "sentinel.flush"
               and lo <= s.t0 and s.t1 <= hi]
    dispatched = [s for s in flushes
                  if any(program.spans[i].name == "sentinel.encode" for i in s.children)]
    assert len(dispatched) == STEPS
    for s in dispatched:
        kids = {program.spans[i].name for i in s.children}
        assert {"sentinel.flush.drain", "sentinel.flush.swap", "sentinel.encode",
                "sentinel.dispatch", "sentinel.fetch", "sentinel.fill",
                "sentinel.post_flush"} <= kids
        assert len({program.spans[i].args.get("flush") for i in s.children}) == 1
    selfs = pt.self_ms(program, "sentinel.flush")
    total = sum((s.t1 - s.t0) * 1e3 for s in flushes)
    assert sum(selfs) <= 0.05 * total
    for s in dispatched:
        kids = tr.merge([(program.spans[i].t0, program.spans[i].t1) for i in s.children])
        assert sum(b - a for a, b in kids) >= 0.95 * (s.t1 - s.t0)


def test_idle_time_is_named(program):
    idle = pt.idle_s_by_open_span(program)
    lo, hi = program.window
    busy = sum(b - a for a, b in program.busy if a >= lo and b <= hi)
    assert sum(idle.values()) + busy == pytest.approx(hi - lo, rel=1e-6)
    named = sum(v for k, v in idle.items() if k.startswith(("sentinel.", "bench.")))
    assert named >= 0.9 * sum(idle.values())
    assert idle["sentinel.fill"] > 0 and idle["sentinel.submit"] > 0
