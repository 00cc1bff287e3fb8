"""Host ms per flush of the engine's own submit work: the program's
``sentinel.submit`` spans (one per ``submit_bulk`` / ``submit_exit_bulk``
call) inside the window, from the profiler trace.  ``submit_ms.backlog``
less this is the harness's own argument building."""

from harness import program_trace as pt


def read(run):
    t = pt.for_run(run)
    return None if t is None else pt.span_ms_per_flush(
        t, "sentinel.submit", len(run.window.steps))
