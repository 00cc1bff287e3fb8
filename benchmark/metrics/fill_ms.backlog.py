"""Host ms per flush of the engine's verdict fill: the program's
``sentinel.fill`` spans (verdicts fanned out to the flush's groups from
the fetched arrays) inside the window, from the profiler trace."""

from harness import program_trace as pt


def read(run):
    t = pt.for_run(run)
    return None if t is None else pt.span_ms_per_flush(
        t, "sentinel.fill", len(run.window.steps))
