"""Host ms per flush of the engine's encode: the program's
``sentinel.encode`` spans (staging, shaping and param encode) inside the
window, from the profiler trace."""

from harness import program_trace as pt


def read(run):
    t = pt.for_run(run)
    return None if t is None else pt.span_ms_per_flush(
        t, "sentinel.encode", len(run.window.steps))
