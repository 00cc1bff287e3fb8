"""Device ms per flush of the flush programs' operations under the
``sentinel/shaping`` scope (the shaping pacer and its scatters), inside
the window, from the profiler trace."""

from harness import program_trace as pt


def read(run):
    t = pt.for_run(run)
    if t is None:
        return None
    secs = pt.device_s_by_scope(t).get("shaping")
    steps = len(run.window.steps)
    return secs / steps * 1e3 if secs and steps else None
