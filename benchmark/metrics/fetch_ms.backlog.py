"""Host ms per flush of the engine's result fetch: the program's
``sentinel.fetch`` spans (the host waiting for the kernel, then the
device-to-host copy) inside the window, from the profiler trace.  Beside
``kernel_device_ms.backlog`` it shows the copy and the sync cost."""

from harness import program_trace as pt


def read(run):
    t = pt.for_run(run)
    return None if t is None else pt.span_ms_per_flush(
        t, "sentinel.fetch", len(run.window.steps))
