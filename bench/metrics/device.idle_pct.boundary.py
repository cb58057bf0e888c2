"""device.idle_pct.boundary: share of the window in which the device
was idle while the program's host code at the block boundary ran (the
device-idle intervals, averaged over planes as for
device.idle_pct.closed, under a decoder.*, scheduler.* or engine.* span
other than decoder.block itself; profiler trace). Idle under loop.wait
is waiting for work, idle under no span is unattributed: neither
counts."""
from bench import devtrace, spans


def read(run):
    if not run.trace:
        return None
    idle = spans.idle_by_span(run.trace)
    if idle is None:
        return None
    lo, hi = devtrace.window_of(run.trace["host"])
    boundary = sum(s for label, s in idle.items() if spans.is_boundary(label))
    return 100.0 * boundary / ((hi - lo) / 1e9)
