"""device.idle_pct.closed: share of the window in which no operation
ran on the device (profiler trace; union of the XLA Ops intervals)."""


def read(run):
    red = run.reduced
    if not red or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
