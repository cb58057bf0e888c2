"""scheduler.queue_wait_p95_ms: 95th percentile of the program's
per-request queue_s (submit to admission into a gang) over the requests
completed in the window."""
from bench.metrics._common import p95


def read(run):
    return p95([1e3 * r.queue_s for r in run.window_requests])
