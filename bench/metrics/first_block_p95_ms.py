"""first_block_p95_ms: 95th percentile, over the requests whose first
block event arrives in the window, of the time from when the request
was sent (closed loop) or due (open loop) to that event."""
from bench.metrics._common import p95


def read(run):
    return p95([1e3 * (q.block_t[0] - q.due_or_sent) for q in run.requests
                if q.block_t and run.in_window(q.block_t[0])])
