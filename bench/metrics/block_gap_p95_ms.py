"""block_gap_p95_ms: 95th percentile of the gaps between consecutive
block events of one request, over every gap that ends in the window."""
from bench.metrics._common import p95


def read(run):
    return p95([1e3 * (b - a) for q in run.requests
                for a, b in zip(q.block_t, q.block_t[1:])
                if run.in_window(b)])
