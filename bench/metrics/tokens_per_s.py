"""tokens_per_s: output tokens delivered in SSE block events inside the
window, over the window (client side, host clock)."""
from bench.metrics._common import block_tokens


def read(run):
    n = sum(block_tokens(run, q, b) for q in run.requests
            for b, t in zip(q.blocks, q.block_t) if run.in_window(t))
    return n / (run.t1 - run.t0)
