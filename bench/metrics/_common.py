"""Shared arithmetic of the metric readers."""
from __future__ import annotations

import numpy as np


def p95(values):
    return float(np.percentile(np.asarray(values, np.float64), 95)) \
        if values else None


def block_tokens(run, req, b: int) -> int:
    """Tokens one block event delivered: the block, cut at EOS or at
    ``max_tokens`` when the request's final event says so."""
    K = run.dims["block"]
    n = req.final["n_tokens"] if req.final else \
        run.cell.traffic["max_tokens"]
    return int(min(K, max(0, n - b * K)))


def steps_per_block(run):
    """Mean device steps per block of the requests completed in the
    window (ServeMetrics: each request's nfe over its blocks)."""
    blocks = sum(r.n_blocks for r in run.window_requests)
    if not blocks:
        return None
    return sum(r.nfe for r in run.window_requests) / blocks
