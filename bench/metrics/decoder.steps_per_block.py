"""decoder.steps_per_block: device denoise steps per decoded block, from
the program's ServeMetrics records of the requests completed in the
window (each request's nfe over its blocks)."""
from bench.metrics._common import steps_per_block


def read(run):
    return steps_per_block(run)
