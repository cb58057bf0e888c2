"""decoder.refresh_ms: mean device time of the block-start refresh per
block program (profiler trace). The block programs are the window's
decoder.block spans; a block's operations are those that start inside
its span, and its refresh is those that start before its outermost
%while (bench/spans.py)."""
from bench import spans


def read(run):
    blocks = spans.window_phases(run)
    if not blocks:
        return None
    return 1e3 * sum(ph["refresh"] for _, ph in blocks) / len(blocks)
