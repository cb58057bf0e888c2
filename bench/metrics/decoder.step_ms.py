"""decoder.step_ms: device time of the denoise steps (each block
program's outermost %while) over the window's decoder.block spans,
divided by the sum of their steps − 1, the refresh being step 1 of
``steps`` (the span's exit arg; profiler trace, bench/spans.py)."""
from bench import spans


def read(run):
    blocks = [(b, ph) for b, ph in spans.window_phases(run) or ()
              if "steps" in b]
    n = sum(int(b["steps"]) - 1 for b, _ in blocks)
    if n <= 0:
        return None
    return 1e3 * sum(ph["steps"] for _, ph in blocks) / n
