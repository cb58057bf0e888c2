"""step_mfu: model FLOPs of the streaming method's passes over the real
rows of the blocks delivered in the window (block-start refresh, the
denoise steps, the head over the block rows; pad rows left out), over
the window times the chip's peak. Steps per block from the program's
counters; FLOPs from bench/flops.py and the model family's counts
(bench/families/)."""
from bench import flops
from bench.metrics._common import steps_per_block


def read(run):
    steps = steps_per_block(run)
    if steps is None or not run.peaks:
        return None
    total = sum(flops.block_flops(run.dims, run.prompt_len, run.gen_len,
                                  b, steps)
                for q in run.requests
                for b, t in zip(q.blocks, q.block_t) if run.in_window(t))
    return 100.0 * total / ((run.t1 - run.t0) * run.peaks["flops_per_s"])
