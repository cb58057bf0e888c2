"""block_attention_roofline: the least time the chip could take for the
block_attention calls of the window's blocks (max of FLOPs over peak
and bytes over bandwidth, per call, as the model family counts them:
valid keys only, K/V once per KV head; every lane of the gang), over
the kernel's device time in the profiler trace. Blocks are the
benchmark's bench.block annotations that lie inside the window; the
kernel's events are those that start inside each block's annotation."""
from bench import devtrace, families, flops
from bench.metrics._common import steps_per_block

KERNEL = "block_attention"


def read(run):
    steps = steps_per_block(run)
    if steps is None or not run.trace or not run.peaks:
        return None
    lo, hi = devtrace.window_of(run.trace["host"])
    least = kernel_s = 0.0
    m, pk = run.dims, run.peaks
    fam = families.load(m["family"])
    calls = devtrace.kernel_calls(run.trace, KERNEL)
    planes = len(run.trace["device"])
    for blk in devtrace.blocks(run.trace["host"]):
        if blk["start_ns"] < lo or blk["end_ns"] > hi:
            continue
        t = devtrace.time_in(calls, blk["start_ns"], blk["end_ns"], planes)
        if t <= 0:
            continue
        for count, sq, skv in flops.block_passes(
                m, run.prompt_len, run.gen_len, int(blk["block"]), steps):
            f = fam.attention_flops(m, sq, skv)
            b = fam.attention_bytes(m, sq, skv)
            least += (count * m["layers"] * int(blk["batch"])
                      * max(f / pk["flops_per_s"], b / pk["hbm_bytes_per_s"]))
        kernel_s += t
    if kernel_s <= 0:
        return None
    return 100.0 * least / kernel_s
