"""Whether the served answers are right: a sample of the window's
finished requests, drawn from the seed, replayed by the plain reference
(``bench/reference.py``) at the configuration's stated precision with
the served tokens. Each served token is read at every state the replay
passes through while its position is masked, and keeps its least gap:
how far its logit lies below the reference's best there. From those:

- ``logit_gap``, the widest gap;
- ``mean_gap``, the mean gap over the sample's tokens;
- ``not_top_pct``, the share of tokens that were never the top.

The configuration's ``limits`` name the numbers compared and their
limits, set from the readings ``PERF.md`` gives. The widest gap has no
limit: at both the stated precision and the control's it is one near
tie's rounding, of the same size, so it cannot tell them apart.

A request that met EOS is left out of the sample: the server returns
its tokens cut at EOS, so the block it ended in cannot be replayed.
"""
from __future__ import annotations

import math
import statistics
import time

import numpy as np

from bench import reference, traffic

SAMPLE_TOKENS = 1024     # served tokens to replay, at least two requests
MAX_SAMPLE = 16
NO_READING = 1e9         # the gap when nothing could be compared


def summarize_requests(run, log) -> dict:
    """Requests sent in the window, how many failed, and the figures
    that go on earlier lines of the output (medians, counts, generator
    lateness)."""
    sent = [q for q in run.requests if run.in_window(q.sent)]
    failed = [q for q in sent if not q.done]
    first = [1e3 * (q.block_t[0] - q.due_or_sent) for q in run.requests
             if q.block_t and run.in_window(q.block_t[0])]
    late = [q.sent - q.due for q in sent if q.due > 0]
    finish = {}
    for q in sent:
        key = (q.final or {}).get("finish_reason", q.error or "none")
        finish[key] = finish.get(key, 0) + 1
    print(f"window {run.t1 - run.t0:.3f}s: {len(sent)} requests sent, "
          f"{len(failed)} failed, finish {finish}", file=log)
    if first:
        print(f"first block: median {statistics.median(first):.1f} ms over "
              f"{len(first)} requests", file=log)
    if late:
        print(f"generator lateness: median {1e3 * statistics.median(late):.2f}"
              f" ms, max {1e3 * max(late):.2f} ms", file=log)
    for q in failed[:3]:
        print(f"failed request {q.index}: status {q.status} {q.error} "
              f"{(q.final or {}).get('finish_reason')}", file=log)
    return {"attempted": len(sent), "failed": len(failed)}


def sample(run, seed: int):
    """Finished requests to replay: drawn from the seed among those
    finished in the window (all have the cell's one length)."""
    L = run.cell.traffic["max_tokens"]
    ok = [q for q in run.requests
          if q.done and q.final["n_tokens"] == L and len(q.final["tokens"])
          == L and q.block_t and run.in_window(q.block_t[-1])]
    want = min(MAX_SAMPLE, max(2, math.ceil(SAMPLE_TOKENS / L)))
    rng = np.random.default_rng([seed, 1])
    pick = rng.permutation(len(ok))[:want]
    return [ok[i] for i in sorted(pick)]


def readings(gap: np.ndarray) -> dict:
    """The compared numbers from a decoder's per-token gaps."""
    g = gap[~np.isnan(gap)]
    return {"logit_gap": float(g.max()), "mean_gap": float(g.mean()),
            "not_top_pct": 100.0 * float((g > 0).mean())}


def describe(gap: np.ndarray) -> str:
    g = np.sort(gap[~np.isnan(gap)])
    return (f"widest {g[-1]:.6g}, 99th pct {np.percentile(g, 99):.6g}, "
            f"mean {g.mean():.6g}, not top {100.0 * (g > 0).mean():.4g}% "
            f"of {g.size}")


# The control: the reference a step of precision below the one the
# configurations state (float32 at the default matmul precision).
CONTROL = "bfloat16"


def check(run, params, seed: int, log, control: bool = False):
    """The compared numbers, each with its limit, and the program's
    readings. With ``control`` the reference in the next lower precision
    stands in the program's place: the same prompts, tokens and states,
    read at the token the control puts first, and it is the control's
    numbers that meet the limits."""
    limits = run.cell.config["limits"]
    picked = sample(run, seed)
    if not picked:
        print("no finished request to compare", file=log)
        return {k: {"value": NO_READING, "limit": v}
                for k, v in limits.items()}, None
    mix = run.cell.traffic
    prompts = np.stack([np.frombuffer(traffic.prompt(
        seed, q.index, mix["prompt_bytes"]).encode(), np.uint8)
        for q in picked]).astype(np.int32)
    served = np.asarray([q.final["tokens"] for q in picked], np.int32)
    t0 = time.perf_counter()
    out = reference.replay(run.dims, params, prompts, served,
                           control=CONTROL if control else "")
    got = readings(out["least_gap"])
    print(f"reference replay: {len(picked)} requests, {served.size} served "
          f"tokens in {time.perf_counter() - t0:.1f}s; served tokens, least "
          f"over states: {describe(out['least_gap'])}; at the replay's "
          f"commit: {describe(out['gap'])}", file=log)
    program = got
    if control:
        got = readings(out["control_gap"])
        print(f"control {CONTROL} in the program's place: "
              f"{describe(out['control_gap'])}", file=log)
    return {k: {"value": got[k], "limit": v}
            for k, v in limits.items()}, program
