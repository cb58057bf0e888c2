"""The program's own spans in the profiler's trace, on the device's
clock: where the device's idle time goes, and how long each phase of
the block program runs.

The program annotates its host work with ``repro.obs.trace.span``,
which is also a ``jax.profiler.TraceAnnotation``: ``decoder.block``
around each block program (args ``batch``, ``live``, ``block``,
``prompt_len`` at entry, ``steps`` and ``committed`` at exit), inside
it ``decoder.inputs``, ``decoder.dispatch`` and ``decoder.sync``; the
scheduler's ``scheduler.merge``, ``scheduler.admit`` (with
``scheduler.prefill`` inside), ``scheduler.harvest`` and
``scheduler.compact``; ``engine.publish``; and ``loop.wait``, the
engine loop's wait for work. A trace of a program without these spans
yields nothing here, and the readers then report nothing.

Phases of a block program are found on the ops line, from each
block's outermost ``%while`` (the denoise ``while_loop``; the last
loop of the block that no other container holds): the operations
that start before it are the block-start refresh, the loop's own
duration is its denoise steps, and what follows is the finalize. The
TPU's op events carry no stat with the program's named scopes (only
their device offset and duration), so the scopes cannot be read from
the trace; the same ``devtrace.collect`` output the other readers use
is enough.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from bench import devtrace

BLOCK = "decoder.block"
WAIT = "loop.wait"
# spans of host work at the block boundary: idle under them is the
# device waiting on the program's host code
BOUNDARY = ("decoder.", "scheduler.", "engine.")
UNATTRIBUTED = "unattributed"


def _is_program(name: str) -> bool:
    return name == WAIT or name.startswith(BOUNDARY)


def is_boundary(label: str) -> bool:
    """Whether idle under a span of this name is waiting on host work
    at the block boundary (every program span but ``decoder.block``
    itself and ``loop.wait``)."""
    return label != BLOCK and label.startswith(BOUNDARY)


def timeline(host, lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into disjoint ``(start, end, label)`` pieces, each
    labelled with the shortest program span that covers it, or
    ``unattributed``."""
    spans = [(s, s + d, n) for n, s, d, _ in host
             if _is_program(n) and s < hi and s + d > lo]
    cuts = sorted({lo, hi} | {t for s, e, _ in spans
                              for t in (s, e) if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        best = None
        for s, e, n in spans:
            if s <= a and e >= b and (best is None or e - s < best[0]):
                best = (e - s, n)
        label = best[1] if best else UNATTRIBUTED
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def _overlap_by_label(gaps, pieces) -> Dict[str, float]:
    """ns of the sorted, disjoint ``gaps`` under each label of the
    sorted, disjoint ``pieces``."""
    out: Dict[str, float] = {}
    i = 0
    for gs, ge in gaps:
        while i < len(pieces) and pieces[i][1] <= gs:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < ge:
            a, b, label = pieces[j]
            ov = min(b, ge) - max(a, gs)
            if ov > 0:
                out[label] = out.get(label, 0.0) + ov
            j += 1
    return out


def idle_by_span(ev: dict) -> Optional[Dict[str, float]]:
    """Seconds of the window in which the device was idle, by the
    program span the host was in (the shortest that covers it),
    averaged over device planes as ``devtrace.reduce`` does. None when
    the trace has no window, no device plane or no program span."""
    win = devtrace.window_of(ev["host"])
    if win is None or not ev["device"]:
        return None
    lo, hi = win
    pieces = timeline(ev["host"], lo, hi)
    if all(label == UNATTRIBUTED for _, _, label in pieces):
        return None
    total: Dict[str, float] = {}
    for evs in ev["device"].values():
        iv = [(max(s, lo), min(s + d, hi)) for _, s, d in evs
              if min(s + d, hi) > max(s, lo)]
        for label, ns in _overlap_by_label(devtrace.gaps(iv, lo, hi),
                                           pieces).items():
            total[label] = total.get(label, 0.0) + ns
    n = len(ev["device"])
    return {label: ns / n / 1e9 for label, ns in total.items()}


def blocks(host, lo: float, hi: float) -> List[dict]:
    """The ``decoder.block`` spans that lie inside [lo, hi], with their
    args and ``start_ns``/``end_ns``."""
    return [dict(st, start_ns=s, end_ns=s + d) for n, s, d, st in host
            if n == BLOCK and s >= lo and s + d <= hi]


def _outermost_loop(ops) -> Optional[Tuple[float, float]]:
    """``(start, end)`` of the last ``while`` among ``ops`` (sorted by
    start) that no other container holds."""
    containers = [(s, s + d, name) for name, s, d in ops
                  if devtrace.is_container(name)]
    best = None
    for s, e, name in containers:
        if devtrace.op_name(name).lstrip("%").split(".", 1)[0] != "while":
            continue
        inside = any(cs <= s and ce >= e and (cs, ce) != (s, e)
                     for cs, ce, _ in containers)
        if not inside and (best is None or s > best[0]):
            best = (s, e)
    return best


def phases(planes, blk: dict) -> Optional[Dict[str, float]]:
    """Device seconds of one block program's phases, averaged over the
    device planes (``planes``: each plane's ops sorted by start):
    ``refresh`` (operations that start before the denoise loop) and
    ``steps`` (the loop's duration). The program's operations are
    those that start inside the block's span. None when no plane shows
    the loop."""
    s0, e0 = blk["start_ns"], blk["end_ns"]
    refresh = steps = 0.0
    seen = False
    for starts, evs in planes:
        ops = evs[bisect.bisect_left(starts, s0):
                  bisect.bisect_right(starts, e0)]
        loop = _outermost_loop(ops)
        if loop is None:
            continue
        seen = True
        steps += loop[1] - loop[0]
        refresh += sum(d for name, s, d in ops
                       if s < loop[0] and not devtrace.is_container(name))
    if not seen:
        return None
    n = len(planes)
    return {"refresh": refresh / n / 1e9, "steps": steps / n / 1e9}


def window_phases(run) -> Optional[List[Tuple[dict, Dict[str, float]]]]:
    """``(block, phases)`` for each ``decoder.block`` span of the
    window whose program shows its loop, or None."""
    if not run.trace:
        return None
    win = devtrace.window_of(run.trace["host"])
    if win is None:
        return None
    planes = []
    for evs in run.trace["device"].values():
        evs = sorted(evs, key=lambda o: o[1])
        planes.append(([s for _, s, _ in evs], evs))
    out = []
    for blk in blocks(run.trace["host"], *win):
        ph = phases(planes, blk)
        if ph is not None:
            out.append((blk, ph))
    return out or None
