"""The profiler's trace of the window, reduced to device metrics.

Only a ``--trace 1`` run starts the profiler. The reduction reads the
``.xplane.pb`` with ``jax.profiler.ProfileData``: device planes are
``/device:TPU:<n>``, their ``XLA Ops`` line holds one event per
operation run, host planes hold the benchmark's own annotations
(``bench.block``, one per decoded block with its shape) and the
runtime's events. All times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):] \
        .isdigit()


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def collect(profile) -> dict:
    """Plain lists from a ProfileData (or any object shaped like one):
    ``device``: {plane: [(name, start_ns, dur_ns)]} from each device
    plane's ops line; ``host``: [(name, start_ns, dur_ns, stats)] from
    every host line."""
    device: Dict[str, List[Tuple]] = {}
    host: List[Tuple] = []
    for plane in profile.planes:
        if is_device_plane(plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns, ev.duration_ns,
                                 _stats(ev)))
    return {"device": device, "host": host}


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float):
    """The idle ``(start, end)`` stretches of [lo, hi] not covered by
    any interval."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def window_of(host, name: str = "bench.window"):
    """The [start, end] ns of the benchmark's window annotation."""
    for n, s, d, _ in host:
        if n == name:
            return s, s + d
    return None


# operations that contain others on the ops line: their time is the
# time of what runs inside them
CONTAINERS = ("while", "conditional", "call")


def op_name(name: str) -> str:
    """An XLA op event's short name: the instruction before ``=``."""
    return name.split(" = ", 1)[0].strip()


def is_container(name: str) -> bool:
    short = op_name(name).lstrip("%")
    return short.split(".", 1)[0] in CONTAINERS


def matches(name: str, kernel: str) -> bool:
    """Whether an ops-line event is a call of ``kernel``: a named
    ``pallas_call`` appears as ``%<kernel>.<n> = ... custom-call(...)``."""
    short = op_name(name).lstrip("%")
    return short == kernel or short.startswith(kernel + ".")


def reduce(ev: dict, kernels=("block_attention", "confidence_argmax"),
           top: int = 10) -> dict:
    """Busy and idle time of the device over the window, time per
    kernel, the operations that took the most time (by short name,
    leaving out the loops and calls that contain other operations) and
    the longest idle gaps, each labelled with the host event that
    overlaps it most."""
    win = window_of(ev["host"])
    if win is None:
        raise ValueError("trace has no bench.window annotation")
    lo, hi = win
    busy, by_op, kern = [], {}, {k: 0.0 for k in kernels}
    all_gaps = []
    for plane, evs in ev["device"].items():
        iv = []
        for name, s, d in evs:
            s2, e2 = max(s, lo), min(s + d, hi)
            if e2 <= s2:
                continue
            iv.append((s2, e2))
            if not is_container(name):
                key = op_name(name)
                by_op[key] = by_op.get(key, 0.0) + (e2 - s2)
            for k in kernels:
                if matches(name, k):
                    kern[k] += e2 - s2
        busy.append(union_ns(iv))
        all_gaps.extend(gaps(iv, lo, hi))
    n = max(len(busy), 1)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(label_gap(ev["host"], s, e), (e - s) / 1e9)
                for s, e in all_gaps[:top]]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / n / 1e9,
            "devices": len(busy),
            "kernel_s": {k: v / n / 1e9 for k, v in kern.items()},
            "device_ops": sorted(((k, v / n / 1e9) for k, v in by_op.items()),
                                 key=lambda kv: -kv[1])[:top],
            "idle_gaps": labelled}


def label_gap(host, s: float, e: float) -> str:
    """The shortest host event that covers most of the gap, so an
    enclosing span (the window itself) does not name every gap."""
    best, best_key = "no host event", None
    for name, hs, hd, _ in host:
        if name == "bench.window":
            continue
        ov = min(e, hs + hd) - max(s, hs)
        if ov <= 0:
            continue
        key = (-min(ov / (e - s), 0.5), hd)
        if best_key is None or key < best_key:
            best, best_key = name, key
    return best


def blocks(host) -> List[dict]:
    """The benchmark's per-block annotations: start/end ns and shape."""
    out = []
    for name, s, d, st in host:
        if name == "bench.block":
            out.append(dict(st, start_ns=s, end_ns=s + d))
    return out


def kernel_calls(ev: dict, kernel: str):
    """Start times (sorted) and durations, in ns, of ``kernel``'s calls
    on every device plane."""
    import numpy as np
    calls = sorted((s, d) for evs in ev["device"].values()
                   for name, s, d in evs if matches(name, kernel))
    arr = np.asarray(calls, np.float64).reshape(-1, 2)
    return arr[:, 0], arr[:, 1]


def time_in(calls, s: float, e: float, planes: int) -> float:
    """Seconds of the ``calls`` that start within [s, e], averaged over
    ``planes`` device planes."""
    import numpy as np
    starts, durs = calls
    i, j = np.searchsorted(starts, [s, e], side="left")
    j = np.searchsorted(starts, e, side="right")
    return float(durs[i:j].sum()) / max(planes, 1) / 1e9
