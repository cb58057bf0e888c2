"""Compile accounting: per-engine jit-variant ledger + process-level
persistent-cache counters.

XLA compiles are the single largest host-side latency source on a cold
engine (the PR 6 trace attributed the multi-engine throughput
regression to concurrent first-block compiles), so they are tracked
like any other resource:

* ``CompileWatch`` — one per ``BlockScheduler``. Every jit-dispatching
  call site (prefill, decode_block, resume re-prime, merge/compaction
  buffer acquire) is wrapped so the scheduler-wide ``jit_cache_size()``
  delta attributes new compiled variants to the call that triggered
  them, with its wall time. After ``mark_warm()`` (the startup pre-warm
  finished), any further compile is a *post-warmup compile*: counted,
  logged loudly, and exported (``repro_post_warm_compiles_total``) —
  the recompile-watchdog test asserts the counter stays zero under a
  mixed-bucket load.
* ``watch_persistent_cache()`` — process-global listener on jax's
  ``/jax/compilation_cache/*`` monitoring events, counting hits and
  misses of the on-disk persistent cache enabled via
  ``repro.launch.host.enable_compile_cache``. These are distinct from
  the CompileWatch numbers: a persistent-cache *hit* still shows up as
  a CompileWatch miss (a new in-process variant was built — just from
  cached bytes instead of an XLA compile).

Both surfaces are read by the ``/metrics`` endpoint and by
``bench_sharded.py`` (zero-post-warm-compiles acceptance line).
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Optional

from repro.obs.log import get_logger

log = get_logger("obs.compile")


class CompileWatch:
    """Single-writer ledger (the owning engine's decode thread); the
    plain-int counters are mirrored into ``ServeMetrics`` each engine
    step, so cross-thread readers go through the metrics snapshot."""

    def __init__(self) -> None:
        self.misses = 0          # new compiled variants (jit cache grew)
        self.hits = 0            # dispatches fully served by compiled code
        self.seconds = 0.0       # wall attributed to variant-building calls
        self.warm = False        # pre-warm declared complete
        self.post_warm = 0       # variants built after mark_warm()

    def mark_warm(self) -> None:
        self.warm = True

    def counters(self) -> dict:
        """JSON-safe ledger snapshot (debug_state / flight dumps)."""
        return {"misses": self.misses, "hits": self.hits,
                "seconds": self.seconds, "warm": self.warm,
                "post_warm": self.post_warm}

    def watched(self, thunk: Callable, sizer: Callable[[], int],
                what: str, tracer=None, pid: int = 0):
        """Run ``thunk``; attribute any jit-cache growth (measured via
        ``sizer``) to it. Emits a retrospective ``compile`` span on the
        engine's thread track when variants were built, so warm vs cold
        calls are visually distinct in the trace."""
        before = sizer()
        t0_ns = time.perf_counter_ns()
        out = thunk()
        t1_ns = time.perf_counter_ns()
        self.observe(sizer() - before, (t1_ns - t0_ns) / 1e9, what,
                     tracer=tracer, pid=pid, t0_ns=t0_ns, t1_ns=t1_ns)
        return out

    def observe(self, delta: int, wall_s: float, what: str, *,
                tracer=None, pid: int = 0,
                t0_ns: Optional[int] = None,
                t1_ns: Optional[int] = None) -> None:
        if delta <= 0:
            self.hits += 1
            return
        self.misses += delta
        self.seconds += wall_s
        if tracer is not None and t0_ns is not None:
            tracer.complete("compile", t0_ns, t1_ns, pid=pid,
                            variants=delta, what=what)
        if self.warm:
            self.post_warm += delta
            log.warning(
                "post-warmup compile: %d new variant(s) in %s (%.2fs) — "
                "pre-warm missed a (bucket, batch, block) shape",
                delta, what, wall_s)


# ------------------------------------------------ persistent cache events

_pc_lock = threading.Lock()
_pc_counters = {"hits": 0, "misses": 0}
_pc_registered = False


def _on_event(event: str, **kw) -> None:
    if "/jax/compilation_cache/" not in event:
        return
    with _pc_lock:
        if event.endswith("cache_hits"):
            _pc_counters["hits"] += 1
        elif event.endswith("cache_misses"):
            _pc_counters["misses"] += 1


def watch_persistent_cache() -> None:
    """Register the jax monitoring listener (idempotent)."""
    global _pc_registered
    if _pc_registered:
        return
    from jax._src import monitoring
    monitoring.register_event_listener(_on_event)
    _pc_registered = True


def persistent_cache_counters() -> dict:
    with _pc_lock:
        return dict(_pc_counters)
