"""Observability subsystem: tracing, decode telemetry, histograms,
structured logging, and profiler hooks.

Layering (threaded through every serving layer):

    trace      — request span trees + per-thread timelines on a
                 lock-free-ish ring-buffer ``Tracer`` (monotonic
                 clocks), exported as Chrome-trace JSON loadable in
                 Perfetto: one track per engine/decode thread plus an
                 async track per request (accept → admission → blocks
                 → finalize), correlated by trace id. ``span`` also
                 writes every thread-track span into a running
                 ``jax.profiler`` capture, on the device trace's clock.
    telemetry  — per-block diffusion dynamics harvested from the fused
                 decode loop in its ONE existing host sync (steps used
                 vs the τ-schedule cap, tokens committed per step,
                 confidence histogram, suffix-window size, early-exit/
                 straggler flags), aggregated per (method, block index).
    metrics    — bucketed ``Histogram`` counters for Prometheus
                 exposition and device memory gauges.
    log        — JSON-lines structured logger carrying uid/engine/gang
                 fields (``--log-level`` / ``--log-json``).
    profiler   — ``jax.profiler`` start/stop around the first N decoded
                 blocks (``--profile-blocks N``).
    audit      — online quality auditing: shadow-oracle re-decode of
                 sampled completions (host-loop and cache-bypass
                 lanes, divergences classified by source and attributed
                 to their block), confidence-calibration/early-exit-
                 regret counters, rolling SLO watchdog, and a
                 flight-recorder post-mortem dump
                 (``--flight-dir`` / ``GET /debug/flight``).
    series     — fleet time-series: a per-engine ``MetricsRecorder``
                 sampling counter deltas on the decode-thread cadence
                 into a bounded ring, derived rate series (tok/s, rps,
                 goodput, busy fractions — the pool-sizing signal),
                 fleet/pool fan-in for ``GET /debug/timeline`` and the
                 ``GET /console`` page, optional ``--metrics-log``
                 JSONL persistence.

Everything is optional: a ``tracer=None`` (the default everywhere)
leaves a ``span`` one profiler annotation (about a microsecond with no
capture running), other call sites one ``is None`` test, and telemetry
rides inside
the already-compiled fused loop, so ``host_syncs_per_block`` is
unchanged with observability on.
"""
from repro.obs.audit import (AuditConfig, AuditResult, FlightRecorder,
                             ShadowAuditor, SLOWatchdog)
from repro.obs.compile import (CompileWatch, persistent_cache_counters,
                               watch_persistent_cache)
from repro.obs.log import get_logger, setup_logging
from repro.obs.metrics import Histogram, device_memory_stats
from repro.obs.profiler import BlockProfiler
from repro.obs.series import (JsonlSink, MetricsRecorder, fleet_series,
                              timeline_doc)
from repro.obs.telemetry import (CONF_BUCKETS, BlockStats,
                                 TelemetryAggregator)
from repro.obs.trace import Tracer, TraceFlusher, span

__all__ = [
    "Tracer", "TraceFlusher", "span", "BlockStats", "TelemetryAggregator",
    "CONF_BUCKETS",
    "Histogram", "device_memory_stats", "BlockProfiler",
    "CompileWatch", "watch_persistent_cache", "persistent_cache_counters",
    "get_logger", "setup_logging",
    "AuditConfig", "AuditResult", "ShadowAuditor", "SLOWatchdog",
    "FlightRecorder",
    "MetricsRecorder", "JsonlSink", "fleet_series", "timeline_doc",
]
