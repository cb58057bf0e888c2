"""``jax.profiler`` start/stop around the first N decoded blocks.

``--profile-blocks N`` captures a device-level profile of exactly the
steady-state region that matters (skipping jit warm-up is the caller's
job — the engine ticks the profiler only after its warm-up wave).
The capture is written as a TensorBoard-loadable trace under
``<trace_dir>/jax_profile``. It holds the program's own spans
(``repro.obs.trace.span``: ``decoder.*``, ``scheduler.*``,
``engine.*``, ``loop.wait``) on the device's clock, without Python
function tracing, as the chip benchmark's capture does; the same spans
also feed the host-side Chrome trace the
:class:`~repro.obs.trace.Tracer` exports.

Failure to start the profiler (unsupported backend, second profiler
already live) degrades to a no-op with a warning — observability must
never take down serving.
"""
from __future__ import annotations

import os
from typing import Optional

from repro.obs.log import get_logger

log = get_logger(__name__)


class BlockProfiler:
    """Counts decoded blocks; profiles the first ``n_blocks`` of them.

    Call ``tick(k)`` with the number of blocks decoded since the last
    tick (0 is fine and cheap). The first tick with work starts the
    capture; the tick that crosses ``n_blocks`` stops it. ``close()``
    stops a capture left running at shutdown.
    """

    def __init__(self, trace_dir: str, n_blocks: int):
        self.trace_dir = os.path.join(trace_dir, "jax_profile")
        self.n_blocks = n_blocks
        self.seen = 0
        self.active = False
        self.done = n_blocks <= 0

    def tick(self, blocks_decoded: int) -> None:
        if self.done:
            return
        if not self.active and blocks_decoded > 0:
            try:
                import jax
                os.makedirs(self.trace_dir, exist_ok=True)
                opts = jax.profiler.ProfileOptions()
                # no Python function tracing: it would slow the host
                # path between blocks that the capture is there to show;
                # the program's spans (repro.obs.trace.span) are
                # recorded without it
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self.active = True
                log.info("jax profiler started",
                         extra={"trace_dir": self.trace_dir,
                                "profile_blocks": self.n_blocks})
            except Exception as e:
                log.warning("jax profiler unavailable: %s", e)
                self.done = True
                return
        self.seen += blocks_decoded
        if self.active and self.seen >= self.n_blocks:
            self._stop()

    def _stop(self) -> None:
        try:
            import jax
            jax.profiler.stop_trace()
            log.info("jax profiler stopped",
                     extra={"blocks": self.seen,
                            "trace_dir": self.trace_dir})
        except Exception as e:   # pragma: no cover - defensive
            log.warning("jax profiler stop failed: %s", e)
        self.active = False
        self.done = True

    def close(self) -> None:
        if self.active:
            self._stop()
