"""How the Pallas kernels run: compiled or interpreted, and at what dot
precision. Both are decided at trace time, for every kernel alike."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode unless the kernel is being traced for a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def matmul_precision():
    """In-kernel dot precision following the caller's
    ``jax.default_matmul_precision`` scope, so a float32 reference run
    under "highest" also gets full-precision MXU passes in the kernel."""
    if jax.config.jax_default_matmul_precision in ("highest", "float32"):
        return jax.lax.Precision.HIGHEST
    return None
