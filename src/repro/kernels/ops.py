"""Jit'd public wrappers around the Pallas kernels.

The kernels run compiled on TPU and in interpret mode on any other
backend, picked at trace time; pass ``interpret=`` to override (the
TPU compile tests pass ``False`` while tracing on a CPU host).
"""
from __future__ import annotations

import math
import re

import jax.numpy as jnp

from repro.kernels.block_attention import block_attention as _block_attention
from repro.kernels.confidence import confidence_argmax as _confidence_argmax


_KERNEL_CALL = re.compile(r"%([A-Za-z_]+)(?:\.\d+)? = .*tpu_custom_call")


def compiled_kernels(hlo_text: str) -> set:
    """Names of the Pallas kernels a compiled TPU program calls (each
    ``pallas_call`` is named after its kernel). An interpreted kernel
    lowers to plain HLO and never shows up here."""
    return {m.group(1) for m in _KERNEL_CALL.finditer(hlo_text)}


def block_attention(q, k, v, q_pos, kv_pos, kv_mask, *, scale=None,
                    softcap: float = 0.0, window: int = 0, **kw):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _block_attention(q, k, v, q_pos, kv_pos, kv_mask, scale=scale,
                            softcap=softcap, window=window, **kw)


def sliding_window_attention(q, k, v, q_pos, kv_pos, *, window: int,
                             scale=None, softcap: float = 0.0, **kw):
    """Local-attention specialization (gemma2 local layers, long_500k
    dense variant): full KV validity, distance-window mask only."""
    kv_mask = jnp.ones(kv_pos.shape, jnp.bool_)
    return block_attention(q, k, v, q_pos, kv_pos, kv_mask, scale=scale,
                           softcap=softcap, window=window, **kw)


def confidence_argmax(logits, **kw):
    """logits: (..., V) -> (conf (...,), idx (...,)).

    2-D inputs (the fused-head path feeds row chunks) go straight to the
    kernel — no intermediate full-vocab reshape of an array that is
    already in kernel layout."""
    if logits.ndim == 2:
        return _confidence_argmax(logits, **kw)
    shape = logits.shape[:-1]
    conf, idx = _confidence_argmax(logits.reshape(-1, logits.shape[-1]), **kw)
    return conf.reshape(shape), idx.reshape(shape)


def head_confidence_argmax(hidden, head, *, mask_id: int = -1,
                           logit_softcap: float = 0.0,
                           row_chunk: int = 1024, **kw):
    """Fused LM-head projection + confidence/argmax (Eq. 4) without ever
    materializing the full ``(..., V)`` logits in HBM.

    hidden: (..., d) final hidden states (``apply_model(skip_head=True)``);
    head: (d, V) projection. Rows are chunked so peak memory is
    O(row_chunk x V); within each chunk the Pallas kernel streams vocab
    tiles through VMEM. ``mask_id >= 0`` bans that token (LLaDA: never
    emit [MASK]) inside the projected tile, before the reduction."""
    from repro.core.schedule import chunked_head_reduce
    return chunked_head_reduce(
        hidden, head, lambda logits: confidence_argmax(logits, **kw),
        mask_id=mask_id, logit_softcap=logit_softcap, row_chunk=row_chunk)
