"""Pallas TPU kernel: suffix-pruned block attention (flash-style).

The dLLM decode access pattern: a small query region (current block +
pruned suffix + trailing token, typically 33-1057 tokens) attends
bidirectionally over [cached prefix KV || self KV] (up to 512k tokens at
long context). This is the compute hot-spot of every denoise step, so we
tile it explicitly for VMEM:

  grid = (B, H, nQ, nK)   -- nK innermost (sequential on TPU)
  q tile  (TQ, D) VMEM    -- MXU-aligned (TQ, D multiples of 128 ideal)
  k/v tile (TK, D) VMEM
  online-softmax scratch: acc (TQ, D) f32, m/l (TQ, 1) f32

Layout (TPU tiling: the last two dims of every block must be multiples
of (8, 128) or span the whole array dim). q/k/v are viewed as
``(B, S, H*D)`` — a free reshape — and one head is the block
``(1, T, D)`` at column-block ``h``, so the head dim must be a multiple
of 128 on TPU (LLaDA-8B and Dream-7B use 128). Query positions travel
as ``(B, Sq, 1)`` (a ``(TQ, 1)`` column per tile), key positions and
the key-validity mask as ``(B, 1, Skv)`` (a ``(1, TK)`` row); the mask
is int32 because Mosaic has no bool memrefs.

Features folded into the same kernel (all static): GQA head mapping,
attention-logit softcap (gemma2), sliding-window masking (local layers /
long_500k dense variant), and arbitrary KV validity (growing caches and
the dKV position-indexed cache).

The kernel runs compiled on TPU and in interpret mode on every other
backend (``interpret=None`` picks at trace time, see
``kernels.mode``); the CPU tests validate it against
``ref.block_attention_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mode

NEG_INF = -1e30
# Clamp for the running max so fully-masked tiles don't produce
# exp(-inf - (-inf)) = 1 artifacts.
M_CLAMP = -1e4


def _kernel(q_ref, k_ref, v_ref, qpos_ref, kvpos_ref, kvmask_ref,
            o_ref, acc_ref, m_ref, l_ref, *, scale, softcap, window,
            n_kv_tiles, precision):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, M_CLAMP)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale                # (TQ, D)
    k = k_ref[0].astype(jnp.float32)                        # (TK, D)
    v = v_ref[0].astype(jnp.float32)                        # (TK, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=precision)            # (TQ, TK)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    mask = kvmask_ref[0] != 0                               # (1, TK)
    if window:
        qp = qpos_ref[0]                                    # (TQ, 1)
        kp = kvpos_ref[0]                                   # (1, TK)
        mask = mask & (jnp.abs(qp - kp) <= window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                     # (TQ, 1)
    m_cur = jnp.maximum(jnp.max(s, axis=1, keepdims=True), M_CLAMP)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                  # (TQ, TK)
    correction = jnp.exp(m_prev - m_new)                    # (TQ, 1)
    l_ref[...] = l_ref[...] * correction + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=precision)
    acc_ref[...] = acc_ref[...] * correction + pv
    m_ref[...] = m_new

    @pl.when(j == n_kv_tiles - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "window",
                                             "tq", "tk", "interpret"))
def block_attention(q, k, v, q_pos, kv_pos, kv_mask, *, scale,
                    softcap: float = 0.0, window: int = 0, tq: int = 128,
                    tk: int = 128, interpret: Optional[bool] = None):
    """q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D); masks per ref.py.

    Returns (B, Sq, H, D) f32. Pads Sq/Skv to tile multiples internally.
    """
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    tq = min(tq, max(8, 1 << (Sq - 1).bit_length()))
    tk = min(tk, max(8, 1 << (Skv - 1).bit_length()))
    Sq_p = -(-Sq // tq) * tq
    Skv_p = -(-Skv // tk) * tk
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, Sq_p - Sq)))
    if Skv_p != Skv:
        k = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, Skv_p - Skv)))
        kv_mask = jnp.pad(kv_mask, ((0, 0), (0, Skv_p - Skv)))
    nq, nk = Sq_p // tq, Skv_p // tk

    grid = (B, H, nq, nk)
    kernel = functools.partial(_kernel, scale=scale, softcap=softcap,
                               window=window, n_kv_tiles=nk,
                               precision=mode.matmul_precision())
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, D), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((1, tk, D), lambda b, h, i, j: (b, j, h // g)),
            pl.BlockSpec((1, tk, D), lambda b, h, i, j: (b, j, h // g)),
            pl.BlockSpec((1, tq, 1), lambda b, h, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, tk), lambda b, h, i, j: (b, 0, j)),
            pl.BlockSpec((1, 1, tk), lambda b, h, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, tq, D), lambda b, h, i, j: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct((B, Sq_p, H * D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tq, D), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
        ],
        interpret=mode.resolve_interpret(interpret),
        name="block_attention",
    )(q.reshape(B, Sq_p, H * D), k.reshape(B, Skv_p, Hkv * D),
      v.reshape(B, Skv_p, Hkv * D),
      q_pos.astype(jnp.int32)[:, :, None],
      kv_pos.astype(jnp.int32)[:, None, :],
      kv_mask.astype(jnp.int32)[:, None, :])
    return out.reshape(B, Sq_p, H, D)[:, :Sq]
