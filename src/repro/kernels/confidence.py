"""Pallas TPU kernel: fused confidence + argmax over the vocabulary.

Eq. 4 of the paper needs, per query position, the max softmax probability
(the commit confidence) and the argmax token. Materializing softmax over
a 256k vocab every denoise step is pure HBM waste; this kernel streams
vocab tiles through VMEM once, tracking running (max, sum-exp, argmax):

  conf = exp(max - logsumexp) = 1 / sumexp_normalized_by_max

  grid = (nS, nV)  -- vocab tiles innermost/sequential
  logits tile (TS, TV) VMEM; scratch m/s (TS,1) f32, amax (TS,1) i32

Runs compiled on TPU and in interpret mode elsewhere (see
``kernels.mode``); validated on CPU against
ref.confidence_argmax_ref.
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


def _kernel(x_ref, conf_ref, idx_ref, m_ref, s_ref, a_ref, *, n_v_tiles, tv):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        a_ref[...] = jnp.zeros_like(a_ref)

    x = x_ref[...].astype(jnp.float32)                       # (TS, TV)
    tile_max = jnp.max(x, axis=1, keepdims=True)             # (TS, 1)
    tile_arg = jnp.argmax(x, axis=1).astype(jnp.int32)[:, None] + j * tv

    m_prev = m_ref[...]
    better = tile_max > m_prev
    m_new = jnp.maximum(m_prev, tile_max)
    s_ref[...] = s_ref[...] * jnp.exp(m_prev - m_new) + \
        jnp.sum(jnp.exp(x - m_new), axis=1, keepdims=True)
    a_ref[...] = jnp.where(better, tile_arg, a_ref[...])
    m_ref[...] = m_new

    @pl.when(j == n_v_tiles - 1)
    def _finalize():
        conf_ref[...] = 1.0 / jnp.maximum(s_ref[...], 1e-30)
        idx_ref[...] = a_ref[...]


@functools.partial(jax.jit, static_argnames=("ts", "tv", "interpret"))
def confidence_argmax(logits, *, ts: int = 128, tv: int = 512,
                      interpret: Optional[bool] = None):
    """logits: (N, V) -> (conf (N,) f32, idx (N,) i32)."""
    N, V = logits.shape
    ts = min(ts, max(8, 1 << (N - 1).bit_length()))
    tv = min(tv, max(128, 1 << (V - 1).bit_length()))
    N_p = -(-N // ts) * ts
    V_p = -(-V // tv) * tv
    x = logits
    if N_p != N:
        x = jnp.pad(x, ((0, N_p - N), (0, 0)))
    if V_p != V:
        x = jnp.pad(x, ((0, 0), (0, V_p - V)), constant_values=NEG_INF)
    ns, nv = N_p // ts, V_p // tv
    kernel = functools.partial(_kernel, n_v_tiles=nv, tv=tv)
    conf, idx = pl.pallas_call(
        kernel,
        grid=(ns, nv),
        in_specs=[pl.BlockSpec((ts, tv), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((ts, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((ts, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((N_p, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N_p, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((ts, 1), jnp.float32),
                        pltpu.VMEM((ts, 1), jnp.float32),
                        pltpu.VMEM((ts, 1), jnp.int32)],
        interpret=mode.resolve_interpret(interpret),
        name="confidence_argmax",
    )(x)
    return conf[:N, 0], idx[:N, 0]
