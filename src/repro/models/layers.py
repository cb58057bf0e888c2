"""Core neural layers: RMSNorm, RoPE, GQA attention (reference path),
SwiGLU/GELU FFNs.

All layers are pure functions over pytree params. Attention supports the
diffusion access pattern: a (possibly short) query region attending over
``[cached prefix KV || self KV]`` bidirectionally, with optional sliding
window, qk-norm, and logit softcap. Position ids are explicit everywhere
because suffix pruning produces non-contiguous positions (Eq. 7 in the
paper keeps the trailing position id).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.heads import HeadPlan

NEG_INF = -1e30


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------- RoPE

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)                      # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs      # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- init

def _dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / math.sqrt(in_axis_size)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_attention(key, cfg, plan: HeadPlan, dtype) -> dict:
    """Weights at *padded* head counts; padded q heads are zero."""
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    # Place real q heads group-contiguously: group g occupies
    # [0 : q_per_kv_real] within each padded group (rest zero).
    p_real = plan.n_q // plan.n_kv
    real_q = _dense_init(ks[0], (d, plan.n_kv, p_real, hd), d, dtype)
    real_o = _dense_init(ks[1], (plan.n_kv, p_real, hd, d), plan.n_q * hd, dtype)
    n_groups = plan.n_kv + plan.kv_zero_groups
    pp = plan.pad_q // n_groups
    wq = jnp.zeros((d, n_groups, pp, hd), dtype).at[:, :plan.n_kv, :p_real].set(real_q)
    wo = jnp.zeros((n_groups, pp, hd, d), dtype).at[:plan.n_kv, :p_real].set(real_o)
    wq = wq.reshape(d, plan.pad_q, hd)
    wo = wo.reshape(plan.pad_q, hd, d)

    wk_real = _dense_init(ks[2], (d, plan.n_kv, hd), d, dtype)
    wv_real = _dense_init(ks[3], (d, plan.n_kv, hd), d, dtype)
    if plan.kv_zero_groups:
        z = jnp.zeros((d, plan.kv_zero_groups, hd), dtype)
        wk_real = jnp.concatenate([wk_real, z], axis=1)
        wv_real = jnp.concatenate([wv_real, z], axis=1)
    wk = jnp.repeat(wk_real, plan.kv_dup, axis=1)
    wv = jnp.repeat(wv_real, plan.kv_dup, axis=1)
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def init_ffn(key, cfg, kind: str, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {"w_gate": _dense_init(ks[0], (d, f), d, dtype),
                "w_up": _dense_init(ks[1], (d, f), d, dtype),
                "w_down": _dense_init(ks[2], (f, d), f, dtype)}
    return {"w_up": _dense_init(ks[0], (d, f), d, dtype),
            "w_down": _dense_init(ks[1], (f, d), f, dtype)}


# ---------------------------------------------------------------- attention

# Above this many score elements per (B*H) the reference path chunks the
# query axis (lax.map) so peak memory is O(chunk x Skv), matching the
# flash-style Pallas kernel it stands in for (EXPERIMENTS.md §Perf #3).
# REPRO_DISABLE_CHUNKING=1 (exact-flops dry-runs) turns chunking off:
# XLA cost analysis counts a lax.map body once, so chunked attention
# under-reports flops by the chunk count.
_SCORE_BUDGET = 32 * 1024 * 1024


def _score_budget():
    import os
    if os.environ.get("REPRO_DISABLE_CHUNKING") == "1":
        return 1 << 60
    return _SCORE_BUDGET


def _attend_chunk(q, k, v, q_pos, kv_pos, kv_mask, *, scale, attn_softcap,
                  window):
    """One query chunk. q: (B,Sq,H,D); kv_mask: (B,Skv) bool or None."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    # K/V stay in storage dtype (bf16 on TPU); dots accumulate in f32 via
    # preferred_element_type — no f32 copy of the (500k-token) cache.
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(B, Sq, Hkv, g, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    if attn_softcap:
        scores = softcap(scores, attn_softcap)
    mask = None
    if window:
        dist = jnp.abs(q_pos[:, :, None].astype(jnp.int32)
                       - kv_pos[:, None, :].astype(jnp.int32))  # (B,Sq,Skv)
        mask = dist <= window
    if kv_mask is not None:
        vmask = jnp.broadcast_to(kv_mask[:, None, :], (B, Sq, k.shape[1]))
        mask = vmask if mask is None else (mask & vmask)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def attend_ref(q, k, v, *, scale, attn_softcap=0.0, window=0,
               q_pos=None, kv_pos=None, kv_valid=None, kv_mask=None):
    """Reference bidirectional attention (the Pallas-kernel oracle path).

    q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D). H % Hkv == 0 (GQA).
    window > 0 masks |q_pos - kv_pos| > window (bidirectional local).
    kv_valid: (B,) used length; kv_mask: (B, Skv) explicit validity.
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    if kv_valid is not None and kv_mask is None:
        idx = jnp.arange(Skv)[None, :]
        kv_mask = idx < jnp.asarray(kv_valid).reshape(-1, 1)
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    if kv_pos is None:
        kv_pos = jnp.broadcast_to(jnp.arange(Skv)[None], (B, Skv))
    kw = dict(scale=scale, attn_softcap=attn_softcap, window=window)

    chunk = max(128, _score_budget() // max(Skv, 1))
    if Sq <= chunk:
        return _attend_chunk(q, k, v, q_pos, kv_pos, kv_mask, **kw)
    n = -(-Sq // chunk)
    pad = n * chunk - Sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)))
    qs = q.reshape(B, n, chunk, H, D).swapaxes(0, 1)
    ps = q_pos.reshape(B, n, chunk).swapaxes(0, 1)
    out = jax.lax.map(
        lambda c: _attend_chunk(c[0], k, v, c[1], kv_pos, kv_mask, **kw),
        (qs, ps))
    out = out.swapaxes(0, 1).reshape(B, n * chunk, H, D)
    return out[:, :Sq]


def apply_attention(cfg, p, x, *, q_pos, kv_pos=None, kv_cache=None,
                    kv_valid=None, window=0, return_kv=False,
                    self_kv_override=None, self_mask=None,
                    use_kernels=False):
    """GQA attention over [kv_cache || self].

    x: (B, Sq, d). kv_cache: optional (k, v) each (B, P, Hkv, D) with
    positions implicit in kv_pos (length P + Sq when cache present,
    else Sq). ``self_mask`` (B, Sq) bool: which of this pass's own
    tokens serve as keys (all of them when None); a masked token still
    gets its output. ``use_kernels`` routes the attend to the Pallas
    flash-style kernel (``kernels.ops.block_attention``) instead of the
    chunked reference path — same GQA mapping, softcap, window, and KV
    validity semantics.
    """
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if kv_pos is None:
        kv_pos = q_pos
    self_kv_pos = kv_pos[:, -x.shape[1]:]
    q = apply_rope(q, q_pos, cfg.rope_theta)
    k = apply_rope(k, self_kv_pos, cfg.rope_theta)
    if self_kv_override is not None:
        # dKV-Cache: frozen (cached) K/V replace the fresh ones for
        # already-decoded positions within the query region.
        mix, gk, gv = self_kv_override
        m = mix[:, :, None, None]
        k = jnp.where(m, gk.astype(k.dtype), k)
        v = jnp.where(m, gv.astype(v.dtype), v)
    new_kv = (k, v)
    kv_mask = None
    if kv_cache is not None:
        ck, cv = kv_cache
        B, Sq_self = x.shape[0], x.shape[1]
        P = ck.shape[1]
        k = jnp.concatenate([ck.astype(k.dtype), k], axis=1)
        v = jnp.concatenate([cv.astype(v.dtype), v], axis=1)
        if kv_valid is not None:
            # Validity applies to the cache region; self region always
            # valid. kv_valid is either a (B,) used-length or a (B, P)
            # bool mask (position-indexed caches, e.g. the dKV baseline).
            if kv_valid.ndim == 2:
                pad = jnp.ones((B, Sq_self), jnp.bool_)
                kv_mask = jnp.concatenate([kv_valid, pad], axis=1)
            else:
                idx = jnp.arange(P + Sq_self)[None, :]
                kv_mask = (idx < kv_valid.reshape(-1, 1)) | (idx >= P)
        if self_mask is not None:
            own = jnp.concatenate([jnp.ones((B, P), jnp.bool_), self_mask],
                                  axis=1)
            kv_mask = own if kv_mask is None else kv_mask & own
    elif self_mask is not None:
        kv_mask = self_mask
    scale = cfg.attn_scale or (1.0 / math.sqrt(cfg.head_dim))
    if use_kernels:
        from repro.kernels import ops as kops
        km = kv_mask if kv_mask is not None \
            else jnp.ones((x.shape[0], k.shape[1]), jnp.bool_)
        out = kops.block_attention(
            q, k, v, q_pos, kv_pos, km, scale=scale,
            softcap=cfg.attn_softcap, window=window).astype(q.dtype)
    else:
        out = attend_ref(q, k, v, scale=scale, attn_softcap=cfg.attn_softcap,
                         window=window, q_pos=q_pos, kv_pos=kv_pos,
                         kv_mask=kv_mask)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return (out, new_kv) if return_kv else out


# ---------------------------------------------------------------- ffn

def apply_ffn(p, x, kind: str):
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    return h @ p["w_down"]
