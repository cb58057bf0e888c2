"""``dense_gqa``: the LLaDA / Dream decoder layer, its weights, its plain
reference in ``jax.numpy`` (imported by nothing of the program) and its
counts.

Model: token embedding; per layer pre-RMSNorm, grouped-query attention
with rotary position embedding (rotate-half, base ``rope_theta``) over
the keys, bidirectional, scale 1/sqrt(head_dim), output projection and
residual; pre-RMSNorm SwiGLU MLP and residual; a final RMSNorm and the
LM head. Query head h reads KV head h // (heads / kv_heads).

Weights, in the tree the program's dense GQA + SwiGLU layout takes:
``embed (V, d)``, ``lm_head (d, V)``, ``out_norm (d,)`` and, stacked
over layers, ``norm1``/``norm2 (L, d)``, ``mixer.wq (L, d, H, hd)``,
``wk``/``wv (L, d, Hkv, hd)``, ``wo (L, H, hd, d)``, ``ffn.w_gate``/
``w_up (L, d, f)``, ``w_down (L, f, d)``. RMSNorm weights are stored as
offsets from a gain of 1 (gain = 1 + w). Matrices are N(0, 1/fan_in).

Counts follow the algorithm, not the implementation: attention is
counted over the keys that are valid (not the cache buffer), and K/V
bytes once per KV head (not once per query head). A rewrite that skips
dead tiles or groups GQA heads keeps the same count, so its share of
the roofline rises.

The building blocks (``rms``, ``rope``, ``attend``, the counts) are for
other families to import.
"""
from __future__ import annotations

import numpy as np

from bench.cells import program_overrides

NEG = -1e30


def dims(config: dict) -> dict:
    o = program_overrides(config)
    return {"d": o["d_model"], "heads": o["n_heads"],
            "kv_heads": o["n_kv_heads"], "head_dim": o["head_dim"],
            "d_ff": o["d_ff"], "vocab": o["vocab_size"],
            "rope_theta": o["rope_theta"], "norm_eps": o["norm_eps"]}


def init(m: dict, key, dtype):
    """Weights for model dims ``m`` (``cells.model_dims``); jittable."""
    import jax
    import jax.numpy as jnp

    d, H, Hkv, hd = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    f, V, L = m["d_ff"], m["vocab"], m["layers"]
    ks = iter(jax.random.split(key, 12))

    def mat(shape, fan_in):
        w = jax.random.normal(next(ks), shape, jnp.float32)
        return (w / np.sqrt(fan_in)).astype(dtype)

    def gain(shape):
        return (0.05 * jax.random.normal(next(ks), shape,
                                         jnp.float32)).astype(dtype)

    layer = {"norm1": gain((L, d)),
             "mixer": {"wq": mat((L, d, H, hd), d),
                       "wk": mat((L, d, Hkv, hd), d),
                       "wv": mat((L, d, Hkv, hd), d),
                       "wo": mat((L, H, hd, d), H * hd)},
             "norm2": gain((L, d)),
             "ffn": {"w_gate": mat((L, d, f), d),
                     "w_up": mat((L, d, f), d),
                     "w_down": mat((L, f, d), f)}}
    return {"embed": mat((V, d), d), "out_norm": gain((d,)),
            "lm_head": mat((d, V), d), "scan": (layer,), "tail": ()}


# ---------------------------------------------------------------- reference

def rms(x, w, eps):
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rope(x, pos, theta):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2
                          / x.shape[-1])
    ang = pos.astype(jnp.float32)[..., None] * inv       # (R, S, half)
    c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1).astype(
        x.dtype)


def attend(q, kk, vv, mask, hd, chunk=512):
    """Softmax attention of queries ``q`` (R, S, Hkv, g, hd) over keys
    (R, Sk, Hkv, hd), in query chunks of ``chunk`` so that long prompts
    fit. ``mask`` is (R, Sk), the same keys for every query, or (R, S,
    Sk), each query's own (a mask by position)."""
    import jax
    import jax.numpy as jnp
    outs = []
    for c in range(0, q.shape[1], chunk):
        s = jnp.einsum("rqhgd,rkhd->rhgqk", q[:, c:c + chunk],
                       kk).astype(jnp.float32) / np.sqrt(hd)
        mk = mask[:, None, None, None, :] if mask.ndim == 2 else \
            mask[:, None, None, c:c + chunk, :]
        s = jnp.where(mk, s, NEG)
        a = jax.nn.softmax(s, -1).astype(q.dtype)
        outs.append(jnp.einsum("rhgqk,rkhd->rqhgd", a, vv))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, 1)


def token_vectors(m, p, toks, dtype):
    return p["embed"][toks].astype(dtype)


def layers(p):
    return p["scan"][0]


def layer(m, lw, x, pos, key_k, key_v, key_valid, key_pos, dtype):
    """One layer (weights ``lw``, cast to ``dtype`` where used) over
    queries ``x`` (R, S, d) at positions ``pos`` (R, S); keys are
    [``key_k``, ``key_v`` (given, may be None) | the queries' own],
    masked by ``key_valid`` (R, S_keys), at positions ``key_pos`` (R,
    S_keys; bidirectional attention reads no key's position). Returns
    (x, own k, own v)."""
    import jax
    import jax.numpy as jnp
    R, S, _ = x.shape
    H, Hkv, hd = m["heads"], m["kv_heads"], m["head_dim"]
    w = jax.tree.map(lambda a: a.astype(dtype), lw)
    mix, f = w["mixer"], w["ffn"]
    h = rms(x, w["norm1"], m["norm_eps"])
    q = rope(jnp.einsum("rsd,dhk->rshk", h, mix["wq"]), pos,
             m["rope_theta"])
    k = rope(jnp.einsum("rsd,dhk->rshk", h, mix["wk"]), pos,
             m["rope_theta"])
    v = jnp.einsum("rsd,dhk->rshk", h, mix["wv"])
    kk = k if key_k is None else jnp.concatenate([key_k, k], 1)
    vv = v if key_v is None else jnp.concatenate([key_v, v], 1)
    o = attend(q.reshape(R, S, Hkv, H // Hkv, hd), kk, vv, key_valid, hd)
    x = x + jnp.einsum("rshk,hkd->rsd", o.reshape(R, S, H, hd), mix["wo"])
    h2 = rms(x, w["norm2"], m["norm_eps"])
    y = jax.nn.silu(jnp.einsum("rsd,df->rsf", h2, f["w_gate"])) \
        * jnp.einsum("rsd,df->rsf", h2, f["w_up"])
    return x + jnp.einsum("rsf,fd->rsd", y, f["w_down"]), k, v


def head_stats(m, p, x, probe, dtype):
    """Block logits (float32, [MASK] banned) reduced per position: the
    confidence, the top token, the top logit and the logits of the
    ``probe`` tokens (R, K, n)."""
    import jax
    import jax.numpy as jnp
    h = rms(x, p["out_norm"].astype(dtype), m["norm_eps"])
    z = jnp.einsum("rkd,dv->rkv", h,
                   p["lm_head"].astype(dtype)).astype(jnp.float32)
    z = z.at[..., m["mask_id"]].set(NEG)
    top = jnp.max(z, -1)
    conf = jnp.exp(top - jax.scipy.special.logsumexp(z, -1))
    arg = jnp.argmax(z, -1).astype(jnp.int32)
    return conf, arg, top, jnp.take_along_axis(z, probe, -1)


# ---------------------------------------------------------------- counts

def dense_layer_flops(m: dict, sq: int) -> float:
    """Projections and MLP of one layer for ``sq`` query tokens."""
    d, hd = m["d"], m["head_dim"]
    qkv = 2.0 * sq * d * hd * (m["heads"] + 2 * m["kv_heads"])
    out = 2.0 * sq * m["heads"] * hd * d
    mlp = 2.0 * sq * 3 * d * m["d_ff"]
    return qkv + out + mlp


def attention_flops(m: dict, sq: int, skv: int) -> float:
    """QK^T and PV of one layer: ``sq`` queries over ``skv`` valid keys."""
    return 4.0 * sq * skv * m["heads"] * m["head_dim"]


def attention_bytes(m: dict, sq: int, skv: int) -> float:
    """q read and output written once per query head, K and V read
    once per KV head, over the valid keys only."""
    hd, b = m["head_dim"], m["dtype_bytes"]
    return b * hd * (2.0 * sq * m["heads"] + 2.0 * skv * m["kv_heads"])


def layer_flops(m: dict, sq: int, skv: int) -> float:
    """Model FLOPs of one layer: ``sq`` queries over ``skv`` keys."""
    return dense_layer_flops(m, sq) + attention_flops(m, sq, skv)


def head_flops(m: dict, rows: int) -> float:
    return 2.0 * rows * m["d"] * m["vocab"]
