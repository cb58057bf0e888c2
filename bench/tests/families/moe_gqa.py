"""``moe_gqa``, a family for the benchmark's tests: the decoder layer
of OLMoE-style models, which differs from ``dense_gqa`` the way a
mixture-of-experts family does. Grouped-query attention as
``dense_gqa``'s, with qk-norm: an RMSNorm over each head's query and
key before the rotary embedding, gains ``mixer.q_norm``/``k_norm (L,
hd)``. The MLP is a routed mixture of SwiGLU experts: a float32 router
``ffn.router (L, d, E)``, a softmax over all ``E`` experts, each
token's ``top_k`` of them, their gates renormalised to sum to 1;
experts ``ffn.w_gate``/``w_up (L, E, d, f)``, ``w_down (L, E, f, d)``.
Embedding, head, RMSNorm, RoPE, attention and the attention counts are
``dense_gqa``'s, imported.
"""
from __future__ import annotations

import numpy as np

from bench.cells import program_overrides
from bench.families.dense_gqa import (attend, attention_bytes,  # noqa: F401
                                      attention_flops, head_flops,
                                      head_stats, layers, rms, rope,
                                      token_vectors)


def dims(config: dict) -> dict:
    o = program_overrides(config)
    return {"d": o["d_model"], "heads": o["n_heads"],
            "kv_heads": o["n_kv_heads"], "head_dim": o["head_dim"],
            "experts": o["n_experts"], "top_k": o["moe_top_k"],
            "expert_ff": o["moe_d_ff"], "vocab": o["vocab_size"],
            "rope_theta": o["rope_theta"], "norm_eps": o["norm_eps"]}


def init(m: dict, key, dtype):
    import jax
    import jax.numpy as jnp

    d, H, Hkv, hd = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    E, f, V, L = m["experts"], m["expert_ff"], m["vocab"], m["layers"]
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in, dt=dtype):
        w = jax.random.normal(next(ks), shape, jnp.float32)
        return (w / np.sqrt(fan_in)).astype(dt)

    def gain(shape):
        return (0.05 * jax.random.normal(next(ks), shape,
                                         jnp.float32)).astype(dtype)

    layer = {"norm1": gain((L, d)),
             "mixer": {"wq": mat((L, d, H, hd), d),
                       "wk": mat((L, d, Hkv, hd), d),
                       "wv": mat((L, d, Hkv, hd), d),
                       "wo": mat((L, H, hd, d), H * hd),
                       "q_norm": gain((L, hd)), "k_norm": gain((L, hd))},
             "norm2": gain((L, d)),
             "ffn": {"router": mat((L, d, E), d, jnp.float32),
                     "w_gate": mat((L, E, d, f), d),
                     "w_up": mat((L, E, d, f), d),
                     "w_down": mat((L, E, f, d), f)}}
    return {"embed": mat((V, d), d), "out_norm": gain((d,)),
            "lm_head": mat((d, V), d), "scan": (layer,), "tail": ()}


def moe(m, f, h):
    """The routed experts over ``h`` (R, S, d); every expert runs on
    every token and each token keeps its ``top_k``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    probs = jax.nn.softmax(jnp.einsum("rsd,de->rse", h.astype(f32),
                                      f["router"].astype(f32)), -1)
    gate, ids = jax.lax.top_k(probs, m["top_k"])
    gate = gate / gate.sum(-1, keepdims=True)
    g = jax.nn.silu(jnp.einsum("rsd,edf->rsef", h, f["w_gate"])) \
        * jnp.einsum("rsd,edf->rsef", h, f["w_up"])
    y = jnp.einsum("rsef,efd->rsed", g, f["w_down"])
    picked = jnp.take_along_axis(y, ids[..., None], 2)     # (R, S, k, d)
    return jnp.einsum("rskd,rsk->rsd", picked.astype(f32),
                      gate).astype(h.dtype)


def layer(m, lw, x, pos, key_k, key_v, key_valid, key_pos, dtype):
    """As ``dense_gqa.layer``, with qk-norm and the routed experts."""
    import jax
    import jax.numpy as jnp
    R, S, _ = x.shape
    H, Hkv, hd, eps = m["heads"], m["kv_heads"], m["head_dim"], m["norm_eps"]
    w = jax.tree.map(lambda a: a.astype(dtype), lw)
    mix = w["mixer"]
    h = rms(x, w["norm1"], eps)
    q = rope(rms(jnp.einsum("rsd,dhk->rshk", h, mix["wq"]), mix["q_norm"],
                 eps), pos, m["rope_theta"])
    k = rope(rms(jnp.einsum("rsd,dhk->rshk", h, mix["wk"]), mix["k_norm"],
                 eps), pos, m["rope_theta"])
    v = jnp.einsum("rsd,dhk->rshk", h, mix["wv"])
    kk = k if key_k is None else jnp.concatenate([key_k, k], 1)
    vv = v if key_v is None else jnp.concatenate([key_v, v], 1)
    o = attend(q.reshape(R, S, Hkv, H // Hkv, hd), kk, vv, key_valid, hd)
    x = x + jnp.einsum("rshk,hkd->rsd", o.reshape(R, S, H, hd), mix["wo"])
    return x + moe(m, w["ffn"], rms(x, w["norm2"], eps)), k, v


def layer_flops(m: dict, sq: int, skv: int) -> float:
    """Projections, the router, the ``top_k`` experts a token is routed
    to, and attention, for ``sq`` queries over ``skv`` keys."""
    d, hd = m["d"], m["head_dim"]
    qkv = 2.0 * sq * d * hd * (m["heads"] + 2 * m["kv_heads"])
    out = 2.0 * sq * m["heads"] * hd * d
    router = 2.0 * sq * d * m["experts"]
    experts = 2.0 * sq * m["top_k"] * 3 * d * m["expert_ff"]
    return qkv + out + router + experts + attention_flops(m, sq, skv)
