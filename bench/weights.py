"""Seeded random weights, made by the benchmark and handed to both the
program and the reference, so that the reference takes nothing the
program made.

The tree is the one the program's dense GQA + SwiGLU layout takes:
``embed (V, d)``, ``lm_head (d, V)``, ``out_norm (d,)`` and, stacked
over layers, ``norm1``/``norm2 (L, d)``, ``mixer.wq (L, d, H, hd)``,
``wk``/``wv (L, d, Hkv, hd)``, ``wo (L, H, hd, d)``, ``ffn.w_gate``/
``w_up (L, d, f)``, ``w_down (L, f, d)``. RMSNorm weights are stored as
offsets from a gain of 1 (gain = 1 + w). Matrices are N(0, 1/fan_in).
"""
from __future__ import annotations

import numpy as np


def jax_key(seed: int):
    """A JAX key from any whole number (seeds may exceed 32 bits)."""
    import jax
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


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


def make(m: dict, seed: int, dtype, shardings=None):
    """One jitted call on the device; ``shardings`` (a tree matching
    ``init``'s) places every leaf where the program wants it."""
    import jax
    fn = jax.jit(lambda k: init(m, k, dtype), out_shardings=shardings)
    return fn(jax_key(seed))
