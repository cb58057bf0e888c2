"""Seeded random weights, made by the benchmark and handed to both the
program and the reference, so that the reference takes nothing the
program made. The tree and its law are the model family's ``init``
(``bench/families/``)."""
from __future__ import annotations

import numpy as np

from bench import families


def jax_key(seed: int):
    """A JAX key from any whole number (seeds may exceed 32 bits)."""
    import jax
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def make(m: dict, seed: int, dtype, shardings=None):
    """One jitted call on the device; ``shardings`` (a tree matching
    the family's ``init``) places every leaf where the program wants
    it."""
    import jax
    init = families.load(m["family"]).init
    fn = jax.jit(lambda k: init(m, k, dtype), out_shardings=shardings)
    return fn(jax_key(seed))
