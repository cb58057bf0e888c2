"""Masked-diffusion training objective (LLaDA, Nie et al. 2025).

Forward process: sample t ~ U(eps, 1) per example; independently mask
each (loss-eligible) token with probability t. Reverse model predicts
the original token at masked positions under *bidirectional* attention.
Loss = cross-entropy at masked positions, importance-weighted by 1/t —
the ELBO weighting of masked discrete diffusion.

``loss_mask`` restricts masking to the answer region (SFT-style); for
pretraining pass all-True.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.model import apply_model, head_logits


def chunked_ce(cfg: ModelConfig, params, hidden, tokens, weights,
               chunk: int = 512) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Streamed cross-entropy: projects hidden -> logits one sequence
    chunk at a time so the (B, S, V) logits tensor is never materialized
    (essential at vocab 256k x 1M tokens — see EXPERIMENTS.md §Perf).

    Returns (sum of weighted nll, sum of weighted argmax-correct).
    """
    B, S, D = hidden.shape
    n = max(1, -(-S // chunk))
    pad = n * chunk - S
    if pad:  # zero-weight padding contributes nothing to either sum
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
        weights = jnp.pad(weights, ((0, 0), (0, pad)))
        S = S + pad
    hs = hidden.reshape(B, n, S // n, D).swapaxes(0, 1)
    ts = tokens.reshape(B, n, S // n).swapaxes(0, 1)
    ws = weights.reshape(B, n, S // n).swapaxes(0, 1)

    def one(c):
        h, t, w = c
        logits = head_logits(cfg, params, h)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tok_logit = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        nll = ((lse - tok_logit) * w).sum()
        correct = ((jnp.argmax(logits, -1) == t) * w).sum()
        return nll, correct

    nll, correct = jax.lax.map(one, (hs, ts, ws))
    return nll.sum(), correct.sum()


def diffusion_loss(cfg: ModelConfig, params, tokens, loss_mask, rng,
                   *, aux_weight: float = 0.01, mesh=None,
                   data_axes=("data",)) -> Tuple[jnp.ndarray, dict]:
    B, S = tokens.shape
    k_t, k_mask = jax.random.split(rng)
    t = jax.random.uniform(k_t, (B, 1), minval=0.05, maxval=1.0)
    mask = (jax.random.uniform(k_mask, (B, S)) < t) & loss_mask
    # guarantee at least one masked position per row (degenerate rows
    # otherwise contribute no signal)
    none = ~jnp.any(mask, axis=1, keepdims=True)
    first = jnp.argmax(loss_mask, axis=1)
    forced = jax.nn.one_hot(first, S, dtype=jnp.bool_) & loss_mask
    mask = mask | (none & forced)

    x = jnp.where(mask, cfg.mask_token_id, tokens)
    w = mask.astype(jnp.float32) / t                      # 1/t ELBO weight
    n_mask = mask.sum()
    big = cfg.vocab_size * S > 4_000_000                  # stream the CE
    out = apply_model(cfg, params, tokens=x, mode="encode", mesh=mesh,
                      data_axes=data_axes, skip_head=big)
    if big:
        nll, correct = chunked_ce(cfg, params, out.logits, tokens, w)
        ce = nll / jnp.maximum(w.sum(), 1e-6)
        acc = correct / jnp.maximum(w.sum(), 1e-6)
    else:
        logp = jax.nn.log_softmax(out.logits, axis=-1)
        tok_lp = jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
        ce = -(tok_lp * w).sum() / jnp.maximum(w.sum(), 1e-6)
        acc = ((jnp.argmax(out.logits, -1) == tokens) & mask).sum() \
            / jnp.maximum(n_mask, 1)
    loss = ce + aux_weight * out.aux_loss
    return loss, {"ce": ce, "aux": out.aux_loss, "masked_acc": acc,
                  "n_masked": n_mask}
