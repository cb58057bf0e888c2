"""Operations and bytes of the served work, counted from shapes.

Dense decoder layer with grouped-query attention and a SwiGLU MLP (the
layer of LLaDA-8B and Dream-7B). ``model`` is the dict returned by
``cells.model_dims``: d, heads, kv_heads, head_dim, d_ff, vocab,
layers, block, window, dtype_bytes.

Counts follow the algorithm, not the implementation: attention is
counted over the keys that are valid (not the cache buffer), and K/V
bytes once per KV head (not once per query head). A rewrite that skips
dead tiles or groups GQA heads keeps the same count, so its share of
the roofline rises.
"""
from __future__ import annotations


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


def head_flops(m: dict, rows: int) -> float:
    return 2.0 * rows * m["d"] * m["vocab"]


def query_len(m: dict, prompt_len: int, gen_len: int, block_idx: int) -> int:
    """Length of the streaming method's query region for a block: the
    block, the suffix window kept by pruning, and the trailing position
    when the window does not reach the end."""
    K = m["block"]
    remaining = gen_len - (block_idx + 1) * K
    w = min(m["window"], remaining) if m["window"] >= 0 else remaining
    return K + w + (1 if w < remaining else 0)


def block_passes(m: dict, prompt_len: int, gen_len: int, block_idx: int,
                 steps: float):
    """The passes one row makes for one block: the block-start refresh
    over [prefix | query region] and ``steps - 1`` denoise steps of the
    query region over the cached prefix. Returns a list of
    ``(count, sq, skv)``."""
    prefix = prompt_len + block_idx * m["block"]
    sq = query_len(m, prompt_len, gen_len, block_idx)
    return [(1.0, prefix + sq, prefix + sq),
            (max(steps - 1.0, 0.0), sq, prefix + sq)]


def block_flops(m: dict, prompt_len: int, gen_len: int, block_idx: int,
                steps: float) -> float:
    """Model FLOPs of one row for one block of the streaming method:
    every layer of every pass, and the head over the block's rows at
    each of the ``steps`` passes."""
    total = 0.0
    for count, sq, skv in block_passes(m, prompt_len, gen_len, block_idx,
                                       steps):
        per_layer = dense_layer_flops(m, sq) + attention_flops(m, sq, skv)
        total += count * m["layers"] * per_layer
    return total + steps * head_flops(m, m["block"])
