"""Operations of the served work, counted from shapes: the streaming
method's passes here, each layer's FLOPs and the head's from the model
family (``bench/families/``) that ``model["family"]`` names. ``model``
is the dict returned by ``cells.model_dims``.
"""
from __future__ import annotations

from bench import families


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
    ``(count, sq, skv)``: ``sq`` queries, the last ``sq`` of ``skv``
    positions, over those ``skv`` keys."""
    prefix = prompt_len + block_idx * m["block"]
    sq = query_len(m, prompt_len, gen_len, block_idx)
    return [(1.0, prefix + sq, prefix + sq),
            (max(steps - 1.0, 0.0), sq, prefix + sq)]


def block_flops(m: dict, prompt_len: int, gen_len: int, block_idx: int,
                steps: float) -> float:
    """Model FLOPs of one row for one block of the streaming method:
    every layer of every pass, and the head over the block's rows at
    each of the ``steps`` passes."""
    fam = families.load(m["family"])
    total = 0.0
    for count, sq, skv in block_passes(m, prompt_len, gen_len, block_idx,
                                       steps):
        total += count * m["layers"] * fam.layer_flops(m, sq, skv)
    return total + steps * fam.head_flops(m, m["block"])
