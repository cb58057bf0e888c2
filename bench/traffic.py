"""The one traffic generator. A mix is a data file,
``bench/traffic/<mix>.json``:

- ``loop``: ``closed`` (``clients`` clients, each sending its next
  request when the previous one has finished) or ``open`` (arrivals at
  ``rate_per_s`` whether or not earlier requests have finished);
- ``prompt_bytes``: prompt length; printable ASCII, one token a byte;
- ``max_tokens``: tokens asked for per request.

Every request of a mix has one prompt length and one ``max_tokens``:
each exact prompt length is a shape bucket of its own in the program,
compiled during set-up. The open loop's gaps are the same set for
every seed (exponential quantiles at ``rate_per_s``), in an order the
seed draws, so every seed offers the same work with different bursts.
"""
from __future__ import annotations

import math

import numpy as np


def prompt(seed: int, index: int, length: int) -> str:
    rng = np.random.default_rng([seed, index])
    return "".join(map(chr, rng.integers(32, 127, length)))


def open_arrivals(rate_per_s: float, seconds: float, seed: int):
    """Arrival offsets (s) of the requests due within ``seconds``."""
    n = max(1, math.ceil(rate_per_s * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = np.random.default_rng(seed).permutation(-np.log1p(-q))
    # n gaps that fill the window exactly: the rate is n / seconds
    gaps *= seconds / gaps.sum()
    return [float(t) for t in np.concatenate([[0.0], np.cumsum(gaps)[:-1]])]


def validate(mix: dict) -> None:
    need = {"closed": {"clients"}, "open": {"rate_per_s"}}
    if mix.get("loop") not in need:
        raise ValueError(f"traffic loop must be closed or open: {mix}")
    missing = (need[mix["loop"]] | {"prompt_bytes", "max_tokens"}) - set(mix)
    if missing:
        raise ValueError(f"traffic mix lacks {sorted(missing)}: {mix}")
