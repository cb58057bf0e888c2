"""Model families: everything in the benchmark that depends on a
layer's equations, one module per family, ``bench/families/<name>.py``.
A configuration names its family by its ``reference`` key.

A family module gives:

- ``dims(config)``: the model's sizes from the configuration, in the
  benchmark's vocabulary (``cells.model_dims`` adds the method's);
- ``init(m, key, dtype)``: seeded weights in the tree the program's
  ``SpecBuilder(cfg, mesh, mode="serve").params()`` takes; jittable;
- the plain reference of one pass: ``token_vectors(m, p, toks,
  dtype)``, ``layers(p)`` (the per-layer weights stacked on a leading
  axis), ``layer(m, lw, x, pos, key_k, key_v, key_valid, key_pos,
  dtype)`` returning ``(x, own k, own v)``, and ``head_stats(m, p, x,
  probe, dtype)``;
- counts of one layer for ``sq`` queries, the last ``sq`` of ``skv``
  positions, over those ``skv`` keys (a prefix of ``skv - sq``):
  ``layer_flops`` (the model's FLOPs), ``attention_flops`` and
  ``attention_bytes`` (the attention kernel's); and ``head_flops(m,
  rows)``.
"""
from __future__ import annotations

import functools
import importlib.util
import os

from bench.cells import CellError

FAMILIES_DIR = os.path.dirname(os.path.abspath(__file__))


def load(name: str, families_dir: str = ""):
    """The module ``<families_dir>/<name>.py``; by default the directory
    is ``FAMILIES_DIR``, read at the call, so that a test can point it
    at a family of its own."""
    return _load(name, families_dir or FAMILIES_DIR)


@functools.lru_cache(maxsize=None)
def _load(name: str, families_dir: str):
    path = os.path.join(families_dir, name + ".py")
    if name == "__init__" or not os.path.exists(path):
        known = sorted(f[:-3] for f in os.listdir(families_dir)
                       if f.endswith(".py") and f != "__init__.py")
        raise CellError(f"no model family {name!r} at {path}; known: "
                        f"{known}")
    spec = importlib.util.spec_from_file_location(
        "bench_family_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
