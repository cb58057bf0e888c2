"""Minimal dependency-free checkpointing: pytree -> .npz (+ structure).

Arrays are gathered to host (fine for CPU-scale training; the multi-pod
path would swap in per-shard writes keyed by PartitionSpec — noted in
DESIGN.md, not needed for the dry-run).
"""
from __future__ import annotations

import json
import os
from typing import Any, Tuple

import jax
import numpy as np


def _flatten(tree) -> Tuple[list, list]:
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def save(path: str, tree: Any, metadata: dict | None = None) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    leaves, treedef = _flatten(tree)
    arrays = {f"leaf_{i}": np.asarray(l) for i, l in enumerate(leaves)}
    np.savez(path + ".npz", **arrays)
    with open(path + ".json", "w") as f:
        json.dump({"treedef": str(treedef), "n_leaves": len(leaves),
                   "dtypes": [str(np.asarray(l).dtype) for l in leaves],
                   "metadata": metadata or {}}, f)


def restore(path: str, like: Any) -> Any:
    """Host (numpy) arrays shaped like ``like``, which may hold arrays or
    ``jax.ShapeDtypeStruct``s (``jax.eval_shape`` of an init: no device
    copy is made just to describe the tree)."""
    leaves, treedef = _flatten(like)
    with np.load(path + ".npz") as z:
        loaded = [z[f"leaf_{i}"] for i in range(len(leaves))]
    assert len(loaded) == len(leaves), "checkpoint/model structure mismatch"
    cast = [a.astype(l.dtype) if a.dtype != l.dtype else a
            for a, l in zip(loaded, leaves)]
    for a, l in zip(cast, leaves):
        assert a.shape == l.shape, f"shape mismatch {a.shape} vs {l.shape}"
    return treedef.unflatten(cast)


def load_metadata(path: str) -> dict:
    with open(path + ".json") as f:
        return json.load(f).get("metadata", {})
