"""Production meshes (TPU v5e target).

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16); the pod
axis is pure data parallelism (gradients psum over pod+data; serving
replicates over pod).

Functions, not module constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, data: int = 16,
                         model: int = 16, pods: int = 2):
    """(data, model) = (16, 16) per pod; multi_pod prepends pods=2.
    The data/model overrides exist only for reduced-device CI tests —
    production always uses the defaults."""
    shape = (pods, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (real or fake) devices exist —
    used by sharded smoke tests."""
    return _make_mesh((data, model), ("data", "model"))


def data_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_submeshes(n: int, data: int = 1, model: int = 1, devices=None):
    """Split the device set into ``n`` disjoint (data, model) meshes —
    one per serving engine (``repro.server.EngineRouter``). Contiguous
    device slices so each submesh stays within its natural locality
    domain (a TPU slice; adjacent fake host devices in CI)."""
    import numpy as np
    from jax.sharding import Mesh

    devs = list(devices if devices is not None else jax.devices())
    per = data * model
    if len(devs) < n * per:
        raise ValueError(
            f"need {n} x {data}x{model} = {n * per} devices, "
            f"have {len(devs)}")
    return [Mesh(np.array(devs[i * per:(i + 1) * per]).reshape(data, model),
                 ("data", "model")) for i in range(n)]
