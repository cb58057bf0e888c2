"""DecodeExecutor — the placement layer between the decode path and a
device mesh.

Everything above this module (``DiffusionDecoder``, ``BlockScheduler``,
``PrefixKVPool``, the engines) manipulates *row indices and host
arrays*; everything below it (the jitted per-block fused decode
functions, the Pallas kernels) sees *placed device arrays*. The
executor owns the boundary:

* **param placement** — one-time ``jax.device_put`` of the weight
  pytree under ``NamedSharding`` built from the existing
  ``launch/sharding.SpecBuilder`` serve-mode specs (model axis = tensor
  parallel; attention heads / d_ff / experts / vocab shard there).
* **cache placement** — KV buffers are created *on device, already
  sharded* via a jitted ``init_cache`` with ``out_shardings`` from
  ``SpecBuilder.cache`` (batch over the data axis, heads over model).
  A host-side ``init_cache`` + transfer would materialize the whole
  buffer twice.
* **gang submit** — per-block host arrays (tokens, commit masks,
  query positions) are uploaded batch-sharded over the data axis when
  the gang batch divides its extent, and *replicated* when it does
  not (the documented fallback — sharding must never silently pad a
  batch; the scheduler's gang-size rounding makes the fallback rare).
  Harvest needs no executor involvement: every shard is addressable
  in this process, so the decoder's one-per-block ``np.array`` fetch
  already gathers sharded outputs.
* **donation** — the fused per-block fn rewrites the whole KV cache
  (every method but vanilla), so its input cache buffer is dead the
  moment the call is issued. When the backend supports buffer
  donation (TPU/GPU; XLA:CPU only warns and copies) the executor
  tells the decoder to donate it, halving peak KV memory per gang.

``executor=None`` everywhere above this layer means exactly the
pre-executor single-device behavior: ``jnp.asarray`` uploads and a
host-side ``init_cache`` on the default device.

The placement *key* (sorted device ids) tags pool buffers so a
``PrefixKVPool`` can never hand a buffer placed on one mesh to a
decoder driving another — see ``PrefixKVPool``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import data_axes_of
from repro.launch.sharding import SpecBuilder
from repro.models.config import ModelConfig
from repro.models import model as model_lib


class DecodeExecutor:
    """Owns one mesh: placed params, sharded cache creation, and the
    host<->device transfer policy for gang-shaped arrays."""

    def __init__(self, cfg: ModelConfig, params, mesh, *,
                 donate_cache: Optional[bool] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.data_axes: Tuple[str, ...] = data_axes_of(mesh)
        self.data_extent = 1
        for a in self.data_axes:
            self.data_extent *= mesh.shape[a]
        # XLA:CPU accepts donation annotations but ignores them with a
        # warning per call — default it off there, on everywhere else
        self.donate_cache = (jax.default_backend() != "cpu"
                             if donate_cache is None else donate_cache)
        self._sb = SpecBuilder(cfg, mesh, mode="serve")
        self._dp = (self.data_axes if len(self.data_axes) > 1
                    else (self.data_axes[0] if self.data_axes else None))
        # a no-op for params already placed this way (random_init)
        self.params = jax.device_put(params, self._shardings(
            self._sb.params()))
        self._cache_fns: Dict[Tuple[int, int], Any] = {}

    @classmethod
    def random_init(cls, cfg: ModelConfig, mesh, seed: int = 0,
                    **kw) -> "DecodeExecutor":
        """Executor over seeded random weights created *on its mesh*:
        one jitted ``init_params`` with the placement as
        ``out_shardings``, so no other device ever holds a copy. Same
        seed, same weights on every mesh."""
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 SpecBuilder(cfg, mesh, mode="serve").params(),
                                 is_leaf=lambda x: isinstance(x, P))

        def init_params(key):
            return model_lib.init_params(cfg, key)
        params = jax.jit(init_params,
                         out_shardings=shardings)(jax.random.PRNGKey(seed))
        return cls(cfg, params, mesh, **kw)

    # ------------------------------------------------------ identity

    @property
    def placement(self) -> tuple:
        """Hashable placement key: which devices this mesh spans. Pool
        buffers are bucketed by it so meshes never share buffers."""
        return tuple(sorted(d.id for d in self.mesh.devices.flat))

    @property
    def shape_key(self) -> tuple:
        """Hashable mesh-*shape* key. KV numerics depend on the mesh
        shape (sharded-matmul reduction order, head padding), not on
        which device ids back it — so a *shared* ``PrefixKVCache``
        (disaggregated pools, host-staged numpy chunks) is keyed by
        this: any executor with the same axis extents produces and
        consumes byte-identical chunk KV."""
        return ("shape",) + tuple(sorted(self.mesh.shape.items()))

    def __repr__(self):
        return (f"DecodeExecutor(mesh={dict(self.mesh.shape)}, "
                f"devices={self.placement})")

    # ------------------------------------------------------ placement

    def _shardings(self, spec_tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            spec_tree, is_leaf=lambda x: isinstance(x, P))

    def batch_sharding(self, ndim: int, batch: int) -> NamedSharding:
        """Data-axis sharding over dim 0 when the batch divides the
        data extent; replicated otherwise (never silent padding)."""
        if self.data_extent > 1 and batch % self.data_extent == 0:
            spec = P(self._dp, *([None] * (ndim - 1)))
        else:
            spec = P(*([None] * ndim))
        return NamedSharding(self.mesh, spec)

    def put_batch(self, arr) -> jnp.ndarray:
        """Upload one gang-shaped host array (dim 0 = batch)."""
        arr = np.asarray(arr)
        return jax.device_put(arr, self.batch_sharding(arr.ndim,
                                                       arr.shape[0]))

    def init_cache(self, batch: int, total_len: int):
        """Device-resident sharded cache creation: jitted zeros with
        ``out_shardings`` from the SpecBuilder cache specs, compiled
        once per (batch, total_len) bucket."""
        key = (batch, total_len)
        fn = self._cache_fns.get(key)
        if fn is None:
            shardings = self._shardings(self._sb.cache(batch, total_len))

            def init_cache():
                return model_lib.init_cache(self.cfg, batch, total_len)
            fn = jax.jit(init_cache, out_shardings=shardings)
            self._cache_fns[key] = fn
        return fn()

    def constrain_cache(self, cache, batch: int, total_len: int):
        """Pin a cache pytree to the canonical SpecBuilder sharding from
        inside a jitted computation. The decode fns apply this to their
        cache *outputs* so a recycled pool buffer carries exactly the
        sharding a fresh ``init_cache`` buffer does — otherwise the jit
        cache sees two sharding-distinct variants of every (batch,
        block) shape and the second one compiles at serve time, after
        pre-warm declared the engine warm."""
        if cache is None or not jax.tree.leaves(cache):
            return cache
        shardings = self._shardings(self._sb.cache(batch, total_len))
        return jax.tree.map(jax.lax.with_sharding_constraint,
                            cache, shardings)

    def jit_cache_size(self) -> int:
        """Compiled cache-creation variants — counted alongside the
        decoder's jit caches by the CompileWatch ledger, so a pool
        acquire at a never-seen (batch, total_len) shows up as the
        compile it is."""
        return sum(fn._cache_size() for fn in self._cache_fns.values())
