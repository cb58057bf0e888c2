"""Layout-driven transformer backbone.

One model definition serves all 10 assigned architectures: a config's
``pattern × reps + tail`` layout selects per-layer mixers (global/local
attention, mLSTM, sLSTM, RG-LRU) and FFNs (SwiGLU/GELU/MoE/none).
Repeated pattern groups are executed with ``lax.scan`` over stacked
params so the HLO stays compact for the 512-device dry-run compiles.

Three execution modes:
  encode  — full pass over (B, S); optionally emits a KV cache/state
            (the prefill step).
  step    — one diffusion denoise iteration: a query region (current
            block + pruned suffix + trailing token) attends over
            [cache buffer || self]; cache unchanged.
  append  — like step, but commits the query tokens' KV (or recurrent
            state) into the cache (block finalization).

Caches are fixed-size buffers with a ``kv_valid`` (B,) used-length so a
whole generation runs under a single compiled step function.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import recurrent as rec
from repro.models.config import (ATTN, ATTN_LOCAL, GELU, MLSTM, MOE, NONE,
                                 RGLRU, SLSTM, SWIGLU, LayerSpec, ModelConfig)
from repro.models.heads import plan_heads
from repro.models.layers import (_dense_init, apply_attention, apply_ffn,
                                 init_attention, init_ffn, rms_norm, softcap)
from repro.models.moe import apply_moe, init_moe


class ModelOutput(NamedTuple):
    logits: jnp.ndarray
    aux_loss: jnp.ndarray
    cache: Any
    kv_valid: Any


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ------------------------------------------------------------- init

def init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p: dict = {"norm1": jnp.zeros((cfg.d_model,), dtype)}
    if spec.mixer in (ATTN, ATTN_LOCAL):
        plan = plan_heads(cfg.n_heads, cfg.n_kv_heads, cfg.tp)
        p["mixer"] = init_attention(ks[0], cfg, plan, dtype)
    elif spec.mixer == MLSTM:
        p["mixer"] = rec.init_mlstm(ks[0], cfg, dtype)
    elif spec.mixer == SLSTM:
        p["mixer"] = rec.init_slstm(ks[0], cfg, dtype)
    elif spec.mixer == RGLRU:
        p["mixer"] = rec.init_rglru(ks[0], cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != NONE:
        p["norm2"] = jnp.zeros((cfg.d_model,), dtype)
        p["ffn"] = init_moe(ks[1], cfg, dtype) if spec.ffn == MOE \
            else init_ffn(ks[1], cfg, spec.ffn, dtype)
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = _dtype(cfg.param_dtype)
    k_embed, k_head, k_front, k_layers = jax.random.split(key, 4)
    params: dict = {
        "embed": _dense_init(k_embed, (cfg.vocab_size, cfg.d_model),
                             cfg.d_model, dtype),
        "out_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(k_head, (cfg.d_model, cfg.vocab_size),
                                        cfg.d_model, dtype)
    if cfg.frontend_embed_dim:
        params["frontend_proj"] = _dense_init(
            k_front, (cfg.frontend_embed_dim, cfg.d_model),
            cfg.frontend_embed_dim, dtype)

    n_pos = len(cfg.pattern)
    keys = jax.random.split(k_layers, cfg.reps * n_pos + len(cfg.tail))
    scan_params = []
    for i, spec in enumerate(cfg.pattern):
        ks = jnp.stack([keys[r * n_pos + i] for r in range(cfg.reps)])
        scan_params.append(jax.vmap(lambda k: init_layer(k, cfg, spec, dtype))(ks))
    params["scan"] = tuple(scan_params)
    params["tail"] = tuple(
        init_layer(keys[cfg.reps * n_pos + j], cfg, spec, dtype)
        for j, spec in enumerate(cfg.tail))
    return params


# ------------------------------------------------------------- caches

def _layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int,
                 dtype):
    if spec.mixer in (ATTN, ATTN_LOCAL):
        plan = plan_heads(cfg.n_heads, cfg.n_kv_heads, cfg.tp)
        shape = (batch, max_len, plan.pad_kv, cfg.head_dim)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
    if spec.mixer == MLSTM:
        di = 2 * cfg.d_model
        H = cfg.n_heads
        return rec.MLSTMState(
            jnp.zeros((batch, H, di // H // 2, di // H), jnp.float32),
            jnp.zeros((batch, H, di // H // 2), jnp.float32),
            jnp.full((batch, H), -1e30, jnp.float32),
            jnp.zeros((batch, 3, di), dtype))
    if spec.mixer == SLSTM:
        z = jnp.zeros((batch, cfg.d_model), jnp.float32)
        return rec.SLSTMState(z, z, z, jnp.full_like(z, -1e30))
    if spec.mixer == RGLRU:
        w = cfg.lru_width or cfg.d_model
        return rec.RGLRUState(
            jnp.zeros((batch, w), jnp.float32),
            jnp.zeros((batch, cfg.rglru_conv_width - 1, w), dtype))
    raise ValueError(spec.mixer)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               serve_long: bool = False) -> dict:
    """Empty cache pytree matching the model layout (scan-stacked)."""
    dtype = _dtype(cfg.dtype)
    layout = cfg.effective_layout(serve_long)
    pattern = layout[:len(cfg.pattern)]
    tail = layout[cfg.reps * len(cfg.pattern):]
    scan_caches = tuple(
        jax.tree.map(lambda x: jnp.broadcast_to(x[None], (cfg.reps,) + x.shape),
                     _layer_cache(cfg, spec, batch, max_len, dtype))
        for spec in pattern)
    tail_caches = tuple(_layer_cache(cfg, spec, batch, max_len, dtype)
                        for spec in tail)
    return {"scan": scan_caches, "tail": tail_caches}


def cache_take_rows(cache: dict, rows) -> dict:
    """Gather a sub-batch of a cache pytree: batch is axis 1 for the
    scan-stacked pattern groups (leading axis is reps) and axis 0 for
    tail layers. Used by the serving scheduler to compact a batch when
    some rows finish (only the dKV baseline carries KV across block
    boundaries; the other methods rewrite it at the next refresh)."""
    idx = jnp.asarray(rows, jnp.int32)
    return {
        "scan": jax.tree.map(lambda a: jnp.take(a, idx, axis=1),
                             cache["scan"]),
        "tail": jax.tree.map(lambda a: jnp.take(a, idx, axis=0),
                             cache["tail"]),
    }


# ------------------------------------------------------------- layers

def _write_kv(buf, new, kv_valid):
    """buf: (B, P, H, D); new: (B, S, H, D); kv_valid: (B,) offsets."""
    def upd(b, n, off):
        return jax.lax.dynamic_update_slice_in_dim(b, n, off, axis=0)
    return jax.vmap(upd)(buf, new, kv_valid)


def _write_kv_at(buf, new, idx):
    """Scatter new (B, S, H, D) into buf at per-token slots idx (B, S)."""
    def upd(b, n, i):
        return b.at[i].set(n)
    return jax.vmap(upd)(buf, new, idx)


def apply_layer(cfg, p, spec: LayerSpec, x, *, q_pos, cache, kv_valid,
                mode, cache_positions=None, append_at=None,
                self_kv_mix=None, cache_upto=None, self_mask=None,
                mesh=None, data_axes=("data",), use_kernels=False):
    """Returns (y, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    new_cache = cache
    if spec.mixer in (ATTN, ATTN_LOCAL):
        window = cfg.local_window if spec.mixer == ATTN_LOCAL else 0
        if mode == "encode":
            out, kv = apply_attention(cfg, p["mixer"], h, q_pos=q_pos,
                                      window=window, return_kv=True,
                                      self_mask=self_mask,
                                      use_kernels=use_kernels)
            if cache is not None:
                zero = jnp.zeros((x.shape[0],), jnp.int32)
                new_cache = (_write_kv(cache[0], kv[0].astype(cache[0].dtype), zero),
                             _write_kv(cache[1], kv[1].astype(cache[1].dtype), zero))
        else:
            P_len = cache[0].shape[1]
            if cache_positions is None:
                cache_positions = jnp.broadcast_to(
                    jnp.arange(P_len)[None], (x.shape[0], P_len)).astype(jnp.int32)
            kv_pos = jnp.concatenate([cache_positions, q_pos], axis=1)
            override = None
            if self_kv_mix is not None:
                gk = jax.vmap(lambda b, i: b[i])(cache[0], q_pos)
                gv = jax.vmap(lambda b, i: b[i])(cache[1], q_pos)
                override = (self_kv_mix, gk, gv)
            out, kv = apply_attention(cfg, p["mixer"], h, q_pos=q_pos,
                                      kv_pos=kv_pos, kv_cache=cache,
                                      kv_valid=kv_valid, window=window,
                                      return_kv=True,
                                      self_kv_override=override,
                                      self_mask=self_mask,
                                      use_kernels=use_kernels)
            if mode == "append":
                if append_at is not None:
                    new_cache = (_write_kv_at(cache[0], kv[0].astype(cache[0].dtype), append_at),
                                 _write_kv_at(cache[1], kv[1].astype(cache[1].dtype), append_at))
                else:
                    new_cache = (_write_kv(cache[0], kv[0].astype(cache[0].dtype), kv_valid),
                                 _write_kv(cache[1], kv[1].astype(cache[1].dtype), kv_valid))
    else:
        apply_fn = {MLSTM: rec.apply_mlstm, SLSTM: rec.apply_slstm,
                    RGLRU: rec.apply_rglru}[spec.mixer]
        if mode == "encode" and cache is None:
            out = apply_fn(cfg, p["mixer"], h)
        elif mode in ("encode", "append") and cache_upto is not None:
            # Block-refresh: the cached recurrent state must be the state
            # at the prefix boundary, not after the (masked) query region
            # — split the scan there (exactness test: test_models.py::
            # test_cached_step_consistency).
            out1, st = apply_fn(cfg, p["mixer"], h[:, :cache_upto],
                                return_state=True)
            out2, _ = apply_fn(cfg, p["mixer"], h[:, cache_upto:],
                               state=st, return_state=True)
            out = jnp.concatenate([out1, out2], axis=1)
            new_cache = st
        else:
            out, st = apply_fn(cfg, p["mixer"], h, state=cache,
                               return_state=True)
            if mode in ("encode", "append"):
                new_cache = st
    x = x + out
    if cfg.seq_parallel:
        x = _seq_shard(x, mesh, data_axes)
    if spec.ffn != NONE:
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        if spec.ffn == MOE:
            y, aux = apply_moe(cfg, p["ffn"], h2, mesh=mesh, data_axes=data_axes)
        else:
            y = apply_ffn(p["ffn"], h2, spec.ffn)
        x = x + y
        if cfg.seq_parallel:
            x = _seq_shard(x, mesh, data_axes)
    return x, new_cache, aux


def _seq_shard(x, mesh, data_axes):
    """HC2: constrain the residual stream to (batch, S/model, d). GSPMD
    then lowers each TP output psum into reduce-scatter(+all-gather at
    the next matmul), Megatron-LM sequence parallelism — and the
    between-block elementwise ops (norms, residual adds) run sharded."""
    from jax.sharding import PartitionSpec as P
    if mesh is None or "model" not in mesh.axis_names \
            or x.shape[1] % mesh.shape["model"]:
        return x
    dp = tuple(a for a in data_axes if a in mesh.axis_names) or None
    if dp and len(dp) == 1:
        dp = dp[0]
    return jax.lax.with_sharding_constraint(x, P(dp, "model", None))


# ------------------------------------------------------------- forward

def head_weight(cfg: ModelConfig, params):
    """The output head, (d_model, vocab)."""
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def head_logits(cfg: ModelConfig, params, h):
    """Float32 logits of final hidden states: the head projection and
    the config's logit softcap."""
    logits = (h @ head_weight(cfg, params).astype(h.dtype)).astype(
        jnp.float32)
    if cfg.logit_softcap:
        logits = softcap(logits, cfg.logit_softcap)
    return logits


def apply_model(cfg: ModelConfig, params, *, tokens=None, embeds=None,
                prefix_embeds=None,
                positions=None, mode: str = "encode", cache=None,
                kv_valid=None, cache_positions=None, append_at=None,
                self_kv_mix=None, cache_upto=None, self_mask=None,
                serve_long: bool = False,
                mesh=None, data_axes=("data",),
                skip_head: bool = False,
                use_kernels: bool = False) -> ModelOutput:
    """tokens: (B, S) int32 or embeds: (B, S, F|d). positions: (B, S).
    ``use_kernels`` routes attention layers to the Pallas block kernel
    (decode path; the reference path remains the training/autodiff
    route). ``self_mask`` (B, S) bool keeps only the marked tokens of
    this pass as attention keys (attention layers; every token still
    gets its output)."""
    dtype = _dtype(cfg.dtype)
    if tokens is not None:
        x = params["embed"][tokens].astype(dtype)
        B, S = tokens.shape
    else:
        e = embeds.astype(dtype)
        if cfg.frontend_embed_dim and e.shape[-1] == cfg.frontend_embed_dim:
            e = e @ params["frontend_proj"].astype(dtype)
        x = e
        B, S = x.shape[0], x.shape[1]
    if prefix_embeds is not None:
        # Modality-frontend stub (DESIGN.md §6): precomputed patch/frame
        # embeddings projected and prepended to the token embeddings.
        pe = prefix_embeds.astype(dtype)
        if cfg.frontend_embed_dim and pe.shape[-1] == cfg.frontend_embed_dim:
            pe = pe @ params["frontend_proj"].astype(dtype)
        x = jnp.concatenate([pe, x], axis=1)
        S = x.shape[1]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    positions = positions.astype(jnp.int32)
    if kv_valid is None:
        kv_valid = jnp.zeros((B,), jnp.int32)
    kv_valid = jnp.asarray(kv_valid)
    if kv_valid.ndim < 2:
        kv_valid = jnp.broadcast_to(kv_valid.astype(jnp.int32), (B,))

    layout = cfg.effective_layout(serve_long)
    n_pos = len(cfg.pattern)
    pattern = layout[:n_pos]
    tail_specs = layout[cfg.reps * n_pos:]

    scan_caches = cache["scan"] if cache is not None else ()
    have_cache = cache is not None

    def body(carry, xs):
        xc, auxc = carry
        p_i, c_i = xs
        new_cs = []
        for pos, spec in enumerate(pattern):
            def layer_fn(p_l, xc_, cache_, *, _spec=spec):
                return apply_layer(cfg, p_l, _spec, xc_, q_pos=positions,
                                   cache=cache_, kv_valid=kv_valid,
                                   mode=mode,
                                   cache_positions=cache_positions,
                                   append_at=append_at,
                                   self_kv_mix=self_kv_mix,
                                   cache_upto=cache_upto,
                                   self_mask=self_mask, mesh=mesh,
                                   data_axes=data_axes,
                                   use_kernels=use_kernels)
            if cfg.remat:
                layer_fn = jax.checkpoint(layer_fn)
            xc, nc, a = layer_fn(p_i[pos], xc,
                                 c_i[pos] if have_cache else None)
            new_cs.append(nc)
            auxc = auxc + a
        if mode == "step":
            # cache is unchanged in step mode — returning it as scan ys
            # would allocate a full cache copy (EXPERIMENTS.md §Perf #1)
            return (xc, auxc), ()
        return (xc, auxc), tuple(new_cs)

    aux = jnp.zeros((), jnp.float32)
    if cfg.reps > 0:
        if have_cache:
            xs = (params["scan"], scan_caches)
        else:
            dummy = tuple(jnp.zeros((cfg.reps,)) for _ in pattern)
            xs = (params["scan"], dummy)
        (x, aux), new_scan = jax.lax.scan(body, (x, aux), xs,
                                          unroll=min(cfg.scan_unroll,
                                                     cfg.reps))
    else:
        new_scan = ()

    new_tail = []
    for j, spec in enumerate(tail_specs):
        x, nc, a = apply_layer(cfg, params["tail"][j], spec, x,
                               q_pos=positions,
                               cache=cache["tail"][j] if have_cache else None,
                               kv_valid=kv_valid, mode=mode,
                               cache_positions=cache_positions,
                               append_at=append_at,
                               self_kv_mix=self_kv_mix,
                               cache_upto=cache_upto,
                               self_mask=self_mask, mesh=mesh,
                               data_axes=data_axes,
                               use_kernels=use_kernels)
        aux = aux + a
        new_tail.append(nc)

    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    if skip_head:
        logits = x  # final hidden states; caller owns the head projection
    else:
        logits = head_logits(cfg, params, x)

    new_cache = None
    if have_cache and mode != "step":
        new_cache = {"scan": new_scan, "tail": tuple(new_tail)}
    if kv_valid.ndim == 2:  # bool-mask caches are managed by the caller
        new_valid = kv_valid
    else:
        new_valid = kv_valid + (S if mode in ("encode", "append") else 0)
    return ModelOutput(logits, aux, new_cache, new_valid)
