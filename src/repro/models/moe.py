"""Mixture-of-Experts layer: top-k routing with two execution paths.

``dense``  — every expert computed for every token, combined with top-k
             gates. Exact; used for smoke tests and as the oracle in the
             EP-equivalence tests.
``ep``     — expert-parallel: experts sharded over the ``data`` mesh axis,
             per-expert hidden dim over ``model``. Tokens are dispatched
             with a fixed-capacity all_to_all (shard_map), grouped-matmul'd
             on the owning shard (sort-based packing, no one-hot dispatch
             einsum — keeps the roofline honest), and combined with a
             second all_to_all. Capacity overflow drops tokens (counted).

Suffix pruning (the paper's spatial component) directly shrinks the
token count entering this dispatch during decode — the all-to-all bytes
scale with the query region size, which is one of the roofline terms we
track per MoE arch.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import _dense_init


def init_moe(key, cfg, dtype) -> dict:
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d, E), d, jnp.float32),
        "w_gate": _dense_init(ks[1], (E, d, f), d, dtype),
        "w_up": _dense_init(ks[2], (E, d, f), d, dtype),
        "w_down": _dense_init(ks[3], (E, f, d), f, dtype),
    }


def _route(cfg, p, x2d):
    """x2d: (T, d) -> (probs (T,E) f32, topk weights (T,k), topk ids (T,k))."""
    logits = x2d.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.moe_top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return probs, w, ids


def _balance_stats(cfg, probs, ids):
    """Per-token routing statistics: (f_e assignment fractions,
    P_e mean router probs), each (E,). Linear in tokens, so they can be
    averaged across shards/chunks and recombined exactly."""
    E = cfg.n_experts
    f = jnp.mean(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=(0, 1))
    pm = jnp.mean(probs, axis=0)
    return f, pm


def load_balance_loss(cfg, probs, ids) -> jnp.ndarray:
    """Switch-style aux loss: E * sum_e f_e * P_e."""
    f, pm = _balance_stats(cfg, probs, ids)
    return cfg.n_experts * jnp.sum(f * pm)


def _expert_ffn(xe, wg, wu, wd):
    """xe: (E, C, d); weights (E, d, f)/(E, f, d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
    h = h * jnp.einsum("ecd,edf->ecf", xe, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


# ------------------------------------------------------------- dense path

def apply_moe_dense(cfg, p, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, S, d = x.shape
    x2 = x.reshape(-1, d)
    probs, w, ids = _route(cfg, p, x2)
    # all-experts compute: (E, T, d)
    xe = jnp.broadcast_to(x2[None], (cfg.n_experts,) + x2.shape)
    ye = _expert_ffn(xe, p["w_gate"], p["w_up"], p["w_down"])   # (E, T, d)
    onehot = jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32)  # (T,k,E)
    comb = jnp.einsum("tke,tk->te", onehot, w)                      # (T,E)
    y = jnp.einsum("etd,te->td", ye.astype(jnp.float32), comb)
    aux = load_balance_loss(cfg, probs, ids)
    return y.reshape(B, S, d).astype(x.dtype), aux


# ------------------------------------------------------------- ranks

def _rank_within(keys: jnp.ndarray, n_groups: int) -> jnp.ndarray:
    """For int keys (A,), rank of each element among equal keys (stable)."""
    A = keys.shape[0]
    order = jnp.argsort(keys, stable=True)
    sorted_keys = keys[order]
    starts = jnp.searchsorted(sorted_keys, jnp.arange(n_groups), side="left")
    rank_sorted = jnp.arange(A) - starts[sorted_keys]
    return jnp.zeros((A,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))


# ------------------------------------------------------------- EP path

def _moe_local(cfg, p, x, n_shards, axis, model_axis, n_model: int = 1):
    """Runs per-shard inside shard_map. x: (T_loc, d) local tokens.

    Router is replicated (d, E). Returns (y (T_loc, d), f_e, p_e,
    dropped count).

    Two dispatch layouts (EXPERIMENTS.md §Perf HC3):
      1D (default): full-d activations dispatched over ``data``; expert
         weights (E_loc, d, f_loc) with f over ``model``. The model-axis
         psum runs AFTER the return all_to_all and combine, on the
         (T_loc, d) token outputs rather than the (E_loc, Ce, d) expert
         buffers — linear ops commute, ~12x smaller psum.
      2D (cfg.moe_2d_dispatch): every model shard dispatches only its
         d/n_model activation slice (the 1D layout sends identical
         full-d copies down every model column); expert weights
         (E_loc, d_loc, f) with d over ``model``; one f-sized psum
         before the nonlinearity; w_down emits exact d/n_model slices
         that return via all_to_all and all_gather. a2a bytes / device
         drop by n_model.
    """
    T, d = x.shape
    k = cfg.moe_top_k
    E = cfg.n_experts
    E_loc = p["w_gate"].shape[0]
    probs, w, ids = _route(cfg, p, x)

    # -------- dispatch
    A = T * k
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)         # (A,)
    eid = ids.reshape(A).astype(jnp.int32)
    wgt = w.reshape(A)
    dest = eid // E_loc                                          # owning shard
    C = max(1, int(math.ceil(A / n_shards * cfg.moe_capacity_factor)))
    rank = _rank_within(dest, n_shards)
    slot = dest * C + rank
    valid = rank < C
    slot = jnp.where(valid, slot, n_shards * C)                 # drop slot
    two_d = cfg.moe_2d_dispatch and n_model > 1
    if two_d:
        d_loc = d // n_model
        j = jax.lax.axis_index(model_axis)
        x_send = jax.lax.dynamic_slice_in_dim(x, j * d_loc, d_loc, axis=1)
    else:
        d_loc = d
        x_send = x
    buf = jnp.zeros((n_shards * C + 1, d_loc), x.dtype).at[slot].set(
        x_send[tok])
    ebuf = jnp.full((n_shards * C + 1,), E_loc, jnp.int32).at[slot].set(eid % E_loc)
    vbuf = jnp.zeros((n_shards * C + 1,), jnp.bool_).at[slot].set(valid)
    sent = buf[:-1].reshape(n_shards, C, d_loc)
    sent_e = ebuf[:-1].reshape(n_shards, C)
    sent_v = vbuf[:-1].reshape(n_shards, C)

    recv = jax.lax.all_to_all(sent, axis, 0, 0, tiled=True)      # (G, C, dl)
    recv_e = jax.lax.all_to_all(sent_e, axis, 0, 0, tiled=True)
    recv_v = jax.lax.all_to_all(sent_v, axis, 0, 0, tiled=True)

    # -------- grouped expert compute (sort-based packing)
    R = n_shards * C
    rx = recv.reshape(R, d_loc)
    re = jnp.where(recv_v.reshape(R), recv_e.reshape(R), E_loc)  # invalid -> E_loc
    Ce = max(1, int(math.ceil(R / E_loc * cfg.moe_capacity_factor)))
    rrank = _rank_within(re, E_loc + 1)
    pos = re * Ce + rrank
    ok = (re < E_loc) & (rrank < Ce)
    pos = jnp.where(ok, pos, E_loc * Ce)
    xe = jnp.zeros((E_loc * Ce + 1, d_loc), x.dtype).at[pos].set(rx)
    xe = xe[:-1].reshape(E_loc, Ce, d_loc)
    if two_d:
        # weights are (E_loc, d_loc, f): partial contraction over the
        # local d slice, one f-sized psum before the nonlinearity
        hg = jax.lax.psum(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]),
                          model_axis)
        hu = jax.lax.psum(jnp.einsum("ecd,edf->ecf", xe, p["w_up"]),
                          model_axis)
        h = jax.nn.silu(hg) * hu
        # w_down (E_loc, f, d_loc): exact local d slice, no psum
        ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    else:
        ye = _expert_ffn(xe, p["w_gate"], p["w_up"], p["w_down"])
        # NOTE: partial over model (f_loc contraction). The psum runs
        # after the return a2a + combine (linear ops commute) on the
        # (T, d) outputs — ~12x less psum traffic than on (E, Ce, d)
        # expert buffers (§Perf HC3a).
    yflat = jnp.concatenate(
        [ye.reshape(E_loc * Ce, d_loc), jnp.zeros((1, d_loc), ye.dtype)],
        axis=0)
    back = jnp.where(ok[:, None], yflat[pos], 0.0).reshape(n_shards, C, d_loc)

    ret = jax.lax.all_to_all(back, axis, 0, 0, tiled=True)       # (G, C, dl)
    rflat = jnp.concatenate(
        [ret.reshape(n_shards * C, d_loc), jnp.zeros((1, d_loc), ret.dtype)],
        axis=0)
    contrib = rflat[slot] * wgt[:, None].astype(ret.dtype)       # (A, dl)
    y = jnp.zeros((T, d_loc), jnp.float32).at[tok].add(
        jnp.where(valid[:, None], contrib, 0.0).astype(jnp.float32))
    if two_d:
        y = jax.lax.all_gather(y, model_axis, axis=1, tiled=True)  # (T, d)
    else:
        y = jax.lax.psum(y, model_axis)                          # HC3a

    f_e, p_e = _balance_stats(cfg, probs, ids)
    dropped = jax.lax.psum(jnp.sum(~valid) + jnp.sum(recv_v.reshape(R) & ~ok),
                           axis)
    return y.astype(x.dtype), f_e, p_e, dropped


def apply_moe_ep(cfg, p, x, mesh, *, data_axes=("data",), model_axis="model"):
    """x: (B, S, d) global array, batch sharded over data_axes. Experts
    shard over the innermost data axis. Dispatch runs in token chunks
    (``moe_dispatch_chunk``) so the a2a buffers stay bounded at large
    global batch (1M tokens x top-8 x d=7168 would otherwise need
    ~9 GB/device of dispatch buffers — see EXPERIMENTS.md §Perf)."""
    B, S, d = x.shape
    axis = data_axes[-1]
    n_shards = mesh.shape[axis]
    n_model = mesh.shape.get(model_axis, 1)
    two_d = cfg.moe_2d_dispatch and n_model > 1 and d % n_model == 0
    batch_spec = P(data_axes if len(data_axes) > 1 else data_axes[0], None, None)
    if two_d:
        wspec = P(axis, model_axis, None)
        dspec = P(axis, None, model_axis)
    else:
        wspec = P(axis, None, model_axis)
        dspec = P(axis, model_axis, None)
    pspec = {"router": P(None, None), "w_gate": wspec, "w_up": wspec,
             "w_down": dspec}

    def local(x_l, p_l):
        import os
        T = x_l.shape[0] * x_l.shape[1]
        x2 = x_l.reshape(T, d)
        nm = n_model if two_d else 1
        chunk = cfg.moe_dispatch_chunk
        if os.environ.get("REPRO_DISABLE_CHUNKING") == "1":
            chunk = 0  # exact-flops dry-runs (see layers._score_budget)
        if chunk and T > chunk and T % chunk == 0:
            def f(xc):
                return _moe_local(cfg, p_l, xc, n_shards, axis, model_axis,
                                  n_model=nm)
            ys, fs, ps, drops = jax.lax.map(f, x2.reshape(T // chunk, chunk, d))
            y, f_e, p_e, drop = (ys.reshape(T, d), fs.mean(0), ps.mean(0),
                                 drops.sum())
        else:
            y, f_e, p_e, drop = _moe_local(cfg, p_l, x2, n_shards, axis,
                                           model_axis, n_model=nm)
        # exact global aux: average the linear statistics across shards
        # FIRST, then combine (equals the dense single-host value)
        f_e = jax.lax.pmean(f_e, data_axes)
        p_e = jax.lax.pmean(p_e, data_axes)
        aux = cfg.n_experts * jnp.sum(f_e * p_e)
        return y.reshape(x_l.shape), aux, drop

    y, aux, drop = jax.shard_map(
        local, mesh=mesh,
        in_specs=(batch_spec, pspec),
        out_specs=(batch_spec, P(), P()),
        check_vma=False,
    )(x, p)
    return y, aux


def apply_moe_ep_replicated(cfg, p, x, mesh, *, ep_axis="data",
                            model_axis="model"):
    """Replicated-token expert parallelism for tiny query regions
    (long_500k decode, batch=1): every shard computes its local experts
    for ALL tokens, gates zero out non-chosen experts, and a psum over
    (data, model) combines. No all-to-all; overhead E_local/top_k on a
    tiny T — the right trade at batch 1 (DESIGN.md §5)."""
    B, S, d = x.shape
    wspec = P(ep_axis, None, model_axis)
    pspec = {"router": P(None, None), "w_gate": wspec, "w_up": wspec,
             "w_down": P(ep_axis, model_axis, None)}

    def local(x_l, p_l):
        T = B * S
        x2 = x_l.reshape(T, d)
        probs, w, ids = _route(cfg, p_l, x2)
        E_loc = p_l["w_gate"].shape[0]
        off = jax.lax.axis_index(ep_axis) * E_loc
        onehot = jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32)
        comb = jnp.einsum("tke,tk->te", onehot, w)            # (T, E)
        comb_loc = jax.lax.dynamic_slice_in_dim(comb, off, E_loc, axis=1)
        xe = jnp.broadcast_to(x2[None], (E_loc,) + x2.shape)
        ye = _expert_ffn(xe, p_l["w_gate"], p_l["w_up"], p_l["w_down"])
        y = jnp.einsum("etd,te->td", ye.astype(jnp.float32), comb_loc)
        y = jax.lax.psum(y, (ep_axis, model_axis))
        aux = load_balance_loss(cfg, probs, ids)
        return y.reshape(x_l.shape).astype(x_l.dtype), aux

    y, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, None), pspec),
        out_specs=(P(None, None, None), P()),
        check_vma=False,
    )(x, p)
    return y, aux


def apply_moe(cfg, p, x, mesh=None, data_axes=("data",)):
    if mesh is not None and cfg.moe_impl in ("ep", "auto"):
        if not data_axes:
            if "data" in mesh.axis_names and mesh.shape["data"] > 1:
                return apply_moe_ep_replicated(cfg, p, x, mesh)
        elif mesh.shape.get(data_axes[-1], 1) > 1:
            return apply_moe_ep(cfg, p, x, mesh, data_axes=data_axes)
    return apply_moe_dense(cfg, p, x)
