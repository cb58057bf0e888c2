"""Plain reference of the served decoding method, in ``jax.numpy``,
imported by nothing of the program. The model's pass is the family's
that ``m["family"]`` names (``bench/families/``): its tokens' vectors,
its layer and its head statistics.

Method (``streaming``, Streaming-dLLM): the generation is decoded in
blocks of ``block`` tokens. At a block's start, one pass over
[prefix | query region] gives the block's first logits and the keys and
values of the prefix; each later step runs only the query region
(the block, the next ``window`` masked positions, and a trailing
masked position at the generation's last index when the window stops
short of it) against those prefix keys and values. At each step the
masked block positions whose confidence (the top token's softmax
probability, with [MASK] banned) reaches tau0 * (1 - alpha * (1 -
masked share)) are committed, or else the single most confident one.

``replay`` runs that method over a request's prompt with the tokens the
server returned put in as each position is committed. At a block's
start the replay's state is the server's: the same prompt and the same
earlier blocks. Within a block, which of several near-equally
confident positions a decoder commits first is settled by its
rounding, and the order changes the context of later positions; so
where no position clears the threshold the replay commits, of the open
positions whose served token is its top token, the most confident
(``_follow``): the position the server's tokens say it committed. Each
served token is read at the state where the replay commits it.

A control, the same reference computed in a lower precision (one of
``NUMERICS``), can run beside the replay in the program's place: at
each state it computes its own logits, with its own keys and values,
and the token it puts first at the position committed there is read by
the reference as a served token would be.
"""
from __future__ import annotations

import functools

import numpy as np

from bench import families

# (matmul precision, compute type) by name: the reference at the
# configurations' stated precision, float32 at the default matmul
# precision (on the TPU one bfloat16 pass per matmul, as the program
# computes), and the control a step below it
NUMERICS = {
    "reference": ("default", "float32"),
    "bfloat16": ("default", "bfloat16"),
}


def query_positions(m: dict, prompt_len: int, gen_len: int, b: int):
    K, start = m["block"], prompt_len + b * m["block"]
    end = prompt_len + gen_len
    remaining = end - (start + K)
    w = min(m["window"], remaining) if m["window"] >= 0 else remaining
    pos = list(range(start, start + K + w))
    if w < remaining:
        pos.append(end - 1)
    return np.asarray(pos, np.int32)


@functools.lru_cache(maxsize=None)
def _fns(mkey, numerics):
    """Jitted block-start pass and step for one set of dims, in one of
    ``NUMERICS``. Layers run in a scan; the block-start pass runs one
    row at a time."""
    import jax
    import jax.numpy as jnp
    m = dict(mkey)
    fam = families.load(m["family"])
    K = m["block"]
    precision, dtype_name = NUMERICS[numerics]
    dtype = jnp.dtype(dtype_name)

    def refresh_row(p, toks, pos, valid, boff, probe):
        def body(x, lw):
            x, k, v = fam.layer(m, lw, x, pos[None], None, None,
                                valid[None], pos[None], dtype)
            return x, (k[0], v[0])
        x0 = fam.token_vectors(m, p, toks[None], dtype)
        x, (ks, vs) = jax.lax.scan(body, x0, fam.layers(p))
        blk = jax.lax.dynamic_slice_in_dim(x, boff, K, 1)
        stats = fam.head_stats(m, p, blk, probe[None], dtype)
        return tuple(s[0] for s in stats), ks, vs

    def refresh(p, toks, pos, valid, boff, probe):
        stats, ks, vs = jax.lax.map(
            lambda a: refresh_row(p, *a[:3], boff, a[3]),
            (toks, pos, valid, probe))
        # (R, L, S, Hkv, hd) -> (L, R, S, Hkv, hd)
        return stats, ks.swapaxes(0, 1), vs.swapaxes(0, 1)

    def step(p, ks, vs, key_valid, key_pos, toks, pos, q_valid, probe):
        valid = jnp.concatenate([key_valid, q_valid], 1)
        kpos = jnp.concatenate([key_pos, pos], 1)

        def body(x, a):
            lw, k, v = a
            x, _, _ = fam.layer(m, lw, x, pos, k, v, valid, kpos, dtype)
            return x, None
        x0 = fam.token_vectors(m, p, toks, dtype)
        x, _ = jax.lax.scan(body, x0, (fam.layers(p), ks, vs))
        return fam.head_stats(m, p, x[:, :K], probe, dtype)

    def scoped(fn):
        def call(*a):
            with jax.default_matmul_precision(precision):
                return fn(*a)
        return jax.jit(call)
    return scoped(refresh), scoped(step)


def _select(conf, open_, tau0, alpha):
    """Positions to commit: open ones at or over the threshold, else the
    single most confident open one (per row with any open)."""
    r_mask = open_.mean(1)
    tau = tau0 * (1.0 - alpha * (1.0 - r_mask))
    commit = open_ & (conf >= tau[:, None])
    masked_conf = np.where(open_, conf, -np.inf)
    for r in np.nonzero(~commit.any(1) & open_.any(1))[0]:
        commit[r, int(np.argmax(masked_conf[r]))] = True
    return commit


def _follow(conf, gap, open_, tau0, alpha):
    """Positions to commit when replaying served tokens: those at or
    over the threshold, as the method commits them; else, per row, of
    the open positions whose served token is the top one (``gap`` 0)
    the most confident, or where none is, the open position whose
    served token lies least below the top."""
    r_mask = open_.mean(1)
    tau = tau0 * (1.0 - alpha * (1.0 - r_mask))
    commit = open_ & (conf >= tau[:, None])
    for r in np.nonzero(~commit.any(1) & open_.any(1))[0]:
        top = open_[r] & (gap[r] <= 0.0)
        score = np.where(top, conf[r], -np.inf) if top.any() else \
            np.where(open_[r], -gap[r], -np.inf)
        commit[r, int(np.argmax(score))] = True
    return commit


def replay(m: dict, params, prompts: np.ndarray, served=None,
           gen_len: int = 0, numerics: str = "reference",
           control: str = "") -> dict:
    """Decode ``prompts`` (R, P) by the method, committing the
    ``served`` (R, L) tokens, computed in ``numerics`` (``reference``:
    float32 at the default matmul precision). With ``served`` None it decodes
    ``gen_len`` tokens of its own, committing its top token at each
    position. With ``control`` (a name in ``NUMERICS``) that control
    runs beside it in the program's place.

    Returns, per generated position (R, L): ``gap``, the reference's
    best logit minus its logit of the served token in the state where
    the replay commits that position; ``least_gap``, the least such gap
    over every state the replay passed through while the position was
    masked; ``tokens``, what was committed; with ``control``,
    ``control_gap``, the gap of the token the control puts first there,
    in the same state."""
    import jax.numpy as jnp
    R, P = prompts.shape
    own = served is None
    L = gen_len if own else served.shape[1]
    served = np.zeros((R, L), np.int32) if own else np.array(served,
                                                               np.int32)
    K, mask = m["block"], m["mask_id"]
    T = P + L
    q_max = K + m["window"] + 1
    mkey = tuple(sorted(m.items()))
    refresh, step_fn = _fns(mkey, numerics)
    c_refresh, c_step = _fns(mkey, control) if control else (None, None)
    gap = np.full((R, L), np.nan, np.float32)
    least = np.full((R, L), np.nan, np.float32)
    cgap = np.full((R, L), np.nan, np.float32)
    x = np.full((R, T), mask, np.int32)
    x[:, :P] = prompts
    for b in range(L // K):
        bs = P + b * K
        qpos = query_positions(m, P, L, b)
        sq, n = len(qpos), bs + len(qpos)
        toks = np.full((R, T + 1), mask, np.int32)
        pos = np.zeros((R, T + 1), np.int32)
        toks[:, :bs], toks[:, bs:n] = x[:, :bs], x[:, qpos]
        pos[:, :bs], pos[:, bs:n] = np.arange(bs), qpos
        valid = np.zeros((R, T + 1), bool)
        valid[:, :n] = True
        key_valid = np.zeros((R, T + 1), bool)
        key_valid[:, :bs] = True
        key_pos = jnp.asarray(pos)  # positions of the keys the step reads
        args = (jnp.asarray(toks), key_pos, jnp.asarray(valid),
                jnp.int32(bs))
        mine = jnp.asarray(served[:, b * K:(b + 1) * K, None])
        if control:
            (_, c_arg, _, _), cks, cvs = c_refresh(params, *args, mine)
            probe = jnp.concatenate([mine, c_arg[..., None]], -1)
        else:
            probe = mine
        (conf, arg, top, at), ks, vs = refresh(params, *args, probe)
        open_ = np.ones((R, K), bool)
        seen = np.full((R, K), np.inf, np.float32)
        for step in range(K):
            conf_h, top_h, at_h = (np.asarray(conf), np.asarray(top),
                                   np.asarray(at))
            now = top_h - at_h[..., 0]
            commit = _select(conf_h, open_, m["tau0"], m["alpha"]) if own \
                else _follow(conf_h, now, open_, m["tau0"], m["alpha"])
            if step == K - 1:       # the step cap: fill what is open
                commit = open_.copy()
            rr, kk = np.nonzero(commit)
            if own:
                served[rr, b * K + kk] = np.asarray(arg)[rr, kk]
                now[rr, kk] = 0.0
            seen = np.where(open_, np.fmin(seen, now), seen)
            gap[rr, b * K + kk] = now[rr, kk]
            least[rr, b * K + kk] = seen[rr, kk]
            if control:
                cgap[rr, b * K + kk] = (top_h - at_h[..., 1])[rr, kk]
            x[rr, bs + kk] = served[rr, b * K + kk]
            open_[rr, kk] = False
            if not open_.any():
                break
            qt = np.full((R, q_max), mask, np.int32)
            qp = np.zeros((R, q_max), np.int32)
            qv = np.zeros((R, q_max), bool)
            qt[:, :sq], qp[:, :sq], qv[:, :sq] = x[:, qpos], qpos, True
            sargs = (jnp.asarray(key_valid), key_pos, jnp.asarray(qt),
                     jnp.asarray(qp), jnp.asarray(qv))
            mine = jnp.asarray(served[:, b * K:(b + 1) * K, None])
            if control:
                _, c_arg, _, _ = c_step(params, cks, cvs, *sargs, mine)
                probe = jnp.concatenate([mine, c_arg[..., None]], -1)
            else:
                probe = mine
            conf, arg, top, at = step_fn(params, ks, vs, *sargs, probe)
    out = {"gap": gap, "least_gap": least, "tokens": served}
    if control:
        out["control_gap"] = cgap
    return out
