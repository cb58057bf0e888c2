"""The whole run at a tiny size on the CPU, past the harness's look for
a chip: a sound run comes out correct, and a run whose timed path is
broken underneath comes out not correct, once for each fault a served
cell can have. Also the precision control at that size."""
import json
import os
import sys
import time

import numpy as np
import pytest

from bench import cells, correct, harness, reference, weights

SEED = 2**33 + 7


def _tiny_cell(loop="closed"):
    path = os.path.join(cells.ROOT, "bench", "configs", "llada8b-6l.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update({"d_model": 128, "n_heads": 4, "n_kv_heads": 2,
                "head_dim": 32, "mlp_hidden_size": 256, "vocab_size": 512,
                "n_layers": 2, "served_mask_token_id": 511,
                "served_eos_token_id": 510,
                "block_size": 8})
    cfg["engine"] = dict(cfg["engine"], window=8, max_slots=2,
                         use_kernels=False)
    mix = {"loop": loop, "clients": 2, "rate_per_s": 6, "prompt_bytes": 16,
           "max_tokens": 16}
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    return cells.Cell(name="tiny", chips=1, config=cfg, traffic=mix,
                      end_to_end=b["end_to_end"], per_layer=b["per_layer"])


def _run(cell, break_program=None):
    import jax
    return harness.run(cell, SEED, 2.0, False, time.perf_counter(),
                       jax.devices(), log=sys.stderr,
                       break_program=break_program, compile_cache=False)


def _wrap_blocks(engine, after):
    """Run ``after(state, bstart)`` on every block the decoder returns."""
    dec = engine.scheduler.decoder_for(engine.dcfg.gen_len)
    inner = dec.decode_block

    def decode_block(state):
        bstart = state.prompt_len + state.block_idx * engine.dcfg.block_size
        out = inner(state)
        after(out, bstart)
        return out
    dec.decode_block = decode_block


def alter_token(engine):
    """A token altered where it is produced: row 0's first token of each
    block, moved to the next id."""
    def after(state, bstart):
        state.x[0, bstart] = (state.x[0, bstart] + 1) % 256
    _wrap_blocks(engine, after)


def drop_half_the_batch(engine):
    """Half of the batch left out: the answers of every other request
    (odd uid) are never computed and go out as token 0."""
    sched, K = engine.scheduler, engine.dcfg.block_size
    inner = sched._harvest

    def harvest(gang, *args, **kw):
        st = gang.state
        bs = st.prompt_len + (st.block_idx - 1) * K
        for i, req in enumerate(gang.requests):
            if req is not None and req.uid % 2:
                st.x[i, bs:bs + K] = 0
        return inner(gang, *args, **kw)
    sched._harvest = harvest


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_sound_run_is_correct(loop):
    res = _run(_tiny_cell(loop))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["checks"]) >= {"mean_gap", "not_top_pct"}
    assert {c["value"] for c in res["checks"].values()} == {0}
    assert set(res["metrics"]) >= {"first_block_p95_ms", "block_gap_p95_ms",
                                   "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [alter_token, drop_half_the_batch])
def test_broken_timed_path_is_not_correct(fault):
    res = _run(_tiny_cell(), break_program=fault)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values()
               if c["limit"] is not None)


@pytest.mark.parametrize("control", ["bfloat16"])
def test_lower_precision_control_reads_above_float32(control):
    """The reference in a lower precision in the program's place: at the
    states of a replay of the reference's own tokens, the tokens the
    control puts first were not always the reference's top, where the
    reference's own tokens always were."""
    import jax.numpy as jnp
    # a wider vocabulary and more positions make near ties, which the
    # lower precision flips, about as likely as at the published widths
    m = dict(cells.model_dims(_tiny_cell().config), vocab=16384,
             mask_id=16383, eos_id=16382)
    params = weights.make(m, SEED, jnp.float32)
    prompts = np.random.default_rng(0).integers(32, 127, (8, 16)).astype(
        np.int32)
    own = reference.replay(m, params, prompts, gen_len=64)
    assert np.nanmax(own["least_gap"]) == 0.0
    out = reference.replay(m, params, prompts, own["tokens"],
                           control=control)
    assert set(correct.readings(out["least_gap"]).values()) == {0.0}
    got = correct.readings(out["control_gap"])
    assert min(got.values()) > 0.0


def test_replay_follows_the_served_commit_order():
    """Where no position clears the threshold, the replay commits the
    most confident open position whose served token is its top, and
    where none is, the one whose served token lies least below it."""
    conf = np.array([[0.5, 0.9, 0.7, 0.1]], np.float32)
    gap = np.array([[0.0, 0.3, 0.0, 0.0]], np.float32)
    open_ = np.array([[True, True, True, False]])
    got = reference._follow(conf, gap, open_, 0.99, 0.0)
    assert got.tolist() == [[False, False, True, False]]
    gap = np.array([[0.2, 0.3, 0.1, 0.0]], np.float32)
    got = reference._follow(conf, gap, open_, 0.99, 0.0)
    assert got.tolist() == [[False, False, True, False]]
    # at or over the threshold, the method's own rule
    got = reference._follow(conf, gap, open_, 0.6, 0.0)
    assert got.tolist() == [[False, True, True, False]]
