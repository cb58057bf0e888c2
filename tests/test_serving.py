"""Continuous-batching serving subsystem tests: resumable decode_block
equivalence, scheduler backfill on early exit, prefix-KV pool
reuse/eviction, streaming order, preemption, admission control, and
token-identity between the continuous and synchronous engines."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.decoder import METHODS, DecodeConfig, DiffusionDecoder
from repro.core.engine import ServingEngine
from repro.data.tokenizer import ByteTokenizer
from repro.models import get_config, init_params
from repro.serving import (BlockScheduler, ContinuousEngine, PrefixKVPool,
                           StreamRouter, round_up_blocks)

CFG = get_config("tiny")
PARAMS = init_params(CFG, jax.random.PRNGKey(3))
TOK = ByteTokenizer(CFG.vocab_size)
RNG = np.random.default_rng(0)
PROMPTS = RNG.integers(0, 200, (4, 10)).astype(np.int32)


def _dcfg(method="streaming", **kw):
    kw.setdefault("gen_len", 16)
    kw.setdefault("block_size", 8)
    kw.setdefault("window", 8)
    return DecodeConfig(method=method, **kw)


def _fake_eos_cfg(method="streaming", gen_len=32):
    """A config whose eos_token_id is the token the untrained model
    emits most — guarantees early exits (same trick as test_decoder)."""
    d = _dcfg(method, gen_len=gen_len, early_exit=False)
    r = DiffusionDecoder(CFG, PARAMS, d).generate(PROMPTS.copy())
    vals, counts = np.unique(r.tokens, return_counts=True)
    return dataclasses.replace(CFG, eos_token_id=int(vals[counts.argmax()]))


# ------------------------------------------------------------ decoder API


@pytest.mark.parametrize("method", [m for m in METHODS if m != "dkv"])
def test_decode_block_interleaved_matches_generate(method):
    """Two independent DecodeStates advanced alternately through
    decode_block reproduce generate() exactly — the resumability
    contract the scheduler relies on. (dkv is covered by the
    deterministic-backend subprocess test below: it amplifies
    run-to-run ulp noise from threaded CPU matmuls into argmax flips,
    so in-process exact comparison is not sound for it.)"""
    d = _dcfg(method)
    dec = DiffusionDecoder(CFG, PARAMS, d)
    ref_a = dec.generate(PROMPTS[:2].copy())
    ref_b = dec.generate(PROMPTS[2:].copy())
    sa = dec.prefill(PROMPTS[:2].copy())
    sb = dec.prefill(PROMPTS[2:].copy())
    while not (sa.finished and sb.finished):
        dec.decode_block(sa)
        dec.decode_block(sb)
    ra, rb = dec.finalize(sa), dec.finalize(sb)
    assert (ra.tokens == ref_a.tokens).all()
    assert (rb.tokens == ref_b.tokens).all()
    assert ra.nfe == ref_a.nfe and rb.nfe == ref_b.nfe


@pytest.mark.parametrize("method", [m for m in METHODS if m != "dkv"])
def test_batch_invariance(method):
    """Per-row outputs are bit-identical across batch reshaping for
    every method the scheduler compacts (dkv is excluded by design —
    its step-level KV freezing drifts at ulp level, which is why
    BlockScheduler pins dkv gangs to their admitted batch)."""
    d = _dcfg(method)
    dec = DiffusionDecoder(CFG, PARAMS, d)
    assert dec.batch_invariant
    full = dec.generate(PROMPTS.copy())
    for b in range(PROMPTS.shape[0]):
        one = DiffusionDecoder(CFG, PARAMS, d).generate(
            PROMPTS[b:b + 1].copy())
        assert (one.tokens[0] == full.tokens[b]).all()


def test_take_rows_resumes_mid_generation():
    d = _dcfg("streaming", gen_len=32)
    dec = DiffusionDecoder(CFG, PARAMS, d)
    ref = dec.generate(PROMPTS.copy())
    st = dec.prefill(PROMPTS.copy())
    dec.decode_block(st)                       # block 0 done at B=4
    sub = dec.take_rows(st, [1, 3])            # compact to B=2
    while not sub.finished:
        dec.decode_block(sub)
    out = dec.finalize(sub)
    assert (out.tokens == ref.tokens[[1, 3]]).all()


# ------------------------------------------------------------ KV pool


def test_pool_reuse_and_eviction():
    pool = PrefixKVPool(CFG, max_free=2)
    a = pool.acquire(2, 24)
    b = pool.acquire(2, 24)
    assert pool.misses == 2 and pool.hits == 0
    pool.release(2, 24, a)
    pool.release(2, 24, b)
    got = pool.acquire(2, 24)
    assert pool.hits == 1 and got is b          # most recently released
    pool.release(2, 24, got)                    # free: [a, b]
    pool.release(4, 24, pool.acquire(4, 24))    # evicts a (oldest)
    pool.release(2, 48, pool.acquire(2, 48))    # evicts b
    assert pool.evictions == 2
    assert pool.free_buffers == 2
    assert pool.acquire(8, 24) is not None      # miss allocates fresh
    assert pool.stats()["misses"] == 5


def test_pool_reused_across_requests():
    """Sequential same-bucket requests reuse one KV buffer instead of
    allocating per request."""
    eng = ContinuousEngine(CFG, PARAMS, _dcfg(), max_slots=2)
    prompt = PROMPTS[0]
    eng.submit(prompt, max_tokens=16)
    eng.run_to_completion()
    misses0 = eng.pool.misses
    eng.submit(prompt, max_tokens=16)
    eng.run_to_completion()
    assert eng.pool.misses == misses0          # no new allocation
    assert eng.pool.hits >= 1


# ------------------------------------------------------------ scheduler


def test_backfill_on_early_exit():
    """With every slot taken, a waiting request is admitted as soon as
    early exits shrink a gang — before the gang finishes its full
    generation."""
    cfg_eos = _fake_eos_cfg(gen_len=32)
    d = _dcfg("streaming", gen_len=32)
    sched = BlockScheduler(cfg_eos, PARAMS, d, max_slots=2, tokenizer=TOK)
    reqs = [sched.submit(PROMPTS[b], 32, 32) for b in range(3)]
    first, late = reqs[:2], reqs[2]
    n_blocks = 32 // d.block_size
    freed_after = admitted_after = None   # blocks the first gang decoded
    guard = 0
    while not sched.idle and guard < 100:
        guard += 1
        sched.tick()
        decoded = max(r.blocks_decoded for r in first)
        if freed_after is None and any(r.finish_time >= 0 for r in first):
            freed_after = decoded
        if admitted_after is None and late.admit_time >= 0:
            admitted_after = decoded
    assert guard < 100
    # the fake-EOS model exits early: a row of the first gang finishes
    # before its last block, and the waiting request takes the freed
    # slot at that same block boundary, after the exit
    assert freed_after is not None and freed_after < n_blocks
    assert admitted_after == freed_after
    assert late.admit_time >= min(r.finish_time for r in first
                                  if r.finish_time >= 0)


def test_early_exit_frees_compute():
    """Continuous mode spends fewer NFEs than synchronous batch on an
    early-exit-heavy workload: finished rows leave the batch at block
    boundaries instead of being decoded to the last block."""
    cfg_eos = _fake_eos_cfg(gen_len=32)
    d = _dcfg("streaming", gen_len=32)
    sync = ServingEngine(cfg_eos, PARAMS, d, max_batch=4, mode="batch")
    cont = ServingEngine(cfg_eos, PARAMS, d, max_batch=4, mode="continuous")
    for b in range(4):
        sync.submit(TOK.decode(PROMPTS[b])[:10].ljust(10, "x"),
                    max_tokens=32)
    # token prompts must match exactly: drive continuous with the same
    # encoded prompts through its scheduler
    for b in range(4):
        cont._continuous.scheduler.submit(
            sync.tok.encode(TOK.decode(PROMPTS[b])[:10].ljust(10, "x")),
            32, 32)
    sync_done = sync.run_to_completion()
    cont_done = cont._continuous.run_to_completion()
    assert len(sync_done) == len(cont_done) == 4
    sync_nfe = sync_done[0].nfe                 # batch NFE, all rows
    cont_nfe = max(c.nfe for c in cont_done)
    assert cont_nfe <= sync_nfe


@pytest.mark.parametrize("method", [m for m in METHODS if m != "dkv"])
def test_continuous_matches_batch_tokens(method):
    """Acceptance: continuous mode is token-identical to the
    synchronous path on a ragged workload (mixed gen_len buckets,
    backfill + compaction active)."""
    d = _dcfg(method)
    prompts = [TOK.decode(p) for p in
               RNG.integers(32, 126, (6, 9)).astype(np.int32)]
    budgets = [16, 8, 16, 8, 16, 8]
    sync = ServingEngine(CFG, PARAMS, d, max_batch=2, mode="batch")
    cont = ServingEngine(CFG, PARAMS, d, max_batch=2, mode="continuous")
    us = [sync.submit(p, mt) for p, mt in zip(prompts, budgets)]
    uc = [cont.submit(p, mt) for p, mt in zip(prompts, budgets)]
    ds_ = {c.uid: c for c in sync.run_to_completion()}
    dc = {c.uid: c for c in cont.run_to_completion()}
    for a, b in zip(us, uc):
        assert (ds_[a].tokens == dc[b].tokens).all(), method


def test_dkv_equivalence_structural():
    """dkv resumability and continuous/batch equivalence. dkv's
    step-level KV freezing amplifies run-to-run XLA:CPU noise
    (work-stealing threaded matmul reductions — persists even under
    --xla_cpu_multi_thread_eigen=false) into occasional argmax flips,
    so exact token identity is not assertable for it on this backend.
    Structure is: with early_exit off the dkv schedule is fixed
    (1 prefill + 8 steps/block), so NFE and per-block step counts must
    match exactly, and token agreement must stay far above what any
    scheduling logic bug (wrong cache carry / block resume) would
    leave intact."""
    d = _dcfg("dkv", early_exit=False)
    dec = DiffusionDecoder(CFG, PARAMS, d)
    ref = dec.generate(PROMPTS[:2].copy())
    st = dec.prefill(PROMPTS[:2].copy())
    while not st.finished:
        dec.decode_block(st)
    out = dec.finalize(st)
    assert out.nfe == ref.nfe == 1 + 2 * 8
    assert out.steps_per_block == ref.steps_per_block
    assert (out.tokens != CFG.mask_token_id).all()
    assert (out.tokens == ref.tokens).mean() > 0.5

    prompts = [TOK.decode(p) for p in
               RNG.integers(32, 126, (3, 9)).astype(np.int32)]
    sync = ServingEngine(CFG, PARAMS, d, max_batch=4, mode="batch")
    cont = ServingEngine(CFG, PARAMS, d, max_batch=4, mode="continuous")
    us = [sync.submit(p, 16) for p in prompts]
    uc = [cont.submit(p, 16) for p in prompts]
    ds_ = {c.uid: c for c in sync.run_to_completion()}
    dc = {c.uid: c for c in cont.run_to_completion()}
    assert len(ds_) == len(dc) == 3
    a = np.stack([ds_[u].tokens for u in us])
    b = np.stack([dc[u].tokens for u in uc])
    assert (a == b).mean() > 0.5


def test_pad_pow2_admits_groups_larger_than_pow2_capacity():
    """Regression: with pad_pow2, a group whose padded size exceeds
    max_slots must be split down the pow2 ladder, not livelock the
    queue (5 requests at max_slots=6 -> gangs of 4 + 1, all served)."""
    eng = ContinuousEngine(CFG, PARAMS, _dcfg(), max_slots=6,
                           pad_pow2=True)
    uids = [eng.submit(PROMPTS[b % 4], max_tokens=16) for b in range(5)]
    done = eng.run_to_completion()
    assert sorted(c.uid for c in done) == sorted(uids)


def test_admission_control():
    sched = BlockScheduler(CFG, PARAMS, _dcfg(), max_slots=2,
                           max_waiting=2, tokenizer=TOK)
    sched.submit(PROMPTS[0], 16, 16)
    sched.submit(PROMPTS[1], 16, 16)
    with pytest.raises(RuntimeError, match="admission rejected"):
        sched.submit(PROMPTS[2], 16, 16)


def test_preemption_resumes_exactly():
    d = _dcfg("streaming", gen_len=32)
    ref = DiffusionDecoder(CFG, PARAMS, d).generate(PROMPTS[:1].copy())
    eng = ContinuousEngine(CFG, PARAMS, d, max_slots=4)
    uid = eng.submit(PROMPTS[0], max_tokens=32)
    eng.step()                                  # block 0 decoded
    eng.preempt(uid)
    eng.step()                                  # vacated + re-admitted
    assert eng.scheduler.paused or eng.scheduler.gangs
    done = eng.run_to_completion()
    assert len(done) == 1
    assert (done[0].tokens == ref.tokens[0]).all()


# ------------------------------------------------------------ cancellation


def test_cancel_mid_gang_frees_slot_and_preserves_survivors():
    """cancel(uid) on an active row releases the slot at the next
    block boundary (before that tick's decode), yields a partial
    cancelled Completion, and leaves every surviving row bit-identical
    to an uncancelled run (the batch-invariance contract)."""
    d = _dcfg("streaming", gen_len=32, early_exit=False)
    ref = DiffusionDecoder(CFG, PARAMS, d).generate(PROMPTS.copy())
    eng = ContinuousEngine(CFG, PARAMS, d, max_slots=4)
    uids = [eng.submit(PROMPTS[b], max_tokens=32) for b in range(4)]
    eng.step()                                  # block 0 at B=4
    assert eng.scheduler.slots_used == 4
    assert eng.cancel(uids[1]) is None          # active -> deferred
    comps = eng.step()                          # cancel applies first
    cancelled = [c for c in comps if c.cancelled]
    assert [c.uid for c in cancelled] == [uids[1]]
    assert cancelled[0].n_blocks == 1           # paid for exactly 1 block
    assert len(cancelled[0].tokens) == 8        # the committed block only
    assert eng.scheduler.slots_used == 3        # slot freed for good
    comps += eng.run_to_completion()
    done = {c.uid: c for c in comps}
    for b in (0, 2, 3):                         # survivors untouched
        assert (done[uids[b]].tokens == ref.tokens[b]).all()
    assert (cancelled[0].tokens == ref.tokens[1][:8]).all()
    assert eng.metrics.cancelled == 1


def test_cancel_before_admit_drains_waiting_queue():
    """Cancelling a request still in the waiting queue removes it
    immediately (no slot ever consumed) and returns its empty
    Completion synchronously."""
    d = _dcfg("streaming", gen_len=16, early_exit=False)
    eng = ContinuousEngine(CFG, PARAMS, d, max_slots=2)
    uids = [eng.submit(PROMPTS[b], max_tokens=16) for b in range(3)]
    eng.step()                                  # 2 admitted, 1 waiting
    assert len(eng.scheduler.waiting) == 1
    comp = eng.cancel(uids[2])
    assert comp is not None and comp.cancelled and comp.n_tokens == 0
    assert not eng.scheduler.waiting
    rest = eng.run_to_completion()
    assert sorted(c.uid for c in rest) == sorted(uids[:2])
    assert not any(c.cancelled for c in rest)


def test_cancel_unknown_or_finished_uid_is_noop():
    eng = ContinuousEngine(CFG, PARAMS, _dcfg(), max_slots=2)
    uid = eng.submit(PROMPTS[0], max_tokens=16)
    assert eng.cancel(999) is None
    assert not eng.scheduler._cancel            # no stale flag parked
    done = eng.run_to_completion()
    assert len(done) == 1 and not done[0].cancelled
    assert eng.cancel(uid) is None              # finished: ignored
    assert not eng.scheduler._cancel


def test_completion_trims_to_requested_max_tokens():
    """gen_len rounds max_tokens up to a block multiple; the surplus
    must never leave the engine — neither in Completion.tokens/text nor
    in the streamed chunk text."""
    d = _dcfg("streaming", gen_len=16, early_exit=False)
    eng = ContinuousEngine(CFG, PARAMS, d, max_slots=2)
    uid = eng.submit(PROMPTS[0], max_tokens=11)   # rounds up to 16
    got = []
    eng.on_chunk(uid, got.append)
    comp = eng.run_to_completion()[0]
    assert comp.max_tokens == 11
    assert len(comp.tokens) == 11 and comp.n_tokens <= 11
    assert comp.text == TOK.decode(comp.tokens)
    # chunk text: block 0 carries 8 tokens' text, block 1 only 3
    assert "".join(c.text for c in got) == comp.text


# ------------------------------------------------------------ streaming


def test_stream_chunks_ordered_and_complete():
    d = _dcfg("streaming", gen_len=16, early_exit=False)
    eng = ContinuousEngine(CFG, PARAMS, d, max_slots=4)
    uids = [eng.submit(PROMPTS[b], max_tokens=16) for b in range(3)]
    seen = {}
    for chunk in eng.stream():
        seen.setdefault(chunk.uid, []).append(chunk)
    assert set(seen) == set(uids)
    for uid in uids:
        blocks = [c.block_idx for c in seen[uid]]
        assert blocks == list(range(len(blocks)))      # in order, gapless
        assert [c.finished for c in seen[uid]].count(True) == 1
        assert seen[uid][-1].finished
        joined = "".join(c.text for c in seen[uid][:-1])
        assert isinstance(joined, str)


def test_stream_callbacks_fire_per_block():
    d = _dcfg("streaming", gen_len=16, early_exit=False)
    eng = ContinuousEngine(CFG, PARAMS, d, max_slots=2)
    uid = eng.submit(PROMPTS[0], max_tokens=16)
    got = []
    eng.on_chunk(uid, got.append)
    eng.run_to_completion()
    assert [c.block_idx for c in got] == [0, 1]
    assert got[-1].finished


def test_stream_router_unsubscribes_finished():
    router = StreamRouter()
    router.subscribe(7, lambda c: None)
    from repro.serving.types import BlockChunk
    router.publish([BlockChunk(7, 0, np.zeros(2, np.int32), "", True, False)])
    assert 7 not in router._subs


def test_stream_router_hygiene():
    """Regression: a raising subscriber must not abort delivery to
    later subscribers or later chunks (it is logged and dropped), and
    emptied subscriber lists — per-uid and wildcard — are GC'd."""
    from repro.serving.types import BlockChunk

    def chunk(uid, finished=False):
        return BlockChunk(uid, 0, np.zeros(1, np.int32), "", finished,
                          False)

    router = StreamRouter()
    good, wild = [], []

    def bad(c):
        raise RuntimeError("boom")

    router.subscribe(1, bad)
    router.subscribe(1, good.append)
    router.subscribe(None, wild.append)
    router.publish([chunk(1), chunk(1)])
    assert len(good) == 2 and len(wild) == 2    # bad didn't block anyone
    assert bad not in router._subs.get(1, [])   # bad was dropped
    # wildcard entry is GC'd once its last subscriber leaves
    router.unsubscribe(None, wild.append)
    assert None not in router._subs
    # a raising wildcard-only subscriber leaves no empty list behind
    router.subscribe(None, bad)
    router.publish([chunk(2)])
    assert None not in router._subs


# ------------------------------------------------------------ metrics


def test_metrics_snapshot():
    eng = ContinuousEngine(CFG, PARAMS, _dcfg(), max_slots=2)
    for b in range(3):
        eng.submit(PROMPTS[b], max_tokens=16)
    done = eng.run_to_completion()
    snap = eng.metrics.snapshot()
    assert snap["requests"] == 3 == len(done)
    assert snap["throughput_tok_s"] >= 0
    assert 0 < snap["mean_occupancy"] <= 1
    for c in done:
        assert c.ttfb_s <= c.latency_s
        assert c.queue_s <= c.ttfb_s
    assert snap["ttfb_p50_s"] <= snap["latency_p50_s"]
    assert round_up_blocks(13, 8) == 16


def test_legacy_engine_api_continuous_default():
    eng = ServingEngine(CFG, PARAMS, _dcfg(), max_batch=4)
    assert eng.mode == "continuous"
    for i in range(3):
        eng.submit(f"Q:{i}{i}+11=? A:", max_tokens=16)
    done = eng.run_to_completion()
    assert len(done) == 3
    assert all(isinstance(c.text, str) for c in done)
    assert eng.throughput > 0


# ------------------------------------------------------ placement layer


def test_executor_1x1_identity_and_pool_binding():
    """A DecodeExecutor on a trivial 1x1 mesh is the identity
    placement: bit-identical tokens, data_extent 1. Pools are bound to
    one executor — a host pool handed to an executor-backed scheduler
    must be refused (cross-mesh buffer reuse hazard)."""
    from repro.launch.mesh import make_host_mesh
    from repro.serving import DecodeExecutor

    d = _dcfg("streaming")
    ref = DiffusionDecoder(CFG, PARAMS, d).generate(PROMPTS.copy())
    ex = DecodeExecutor(CFG, PARAMS, make_host_mesh(1, 1))
    got = DiffusionDecoder(CFG, None, d, executor=ex).generate(
        PROMPTS.copy())
    assert (ref.tokens == got.tokens).all()
    assert ex.data_extent == 1
    with pytest.raises(ValueError):
        ContinuousEngine(CFG, PARAMS, d, pool=PrefixKVPool(CFG),
                         executor=ex)
    # placement-keyed pool: host and executor pools bucket differently
    host_pool, ex_pool = PrefixKVPool(CFG), PrefixKVPool(CFG, executor=ex)
    assert host_pool._key(2, 24) != ex_pool._key(2, 24)


def test_pooled_prefix_reuse_across_gangs_no_aliasing():
    """Regression (reuse-after-free hazard): a sub-state extracted by
    take_rows must not alias KV of the gang it left — the gang's buffer
    goes back to the pool, is handed to a *new* gang, and gets
    rewritten (or donated on accelerators, where aliased memory is
    *dead*). dkv is the method whose cache carries across blocks, and
    its in-process token comparison is unsound (ulp noise, see
    test_dkv_equivalence_structural), so the contract is asserted on
    the cache bytes themselves: the parked KV must be bit-stable while
    a second gang churns the pooled buffer."""
    d = _dcfg("dkv", gen_len=32)         # dkv: cache carries across blocks
    dec = DiffusionDecoder(CFG, PARAMS, d)
    pool = PrefixKVPool(CFG)

    st = dec.prefill(PROMPTS.copy(), cache=pool.acquire(4, 42))
    dec.decode_block(st)
    sub = dec.take_rows(st, [1])          # park row 1 mid-generation
    snap = [np.array(leaf) for leaf in jax.tree.leaves(sub.cache)]
    # the first gang's buffer returns to the pool and a second gang
    # reuses (and on accelerators would donate) it before the parked
    # row resumes
    pool.release(4, 42, st.cache)
    st2 = dec.prefill(PROMPTS.copy(), cache=pool.acquire(4, 42))
    assert pool.hits >= 1                 # really the same buffer
    while not st2.finished:
        dec.decode_block(st2)
    for before, after in zip(snap, jax.tree.leaves(sub.cache)):
        assert (before == np.array(after)).all(), \
            "parked take_rows KV aliased the pooled buffer"
    while not sub.finished:               # parked row still completes
        dec.decode_block(sub)
    assert dec.finalize(sub).tokens.shape == (1, 32)


def test_gang_sizes_round_to_batch_multiple():
    """Data-shard-aware bucketing: gang batches round up to the data
    extent so sharded placement never falls back silently; pad lanes
    are real (replicate row 0) but carry no request."""
    sched = BlockScheduler(CFG, PARAMS, _dcfg(), max_slots=8,
                           batch_multiple=4)
    assert sched._pad_batch(1) == 4 and sched._pad_batch(5) == 8
    for b in range(3):
        sched.submit(PROMPTS[b], 16, 16)
    sched.tick()
    assert len(sched.gangs) == 1
    gang = sched.gangs[0]
    assert gang.batch == 4
    assert sum(r is not None for r in gang.requests) == 3
    # a multiple that doesn't divide max_slots must not livelock
    sched2 = BlockScheduler(CFG, PARAMS, _dcfg(), max_slots=8,
                            batch_multiple=3)
    n, padded = sched2._gang_target(8, 8, sched2._decoder(16))
    assert n > 0 and padded <= 8 and padded % 3 == 0


# ------------------------------------------------------ cross-gang merge


def test_cross_gang_merge_of_stragglers():
    """Two same-bucket gangs left ragged (here: one row of each
    cancelled) fuse into ONE gang at the next block boundary — half the
    block calls — and the surviving rows stay bit-identical."""
    d = _dcfg("streaming", gen_len=24, early_exit=False)
    ref = DiffusionDecoder(CFG, PARAMS, d).generate(PROMPTS.copy())
    eng = ContinuousEngine(CFG, PARAMS, d, max_slots=4, max_gang=2,
                           tokenizer=TOK)
    uids = [eng.submit(PROMPTS[i], max_tokens=24) for i in range(4)]
    eng.step()                            # two gangs of 2 decode block 0
    assert len(eng.scheduler.gangs) == 2
    eng.cancel(uids[1])
    eng.cancel(uids[3])
    eng.step()          # cancels vacate -> stragglers merge -> block 1
    assert eng.scheduler.merges == 1
    assert len(eng.scheduler.gangs) == 1
    assert eng.scheduler.gangs[0].batch == 2
    comps = {c.uid: c for c in eng.run_to_completion()}
    assert (comps[uids[0]].tokens == ref.tokens[0]).all()
    assert (comps[uids[2]].tokens == ref.tokens[2]).all()
    assert eng.metrics.snapshot()["gang_merges"] == 1


def test_merge_respects_max_gang_and_skips_dkv():
    """Gangs whose combined open rows exceed max_gang stay separate;
    dkv gangs (non-batch-invariant) are never merged."""
    d = _dcfg("streaming", gen_len=24, early_exit=False)
    eng = ContinuousEngine(CFG, PARAMS, d, max_slots=4, max_gang=2,
                           tokenizer=TOK)
    for i in range(4):
        eng.submit(PROMPTS[i], max_tokens=24)
    eng.step()
    eng.step()                            # 2+2 > max_gang: no merge
    assert eng.scheduler.merges == 0 and len(eng.scheduler.gangs) == 2
    dv = _dcfg("dkv", gen_len=24)
    eng2 = ContinuousEngine(CFG, PARAMS, dv, max_slots=4, max_gang=1,
                            tokenizer=TOK)
    for i in range(2):
        eng2.submit(PROMPTS[i], max_tokens=24)
    eng2.step()                           # two 1-row dkv gangs
    assert len(eng2.scheduler.gangs) == 2
    eng2.scheduler.max_gang = 2           # merge would now fit...
    eng2.step()
    assert eng2.scheduler.merges == 0     # ...but dkv is never merged
    eng2.run_to_completion()


# ------------------------------------------ gangs at different blocks


def test_gangs_at_different_blocks_merge():
    """A gang admitted at a boundary merges with a live gang one block
    ahead before its first block: one block program for both, each row
    at its own block, every row's tokens those of decoding it alone."""
    d = _dcfg("streaming", gen_len=24, early_exit=False)
    ref = DiffusionDecoder(CFG, PARAMS, d).generate(PROMPTS.copy())
    eng = ContinuousEngine(CFG, PARAMS, d, max_slots=4, tokenizer=TOK)
    uids = [eng.submit(PROMPTS[i], max_tokens=24) for i in range(2)]
    eng.step()                            # block 0 of the first two
    uids += [eng.submit(PROMPTS[i], max_tokens=24) for i in (2, 3)]
    eng.step()                            # admit -> merge -> one program
    sched = eng.scheduler
    assert len(sched.gangs) == 1 and sched.merges == 1
    assert sorted(sched.gangs[0].state.blocks.tolist()) == [1, 1, 2, 2]
    assert (sched.block_programs, sched.mixed_block_programs) == (2, 1)
    comps = {c.uid: c for c in eng.run_to_completion()}
    for i, uid in enumerate(uids):
        assert (comps[uid].tokens == ref.tokens[i]).all(), i
    snap = eng.metrics.snapshot()
    assert snap["block_programs"] == sched.block_programs == 4
    assert snap["mixed_block_programs"] == 2
    assert snap["ticks"] == 4


def test_request_joins_vacated_lane_on_its_first_tick():
    """A request that arrives while a lane of a live gang is vacated is
    admitted at the next boundary and decodes its first block in that
    gang on that tick — no extra slot, no extra program."""
    d = _dcfg("streaming", gen_len=24, early_exit=False)
    ref = DiffusionDecoder(CFG, PARAMS, d).generate(PROMPTS.copy())
    eng = ContinuousEngine(CFG, PARAMS, d, max_slots=4, pad_pow2=True,
                           tokenizer=TOK)
    uids = [eng.submit(PROMPTS[i], max_tokens=24) for i in range(4)]
    eng.step()
    sched = eng.scheduler
    eng.cancel(uids[1])                   # its lane is vacated ...
    late = eng.submit(PROMPTS[1], max_tokens=24)   # ... as this arrives
    done = eng.step()
    assert len(sched.gangs) == 1 and sched.gangs[0].batch == 4
    assert sched.slots_used <= sched.max_slots
    assert (sched.block_programs, sched.mixed_block_programs) == (2, 1)
    req = next(r for r in sched.gangs[0].requests if r and r.uid == late)
    assert req.blocks_decoded == 1
    comps = {c.uid: c for c in done + eng.run_to_completion()}
    assert comps[uids[1]].cancelled
    for i, uid in ((0, uids[0]), (1, late), (2, uids[2]), (3, uids[3])):
        assert (comps[uid].tokens == ref.tokens[i]).all(), i


@pytest.mark.parametrize("first", [1, 3])
def test_closed_loop_of_eight_settles_at_one_program_per_tick(first):
    """Eight clients in a closed loop, opened as a server sees them (the
    engine thread's first drain takes one request, or three, and the
    rest come a tick later): the late ones merge with the rows one
    block ahead, and every later request joins the live gang, so each
    tick runs one block program for all eight."""
    d = _dcfg("streaming", gen_len=32, early_exit=False)
    sched = BlockScheduler(CFG, PARAMS, d, max_slots=8, pad_pow2=True,
                           tokenizer=TOK)
    rng = np.random.default_rng(1)
    prompt = lambda: rng.integers(0, 200, 10).astype(np.int32)  # noqa
    for _ in range(first):
        sched.submit(prompt(), 32, 32)
    sched.tick()
    for _ in range(8 - first):
        sched.submit(prompt(), 32, 32)
    served = 0
    for _ in range(24):
        _, done = sched.tick()
        for _ in done:                    # each client asks again
            sched.submit(prompt(), 32, 32)
        served += len(done)
    assert served >= 40
    assert sched.block_programs == 25     # one program a tick
    assert sched.mixed_block_programs >= 20
    assert sched.live_rows + len(sched.waiting) == 8


def test_host_loop_keeps_gangs_at_one_block():
    """The per-step host loop decodes a batch at one block index, so its
    decoder does not mix blocks: requests that arrive a tick apart stay
    in gangs of their own block, and every row's tokens are those of
    ``generate`` with the same loop."""
    d = _dcfg("streaming", gen_len=24, early_exit=False, fused=False)
    assert not DiffusionDecoder(CFG, PARAMS, d).mixes_blocks
    ref = DiffusionDecoder(CFG, PARAMS, d).generate(PROMPTS.copy())
    eng = ContinuousEngine(CFG, PARAMS, d, max_slots=4, tokenizer=TOK)
    uids = [eng.submit(PROMPTS[i], max_tokens=24) for i in range(2)]
    eng.step()
    uids += [eng.submit(PROMPTS[i], max_tokens=24) for i in (2, 3)]
    eng.step()
    sched = eng.scheduler
    assert sorted(g.state.block_idx for g in sched.gangs) == [1, 2]
    comps = {c.uid: c for c in eng.run_to_completion()}
    for i, uid in enumerate(uids):
        assert (comps[uid].tokens == ref.tokens[i]).all(), i
    assert sched.mixed_block_programs == 0
