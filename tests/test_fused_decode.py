"""Fused device-resident denoise loop vs the legacy host loop.

The fused path compiles the whole per-block denoise loop (refresh +
``lax.while_loop`` steps + straggler finalize + EOS early exit) into one
jitted function that the host calls once per block. These tests pin the
contract that makes it a pure refactor: token identity with the per-step
host loop for all five methods, under both kernel routings, with exact
NFE / per-block step / flop-proxy counter agreement — plus the
no-per-block-recompilation bound the serving layer relies on."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.decoder import METHODS, DecodeConfig, DiffusionDecoder
from repro.models import get_config, init_params

CFG = get_config("tiny")
PARAMS = init_params(CFG, jax.random.PRNGKey(3))
PROMPT = np.random.default_rng(0).integers(0, 200, (2, 10)).astype(np.int32)


def _pair(method, **kw):
    """(host-loop result, fused-loop result) on identical inputs."""
    kw.setdefault("gen_len", 16)
    kw.setdefault("block_size", 8)
    kw.setdefault("window", 4)
    d = DecodeConfig(method=method, fused=False, **kw)
    host = DiffusionDecoder(CFG, PARAMS, d).generate(PROMPT.copy())
    df = dataclasses.replace(d, fused=True)
    fused = DiffusionDecoder(CFG, PARAMS, df).generate(PROMPT.copy())
    return host, fused


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["ref", "pallas"])
@pytest.mark.parametrize("method", METHODS)
def test_fused_matches_host_loop(method, use_kernels):
    """Bit-identical tokens and identical schedule/flop accounting
    between the two loop implementations, with attention/confidence on
    either the reference or the Pallas route.

    dkv is the one exception to bitwise comparison — the same
    XLA:CPU threaded-reduction run-to-run noise that already forces its
    continuous/batch equivalence to be structural (see
    test_serving.py::test_dkv_equivalence_structural) flips occasional
    argmaxes between any two runs, including two host-loop runs. Its
    schedule and counters are still exact, and token agreement must
    stay far above anything a loop-logic bug would leave intact."""
    host, fused = _pair(method, use_kernels=use_kernels, tau0=0.5)
    if method == "dkv":
        assert (host.tokens == fused.tokens).mean() > 0.5
        assert (fused.tokens != CFG.mask_token_id).all()
    else:
        assert (host.tokens == fused.tokens).all()
    assert host.nfe == fused.nfe
    assert host.steps_per_block == fused.steps_per_block
    assert host.query_tokens_processed == fused.query_tokens_processed
    assert host.kv_tokens_attended == fused.kv_tokens_attended
    assert host.early_exits == fused.early_exits


def test_fused_matches_host_loop_frozen_suffix():
    host, fused = _pair("streaming", gen_len=32, window=8,
                        frozen_suffix=True, tau0=0.5)
    assert (host.tokens == fused.tokens).all()
    assert host.nfe == fused.nfe
    assert host.kv_tokens_attended == fused.kv_tokens_attended


def test_fused_matches_host_loop_early_exit():
    """With a fake EOS the model actually emits, both loops must agree
    on which rows exit, when, and what the truncated outputs are."""
    d0 = DecodeConfig(method="streaming", gen_len=32, block_size=8,
                      window=8, early_exit=False)
    r0 = DiffusionDecoder(CFG, PARAMS, d0).generate(PROMPT.copy())
    vals, counts = np.unique(r0.tokens, return_counts=True)
    cfg2 = dataclasses.replace(CFG, eos_token_id=int(vals[counts.argmax()]))
    d = DecodeConfig(method="streaming", gen_len=32, block_size=8, window=8,
                     fused=False)
    host = DiffusionDecoder(cfg2, PARAMS, d).generate(PROMPT.copy())
    fused = DiffusionDecoder(
        cfg2, PARAMS, dataclasses.replace(d, fused=True)).generate(
        PROMPT.copy())
    assert (host.tokens == fused.tokens).all()
    assert host.early_exits == fused.early_exits > 0
    assert host.steps_per_block == fused.steps_per_block


def test_fused_one_host_sync_per_block():
    """The whole point: the host loop syncs every denoise step (and, on
    the fixed-schedule methods, copies full (B, K, V) logits each time);
    the fused loop syncs once per block and never copies block logits."""
    host, fused = _pair("prefix")
    n_blocks = len(fused.steps_per_block)
    assert fused.host_syncs == n_blocks
    assert fused.logit_syncs == 0
    assert host.host_syncs == host.nfe          # one per step
    assert host.logit_syncs == host.nfe         # (B, K, V) every step
    # parallel methods move even the host loop onto the fused head path:
    # per-step syncs shrink to (conf, toks), never block logits
    host_s, fused_s = _pair("streaming")
    assert host_s.logit_syncs == fused_s.logit_syncs == 0
    assert fused_s.host_syncs == len(fused_s.steps_per_block)


def test_fused_no_per_block_recompilation():
    """The jit cache is bounded by shape buckets: a second generation at
    the same shapes must not add compiled variants (the serving
    scheduler's no-recompile-after-warmup property)."""
    d = DecodeConfig(method="streaming", gen_len=16, block_size=8, window=4,
                     fused=True)
    dec = DiffusionDecoder(CFG, PARAMS, d)
    dec.generate(PROMPT.copy())
    size_after_warmup = dec.jit_cache_size()
    # fused loop: one compiled variant per block index, none per request
    assert size_after_warmup <= d.gen_len // d.block_size + 1
    other = np.random.default_rng(9).integers(0, 200, (2, 10)).astype(
        np.int32)
    dec.generate(other)
    assert dec.jit_cache_size() == size_after_warmup


def test_straggler_finalize_preserves_done_rows():
    """Regression (both loops): when the steps cap forces a straggler
    commit, rows that early-exited in a PRIOR block must keep their
    masked tail instead of having it overwritten with the last step's
    argmax — the EOS truncation in finalize was the only thing hiding
    the overwrite."""
    for fused in (False, True):
        d = DecodeConfig(method="streaming", gen_len=16, block_size=8,
                         window=4, steps_per_block=1, tau0=0.99,
                         fused=fused)
        dec = DiffusionDecoder(CFG, PARAMS, d)
        st = dec.prefill(PROMPT.copy())
        st.done[0] = True               # pretend row 0 exited in block -1
        dec.decode_block(st)
        blk = st.x[:, st.prompt_len:st.prompt_len + 8]
        # the single step's selection still commits its fallback token
        # for every row (legacy semantics), but the cap-time straggler
        # fill must skip the done row: its tail stays masked while the
        # live row's block is fully argmax-filled
        assert (blk[0] == CFG.mask_token_id).any(), fused
        assert (blk[1] != CFG.mask_token_id).all(), fused


def test_decode_state_resume_across_loop_switch():
    """DecodeState is loop-agnostic: blocks decoded by the host loop
    then resumed under the fused loop (or vice versa) reproduce a pure
    single-loop run exactly — the scheduler may flip ``fused`` between
    ticks without perturbing generations."""
    d = DecodeConfig(method="streaming", gen_len=32, block_size=8, window=8,
                     fused=True)
    ref = DiffusionDecoder(CFG, PARAMS, d).generate(PROMPT.copy())
    dec_f = DiffusionDecoder(CFG, PARAMS, d)
    dec_h = DiffusionDecoder(CFG, PARAMS,
                             dataclasses.replace(d, fused=False))
    st = dec_h.prefill(PROMPT.copy())
    dec_h.decode_block(st)              # block 0: host loop
    dec_f.decode_block(st)              # block 1: fused loop
    dec_h.decode_block(st)              # block 2: host loop
    dec_f.decode_block(st)              # block 3: fused loop
    out = dec_f.finalize(st)
    assert (out.tokens == ref.tokens).all()
    assert out.nfe == ref.nfe


def test_block_program_names_its_phases():
    """The fused block program is ``jit_decode_block``, and its
    compiled operations carry the phase scopes a device trace reads."""
    import re
    d = DecodeConfig(method="streaming", gen_len=16, block_size=8, window=4,
                     fused=True)
    dec = DiffusionDecoder(CFG, PARAMS, d)
    lowered = dec.lower_block(dec.prefill(PROMPT.copy()))
    assert lowered.as_text().startswith("module @jit_decode_block")
    op_names = set(re.findall(r'op_name="([^"]*)"',
                              lowered.compile().as_text()))
    for scope in ("jit(decode_block)/refresh/",
                  "jit(decode_block)/while/body/denoise_step/",
                  "/refresh/head_confidence/",
                  "/denoise_step/head_confidence/",
                  "jit(decode_block)/finalize/"):
        assert any(scope in n for n in op_names), scope


def test_jitted_functions_are_named_after_their_keys():
    """Every jitted decoder function, and the executor's cache maker,
    is named for what it does, never ``f`` or ``<lambda>``."""
    from repro.launch.mesh import make_submeshes
    from repro.serving import DecodeExecutor
    dec = DiffusionDecoder(CFG, PARAMS, DecodeConfig(method="streaming"))
    makers = [getattr(dec, a) for a in dir(dec)
                if a.startswith("_") and a.endswith("_fn")]
    for make in makers:
        make()
    assert len(dec._fns) == len(makers) == 13
    for key, fn in dec._fns.items():
        assert fn.__name__ == key
    ex = DecodeExecutor(CFG, PARAMS, make_submeshes(1)[0])
    ex.init_cache(1, 16)
    assert [fn.__name__ for fn in ex._cache_fns.values()] == ["init_cache"]


# ------------------------------------------- rows at different blocks

MIX_PROMPTS = np.random.default_rng(5).integers(0, 200, (3, 10)).astype(
    np.int32)


def _mixing_decoder(method, **kw):
    kw.setdefault("use_kernels", False)
    return DiffusionDecoder(CFG, PARAMS, DecodeConfig(
        method=method, gen_len=32, block_size=8, window=8, tau0=0.5, **kw))


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["ref", "pallas"])
@pytest.mark.parametrize("method", ["streaming", "fast", "prefix"])
def test_mixed_block_gang_matches_rows_alone(method, use_kernels):
    """A gang whose rows stand at blocks 2, 1 and 0 commits, for each
    row, exactly the tokens that row commits decoded alone — through
    every later block, including the calls in which the first row has
    finished and rides along as a done lane."""
    dec = _mixing_decoder(method, use_kernels=use_kernels)
    assert dec.mixes_blocks
    alone = [dec.generate(MIX_PROMPTS[i:i + 1].copy()).tokens[0]
             for i in range(3)]
    parts = []
    for i, ahead in enumerate((2, 1, 0)):
        st = dec.prefill(MIX_PROMPTS[i:i + 1].copy())
        for _ in range(ahead):
            dec.decode_block(st)
        parts.append((st, [0]))
    gang = dec.merge_rows(parts)
    assert gang.blocks.tolist() == [2, 1, 0] and gang.block_idx == 0
    dec.decode_block(gang)
    assert gang.block_stats[-1].row_blocks == [2, 1, 0]
    while not gang.finished:
        dec.decode_block(gang)
    assert gang.block_stats[-1].row_blocks == [-1, -1, 3]
    out = dec.finalize(gang)
    for i in range(3):
        assert (out.tokens[i] == alone[i]).all(), i


def test_block_idx_assignment_puts_every_row_at_that_block():
    """``state.block_idx = b`` puts every row at block b; reading it
    gives the earliest row that still has a block to decode."""
    dec = _mixing_decoder("streaming")
    st = dec.prefill(MIX_PROMPTS[:2].copy())
    st.block_idx = 2
    assert st.blocks.tolist() == [2, 2] and st.block_idx == 2
    dec.decode_block(st)
    assert st.block_stats[-1].row_blocks == [2, 2]
    assert st.blocks.tolist() == [3, 3]
    st.blocks = np.array([3, 1], np.int32)
    assert st.block_idx == 1
    st.done[1] = True
    assert st.block_idx == 3 and not st.finished
    st.blocks[0] = 4
    assert st.finished and st.row_finished(0) and st.row_finished(1)


def test_mixed_gang_adds_no_compiled_variant():
    """After a warm-up of uniform gangs at every block and gang size (as
    the serving warm-up runs them), gangs mixing block indexes compile
    nothing: the program's only static key is (batch, query width),
    and a mixed gang runs at its earliest row's width."""
    dec = _mixing_decoder("streaming")
    for B in (1, 2, 3):
        for b in range(4):
            st = dec.prefill(MIX_PROMPTS[:B].copy())
            st.block_idx = b
            dec.decode_block(st)
    warm = dec.jit_cache_size()
    widths = {len(dec._region(10, b)[1]) for b in range(4)}
    assert warm == 3 * len(widths) == 9
    for ahead in ([1, 0], [3, 0], [2, 1, 0], [3, 3, 1]):
        parts = []
        for i, a in enumerate(ahead):
            st = dec.prefill(MIX_PROMPTS[i:i + 1].copy())
            for _ in range(a):
                dec.decode_block(st)
            parts.append((st, [0]))
        gang = dec.merge_rows(parts)
        while not gang.finished:
            dec.decode_block(gang)
    assert dec.jit_cache_size() == warm


@pytest.mark.parametrize("kw", [
    dict(method="dkv"), dict(method="vanilla"),
    dict(method="streaming", prefix_cache=True),
    dict(method="streaming", frozen_suffix=True)],
    ids=["dkv", "vanilla", "prefix_cache", "frozen_suffix"])
def test_methods_that_cannot_mix_say_so(kw):
    """dkv, vanilla, the prefix-cache and the frozen-suffix refreshes
    keep a batch at one block index, and say so; so does every method
    on a layout whose tokens meet outside attention (MoE routing)."""
    kw = dict(kw)
    method = kw.pop("method")
    dec = _mixing_decoder(method, **kw)
    assert not dec.mixes_blocks
    moe = get_config("tiny-moe")
    assert not DiffusionDecoder(moe, None, DecodeConfig(
        method="streaming")).mixes_blocks
    if method in ("dkv", "vanilla"):
        return
    a = dec.prefill(MIX_PROMPTS[:1].copy())
    dec.decode_block(a)
    b = dec.prefill(MIX_PROMPTS[1:2].copy())
    with pytest.raises(AssertionError, match="one block index"):
        dec.merge_rows([(a, [0]), (b, [0])])
