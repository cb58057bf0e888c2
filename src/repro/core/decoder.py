"""Block-wise diffusion decoding — Streaming-dLLM and all paper baselines.

Five methods (paper Tables 1/2/8):

  vanilla   — no cache; full-sequence forward each denoise step; fixed
              schedule (top-`K/M` most-confident masked tokens per step).
  dkv       — delayed KV cache (Ma et al. 2025): a token's K/V is frozen
              into a position-indexed cache one step after it decodes;
              masked tokens recompute theirs each step. Vanilla schedule.
  prefix    — Fast-dLLM's prefix cache: prompt + finished blocks cached;
              the block + FULL suffix recomputed each step. Vanilla
              schedule.
  fast      — Fast-dLLM: prefix cache + fixed-threshold tau0 parallel
              commit (argmax fallback guarantees progress).
  streaming — OURS: prefix cache + attenuation-guided suffix pruning
              (window w + trailing position token) + dynamic threshold
              tau(t) (Eq. 10) + EOS early exit.

Two execution paths for the per-block denoise loop:

  fused (default) — one jitted, device-resident loop per block: a
      ``lax.while_loop`` carries the token buffer / commit mask / step
      counter on device, with the mask-token ban, confidence, the
      dynamic threshold tau(t), token selection, the straggler finalize
      and EOS early exit all inside the compiled function. The host
      syncs exactly once per block. For the parallel methods the block
      confidence comes from a fused hidden-states -> (confidence, token)
      head path (``apply_model(skip_head=True)`` + row-chunked
      projection), so block logits never materialize as one
      ``(B, K, V)`` array.
  host — the legacy loop: Python drives every denoise step, fetching
      per-step results to numpy and re-uploading the token buffer. Kept
      as the validation oracle (``tests/test_fused_decode.py`` asserts
      token identity) and as the baseline ``benchmarks/bench_decode.py``
      measures against.

Each row of a batch carries its own block index. The fused program
takes the block starts as data and runs a batch's denoise steps over the
widest query region among its rows (the earliest row's), so rows at
different blocks share one program: its jit key is (batch, query width)
for the methods that mix (``DiffusionDecoder.mixes_blocks``), and the
other methods keep every row of a batch at one block.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import schedule as sched
from repro.core.suffix import suffix_query_region
from repro.kernels import ops as kops
from repro.models.config import ATTN, ATTN_LOCAL, MOE, ModelConfig
from repro.models.model import (apply_model, cache_take_rows, head_logits,
                                head_weight, init_cache)
from repro.obs.telemetry import CONF_BUCKETS, BlockStats
from repro.obs.trace import span

METHODS = ("vanilla", "dkv", "prefix", "fast", "streaming")


def round_up_blocks(max_tokens: int, block_size: int) -> int:
    """Generation-length bucket for a request: next block multiple.
    Both serving modes MUST bucket identically (continuous/batch token
    identity depends on it), so this is the single definition."""
    return -(-max_tokens // block_size) * block_size


def eos_truncate(gen: np.ndarray, eos_id: int):
    """Canonical EOS policy for a generated row: the first EOS ends the
    output and the tail is EOS-filled. Returns ``(tokens, n_generated)``
    — the single definition shared by ``row_output`` and the serving
    scheduler's completion builder."""
    eos_pos = np.where(gen == eos_id)[0]
    n = int(eos_pos[0]) if len(eos_pos) else len(gen)
    if len(eos_pos):
        gen = gen.copy()
        gen[eos_pos[0]:] = eos_id
    return gen, n


@dataclasses.dataclass(frozen=True)
class DecodeConfig:
    method: str = "streaming"
    gen_len: int = 256
    block_size: int = 32
    steps_per_block: int = 0       # 0 -> block_size (one token per step)
    tau0: float = 0.9              # base confidence threshold
    alpha: float = 0.3             # Eq. 10 adaptation strength
    window: int = 96               # suffix tokens kept (streaming); -1=full
    trailing_position: bool = True
    early_exit: bool = True
    use_kernels: bool = False      # route attention/confidence to Pallas
    fused: bool = True             # device-resident denoise loop (one host
                                   # sync per block); False = legacy host
                                   # loop (per-step transfers)
    # Beyond-paper (EXPERIMENTS.md §Perf HC1): freeze the pruned-suffix
    # KV at the block-refresh step and reuse it across the block's
    # denoise iterations (DualCache-inspired). Steps then query only the
    # K block tokens instead of K + w + 1 — ~4x less step compute at the
    # paper's config. The suffix KV is one refresh stale within a block
    # (same approximation class as the prefix cache itself).
    frozen_suffix: bool = False
    # Cross-request prefix KV reuse (repro.cache): the prompt KV is
    # computed once at prefill by chunk-causal passes (chunk i attends
    # to chunks 0..i only, bidirectional within the chunk) so each
    # chunk's KV is content-addressable and shareable across requests;
    # block refreshes then rewrite only the generated region and attend
    # to the frozen prompt KV. The prompt no longer sees the masked
    # region (same approximation class as Fast-dLLM's prefix cache);
    # cached vs cold prefill stays bit-identical by construction.
    prefix_cache: bool = False
    cache_chunk: int = 16          # prompt chunk size for repro.cache

    def __post_init__(self):
        assert self.method in METHODS, self.method
        assert self.gen_len % self.block_size == 0
        assert self.cache_chunk > 0
        # the frozen-suffix refresh writes position-indexed over the
        # whole buffer with nothing cached-valid; combining it with a
        # frozen prompt region needs a third refresh variant — out of
        # scope (EXPERIMENTS.md §Prefix caching)
        assert not (self.prefix_cache and self.frozen_suffix), \
            "prefix_cache and frozen_suffix are mutually exclusive"

    @property
    def effective_window(self) -> int:
        if self.method == "streaming":
            return self.window
        return -1                   # baselines see the full suffix

    @property
    def parallel(self) -> bool:
        return self.method in ("fast", "streaming")


@dataclasses.dataclass
class DecodeState:
    """Resumable decode progress for a batch of rows, each at a block
    boundary of its own (``blocks``). Produced by
    ``DiffusionDecoder.prefill`` and advanced one diffusion block per
    row at a time by ``decode_block`` — the host-side contract the
    continuous-batching scheduler (``repro.serving``) is built on:
    between any two blocks the scheduler may harvest finished rows,
    compact the batch, or merge other requests' rows into it."""
    x: np.ndarray                     # (B, T) tokens; mask id where open
    committed: np.ndarray             # (B, T) bool
    done: np.ndarray                  # (B,) early-exited rows
    prompt_len: int
    n_blocks: int
    blocks: Optional[np.ndarray] = None   # (B,) next block of each row
    cache: Any = None
    valid_mask: Optional[np.ndarray] = None    # dkv only: (B, T) bool
    cached_mask: Optional[np.ndarray] = None   # dkv only: (B, T) bool
    prefix_hit_tokens: Optional[np.ndarray] = None  # prefix_cache: (B,)
    nfe: int = 0
    q_tokens: int = 0
    kv_tokens: int = 0
    steps_per_block: list = dataclasses.field(default_factory=list)
    early_exits: int = 0
    host_syncs: int = 0               # blocking device->host fetch points
    logit_syncs: int = 0              # of those, full (B, K, V) logit copies
    prefill_time: float = 0.0
    decode_time: float = 0.0
    # per-block dynamics (repro.obs.telemetry.BlockStats): appended by
    # every decode_block call — harvested from the SAME host sync that
    # returns the block's tokens, so telemetry never adds a sync. The
    # serving scheduler drains this list after each call; standalone
    # decoder users read it off the finished state.
    block_stats: list = dataclasses.field(default_factory=list)

    def __post_init__(self):
        if self.blocks is None:
            self.blocks = np.zeros((self.batch,), np.int32)

    @property
    def batch(self) -> int:
        return self.x.shape[0]

    @property
    def total_len(self) -> int:
        return self.x.shape[1]

    @property
    def live(self) -> np.ndarray:
        """(B,) rows that still have a block to decode."""
        return ~self.done & (self.blocks < self.n_blocks)

    @property
    def block_idx(self) -> int:
        """The earliest live row's next block (every row's, once none
        is live). Assigning an int puts every row at that block."""
        live = self.live
        return int(self.blocks[live].min() if live.any()
                   else self.blocks.max())

    @block_idx.setter
    def block_idx(self, b: int) -> None:
        self.blocks = np.full((self.batch,), b, np.int32)

    @property
    def finished(self) -> bool:
        return not self.live.any()

    def row_finished(self, b: int) -> bool:
        return not self.live[b]


@dataclasses.dataclass
class GenerateResult:
    tokens: np.ndarray             # (B, gen_len) committed tokens
    nfe: int                       # model forward evaluations
    steps_per_block: list
    wall_time: float
    query_tokens_processed: int    # sum of query lengths over all NFEs
    kv_tokens_attended: int        # sum of (kv length * query len) proxy
    tokens_generated: int          # non-EOS tokens (paper's TPS metric)
    early_exits: int
    prefill_time: float = 0.0
    host_syncs: int = 0
    logit_syncs: int = 0

    @property
    def tokens_per_nfe(self) -> float:
        return self.tokens_generated / max(self.nfe, 1)


class DiffusionDecoder:
    """Block diffusion decoder: host scheduler over compiled step fns
    (legacy) or one compiled device-resident loop per block (fused)."""

    def __init__(self, cfg: ModelConfig, params, dcfg: DecodeConfig,
                 mesh=None, data_axes=("data",), executor=None,
                 prompt_cache=None):
        self.cfg = cfg
        self.dcfg = dcfg
        self.executor = executor
        # cross-request chunk store (repro.cache.PrefixKVCache). May be
        # None even in prefix_cache mode: the chunk-aligned prefill then
        # still runs (and the tail refresh still reuses the prompt KV
        # within the request) but nothing is shared across requests.
        self.prompt_cache = prompt_cache
        if dcfg.prefix_cache:
            assert all(s.mixer in (ATTN, ATTN_LOCAL) for s in cfg.layout), \
                ("prefix_cache needs an attention-only layout (recurrent "
                 "states have no chunkable time axis)")
            if prompt_cache is not None:
                assert prompt_cache.chunk_tokens == dcfg.cache_chunk, \
                    (prompt_cache.chunk_tokens, dcfg.cache_chunk)
        if executor is not None:
            # the placement layer owns the placed params and the mesh;
            # a decoder bound to an executor never touches raw params
            self.params = executor.params
            self.mesh = executor.mesh
            self.data_axes = executor.data_axes
        else:
            self.params = params
            self.mesh = mesh
            self.data_axes = data_axes
        self._fns: Dict[Any, Any] = {}

    # ----------------------------------------------- placement boundary

    def _put_batch(self, arr):
        """Host -> device for a gang-shaped array (dim 0 = batch):
        data-axis sharded via the executor, plain upload without one."""
        if self.executor is None:
            return jnp.asarray(arr)
        return self.executor.put_batch(arr)

    def _alloc_cache(self, batch: int, total_len: int):
        if self.executor is None:
            return init_cache(self.cfg, batch, total_len)
        return self.executor.init_cache(batch, total_len)

    # ------------------------------------------------------ shared pieces

    def _head(self, p):
        return head_weight(self.cfg, p)

    def _conf_from_hidden(self, p, h_blk):
        """Fused head path (parallel methods): hidden (B, K, d) ->
        (conf (B, K), toks (B, K)) without a monolithic (B, K, V)
        logits array. Kernel route when use_kernels."""
        cfg = self.cfg
        with jax.named_scope("head_confidence"):
            if self.dcfg.use_kernels:
                return kops.head_confidence_argmax(
                    h_blk, self._head(p), mask_id=cfg.mask_token_id,
                    logit_softcap=cfg.logit_softcap)
            return sched.head_confidence_and_tokens(
                h_blk, self._head(p), mask_id=cfg.mask_token_id,
                logit_softcap=cfg.logit_softcap)

    def _conf_from_logits(self, blk_logits):
        """Full-vocab path (fixed-schedule methods): ban [MASK], Eq. 4."""
        with jax.named_scope("head_confidence"):
            blk = blk_logits.astype(jnp.float32)
            blk = blk.at[..., self.cfg.mask_token_id].set(-1e30)
            return sched.confidence_and_tokens(blk)

    # ------------------------------------------------------ jitted steps

    def _encode_fn(self):
        if "encode" not in self._fns:
            uk = self.dcfg.use_kernels

            def encode(p, toks, pos):
                return apply_model(self.cfg, p, tokens=toks, positions=pos,
                                   use_kernels=uk).logits
            self._fns["encode"] = jax.jit(encode)
        return self._fns["encode"]

    def _prefill_fn(self):
        if "prefill" not in self._fns:
            uk = self.dcfg.use_kernels

            def prefill(p, toks, pos, cache):
                out = apply_model(self.cfg, p, tokens=toks, positions=pos,
                                  mode="encode", cache=cache, use_kernels=uk)
                c = out.cache
                if self.executor is not None:
                    # keep pooled buffers sharding-canonical (see the
                    # matching constraint in the fused fn)
                    c = self.executor.constrain_cache(
                        c, toks.shape[0], toks.shape[1])
                return c, out.kv_valid
            self._fns["prefill"] = jax.jit(prefill)
        return self._fns["prefill"]

    def _refresh_fn(self):
        """Block-start step (paper §3.3): one pass over
        [prefix || current block || (pruned) suffix] that BOTH produces
        the block logits and refreshes the prefix KV cache. Computing the
        prefix KV in the presence of the masked region matches the
        training distribution — a prompt-only prefill does not (it
        measurably degrades small models; see tests/test_decoder.py)."""
        if "refresh" not in self._fns:
            uk = self.dcfg.use_kernels

            def refresh(p, toks, pos, cache, *, upto):
                out = apply_model(self.cfg, p, tokens=toks, positions=pos,
                                  mode="encode", cache=cache,
                                  cache_upto=upto, use_kernels=uk)
                return out.logits, out.cache
            self._fns["refresh"] = jax.jit(
                refresh, static_argnames=("upto",))
        return self._fns["refresh"]

    def _refresh_ct_fn(self):
        """Parallel-method refresh: same pass, but skip_head + the fused
        head path so only (conf, toks) for the block leave the jit."""
        if "refresh_ct" not in self._fns:
            uk, K = self.dcfg.use_kernels, self.dcfg.block_size

            def refresh_ct(p, toks, pos, cache, *, upto):
                out = apply_model(self.cfg, p, tokens=toks, positions=pos,
                                  mode="encode", cache=cache,
                                  cache_upto=upto, skip_head=True,
                                  use_kernels=uk)
                c, t = self._conf_from_hidden(p, out.logits[:, upto:upto + K])
                return c, t, out.cache
            self._fns["refresh_ct"] = jax.jit(
                refresh_ct, static_argnames=("upto",))
        return self._fns["refresh_ct"]

    def _step_fn(self):
        if "step" not in self._fns:
            uk = self.dcfg.use_kernels

            def step(p, toks, pos, cache, kv_valid):
                out = apply_model(self.cfg, p, tokens=toks, positions=pos,
                                  mode="step", cache=cache, kv_valid=kv_valid,
                                  mesh=self.mesh, data_axes=self.data_axes,
                                  use_kernels=uk)
                return out.logits
            self._fns["step"] = jax.jit(step)
        return self._fns["step"]

    def _step_ct_fn(self):
        if "step_ct" not in self._fns:
            uk, K = self.dcfg.use_kernels, self.dcfg.block_size

            def step_ct(p, toks, pos, cache, kv_valid):
                out = apply_model(self.cfg, p, tokens=toks, positions=pos,
                                  mode="step", cache=cache, kv_valid=kv_valid,
                                  mesh=self.mesh, data_axes=self.data_axes,
                                  skip_head=True, use_kernels=uk)
                return self._conf_from_hidden(p, out.logits[:, :K])
            self._fns["step_ct"] = jax.jit(step_ct)
        return self._fns["step_ct"]

    def _chunk_prefill_fn(self):
        """Prefix-cache prefill pass: one prompt chunk attending to
        [cached prompt prefix || self] (chunk-causal across chunks,
        bidirectional within). The chunk offset arrives as the dynamic
        ``kv_valid`` array, so ONE compiled variant serves every chunk
        of every prompt at a given (batch, chunk) shape. skip_head: the
        prefill only needs KV, never logits."""
        if "chunk_prefill" not in self._fns:
            uk = self.dcfg.use_kernels

            def chunk_prefill(p, toks, pos, cache, kv_valid):
                out = apply_model(self.cfg, p, tokens=toks, positions=pos,
                                  mode="append", cache=cache,
                                  kv_valid=kv_valid, skip_head=True,
                                  use_kernels=uk)
                c = out.cache
                if self.executor is not None:
                    # keep pooled buffers sharding-canonical (see the
                    # matching constraint in the fused fn)
                    c = self.executor.constrain_cache(
                        c, toks.shape[0], toks.shape[1])
                return c
            self._fns["chunk_prefill"] = jax.jit(chunk_prefill)
        return self._fns["chunk_prefill"]

    def _tail_refresh_fn(self):
        """Prefix-cache block refresh (fixed-schedule methods): a pass
        over [generated prefix || query region] ONLY — the prompt KV
        was computed at prefill (possibly assembled from the
        cross-request store) and is attended via ``kv_valid`` instead
        of being recomputed every block."""
        if "tail_refresh" not in self._fns:
            uk = self.dcfg.use_kernels

            def tail_refresh(p, toks, pos, cache, kv0):
                out = apply_model(self.cfg, p, tokens=toks, positions=pos,
                                  mode="append", cache=cache, kv_valid=kv0,
                                  use_kernels=uk)
                return out.logits, out.cache
            self._fns["tail_refresh"] = jax.jit(tail_refresh)
        return self._fns["tail_refresh"]

    def _tail_refresh_ct_fn(self):
        """Parallel-method tail refresh: same pass, fused head path so
        only (conf, toks) for the block leave the jit. ``upto`` is the
        in-pass offset of the current block (= generated prefix len)."""
        if "tail_refresh_ct" not in self._fns:
            uk, K = self.dcfg.use_kernels, self.dcfg.block_size

            def tail_refresh_ct(p, toks, pos, cache, kv0, *, upto):
                out = apply_model(self.cfg, p, tokens=toks, positions=pos,
                                  mode="append", cache=cache, kv_valid=kv0,
                                  skip_head=True, use_kernels=uk)
                c, t = self._conf_from_hidden(p, out.logits[:, upto:upto + K])
                return c, t, out.cache
            self._fns["tail_refresh_ct"] = jax.jit(
                tail_refresh_ct, static_argnames=("upto",))
        return self._fns["tail_refresh_ct"]

    def _append_fn(self):
        if "append" not in self._fns:
            uk = self.dcfg.use_kernels

            def append(p, toks, pos, cache, kv_valid):
                out = apply_model(self.cfg, p, tokens=toks, positions=pos,
                                  mode="append", cache=cache,
                                  kv_valid=kv_valid, use_kernels=uk)
                return out.cache, out.kv_valid
            self._fns["append"] = jax.jit(append)
        return self._fns["append"]

    def _frozen_refresh_ct_fn(self):
        """HC1 (frozen suffix, parallel methods only): block-start pass
        over [prefix || query] that writes ALL KV position-indexed into
        a T-sized buffer — including the pruned-suffix and trailing mask
        tokens — so steps can attend to frozen suffix KV and query only
        the block."""
        if "frozen_refresh_ct" not in self._fns:
            uk, K = self.dcfg.use_kernels, self.dcfg.block_size

            def frozen_refresh_ct(p, toks, pos, cache, *, upto):
                B = toks.shape[0]
                out = apply_model(self.cfg, p, tokens=toks, positions=pos,
                                  mode="append", cache=cache,
                                  kv_valid=jnp.zeros((B,), jnp.int32),
                                  append_at=pos,
                                  cache_positions=None, cache_upto=upto,
                                  skip_head=True, use_kernels=uk)
                c, t = self._conf_from_hidden(p, out.logits[:, upto:upto + K])
                return c, t, out.cache
            self._fns["frozen_refresh_ct"] = jax.jit(
                frozen_refresh_ct, static_argnames=("upto",))
        return self._fns["frozen_refresh_ct"]

    def _dkv_step_fn(self):
        if "dkv" not in self._fns:
            uk = self.dcfg.use_kernels

            def dkv(p, toks, pos, cache, valid_mask, mix):
                out = apply_model(self.cfg, p, tokens=toks, positions=pos,
                                  mode="append", cache=cache,
                                  kv_valid=valid_mask, append_at=pos,
                                  self_kv_mix=mix, use_kernels=uk)
                return out.logits, out.cache
            self._fns["dkv"] = jax.jit(dkv)
        return self._fns["dkv"]

    # ------------------------------------------------------ resumable API

    @property
    def batch_invariant(self) -> bool:
        """True when a row's result depends on no other row — the
        property the serving scheduler relies on to compact, pad, merge
        and steal rows without changing generations. Holds for every
        method except dkv, whose step-level KV freezing accumulates
        ulp-level drift across appends under batch reshaping
        (tests/test_serving.py).

        What it does not promise: a different gang *size* rounds the
        matmuls differently (``chip_smoke.gang_witness``: ~1e-6 relative
        on the logits in float32, on XLA:CPU and TPU; ~4e-3 on the TPU
        at the default precision, whose bf16 passes round differently
        per shape). Tokens stay equal unless an argmax or a threshold
        sits within that rounding of a tie: true of the CPU suite's
        models, not of random LLaDA-8B weights on the TPU, where 1.6% of
        argmaxes flip and gangs of 4 and of 2 gave different tokens for
        3 of 8 requests. There compaction, pow2 padding, merges,
        stealing and the shadow auditor's bit-for-bit compare are exact
        only up to such flips. At a fixed gang size a row's logits are
        bit-identical whatever its gang mates."""
        return self.dcfg.method != "dkv"

    @property
    def mixes_blocks(self) -> bool:
        """True when one ``decode_block`` call may advance rows that
        stand at different block indexes — the property the scheduler
        reads to merge gangs across blocks. Holds for the cached methods
        whose block-start refresh runs over the whole sequence under a
        per-row key mask (prefix, fast, streaming) in the fused block
        program, on a layout whose tokens interact only through
        attention: a recurrent state or a capacity-bound MoE router
        would see the masked tokens. The host loop, the prefix-cache and
        frozen-suffix refreshes, dkv and vanilla keep every row of a
        batch at one block."""
        d = self.dcfg
        return (d.fused and d.method in ("prefix", "fast", "streaming")
                and not d.prefix_cache and not d.frozen_suffix
                and all(s.mixer in (ATTN, ATTN_LOCAL) and s.ffn != MOE
                        for s in self.cfg.layout))

    @property
    def cache_carries_state(self) -> bool:
        """True when the KV buffer holds state a block refresh does NOT
        rewrite — dkv's position-indexed cache, or the prefix-cached
        prompt region. Compaction/merge must then *gather* cache rows;
        any other method adopts whatever right-shaped pool buffer it is
        handed, because the next refresh rewrites it wholesale."""
        return self.dcfg.method == "dkv" or (
            self.dcfg.prefix_cache and self.dcfg.method != "vanilla")

    def jit_cache_size(self) -> int:
        """Total compiled-variant count across this decoder's step fns —
        the serving benchmark asserts it stays bounded by shape buckets
        (no per-request recompilation after warmup)."""
        total = 0
        for f in self._fns.values():
            size = getattr(f, "_cache_size", None)
            if callable(size):
                total += size()
        return total

    def prefill(self, prompt: np.ndarray,
                cache: Any = None) -> DecodeState:
        """Admit a batch of prompts: allocate (or adopt a pooled) KV
        buffer and, for dkv, run the full-sequence prefill pass. The
        returned state sits at block 0 ready for ``decode_block``."""
        cfg, d = self.cfg, self.dcfg
        B, P = prompt.shape
        L, K = d.gen_len, d.block_size
        T = P + L
        x = np.full((B, T), cfg.mask_token_id, np.int32)
        x[:, :P] = prompt
        committed = np.zeros((B, T), bool)
        committed[:, :P] = True
        state = DecodeState(x=x, committed=committed,
                            done=np.zeros((B,), bool), prompt_len=P,
                            n_blocks=L // K)
        if d.method == "vanilla":
            return state
        if cache is not None:
            # a pooled buffer from the wrong shape bucket would only
            # surface later as a cryptic XLA shape error inside the
            # refresh fn — check the batch/length dims up front
            tail = jax.tree.leaves(cache["tail"])
            scan = jax.tree.leaves(cache["scan"])
            if tail:
                assert tail[0].shape[0] == B, (tail[0].shape, B)
                if tail[0].ndim == 4:      # attention KV: (B, T, H, D)
                    assert tail[0].shape[1] == T, (tail[0].shape, T)
            elif scan:                     # scan-stacked: (reps, B, ...)
                assert scan[0].shape[1] == B, (scan[0].shape, B)
                if scan[0].ndim == 5:
                    assert scan[0].shape[2] == T, (scan[0].shape, T)
            state.cache = cache
        else:
            state.cache = self._alloc_cache(B, T)
        if d.prefix_cache:
            # chunk-aligned prompt prefill: assemble the longest
            # cross-request cached prefix, compute only the novel tail.
            # dkv rides the same path — its position-indexed masks mark
            # the prompt valid/frozen exactly as the full-sequence
            # prefill would, but the masked-region pass is skipped
            # (those KV entries were never valid anyway).
            self.prime_prompt_kv(state)
            if d.method == "dkv":
                state.valid_mask = np.zeros((B, T), bool)
                state.valid_mask[:, :P] = True
                state.cached_mask = state.valid_mask.copy()
            return state
        if d.method == "dkv":
            # dKV prefill: one full-sequence pass (prompt + masks),
            # position-indexed cache; only the prompt KV is valid.
            tp0 = time.perf_counter()
            pos = self._put_batch(
                np.broadcast_to(np.arange(T, dtype=np.int32)[None], (B, T)))
            state.cache, _ = self._prefill_fn()(self.params,
                                                self._put_batch(x), pos,
                                                state.cache)
            jax.block_until_ready(jax.tree.leaves(state.cache)[0])
            state.prefill_time = time.perf_counter() - tp0
            state.nfe += 1
            state.host_syncs += 1
            state.q_tokens += B * T
            state.kv_tokens += B * T * T
            state.valid_mask = np.zeros((B, T), bool)
            state.valid_mask[:, :P] = True
            state.cached_mask = state.valid_mask.copy()
        return state

    def prime_prompt_kv(self, state: DecodeState) -> DecodeState:
        """Prefix-cache prompt prefill (the chunk-aligned path): look
        up the longest cached prefix per row, copy/assemble its KV into
        the gang cache, run the model only over the uncached chunks
        plus the unaligned remainder, and publish the freshly computed
        chunks back to the store. Also the re-prime hook for resumed
        (preempted) states, whose parked cache was dropped — their own
        chunks are usually still in the store, so resume costs O(tail).

        Exactness: an assembled chunk carries the bytes its original
        prefill pass wrote, and a computed chunk sees only [assembled
        prefix || its own tokens] — so cached and cold prefill are
        bit-identical by construction (tests/test_cache.py)."""
        d = self.dcfg
        assert d.prefix_cache and d.method != "vanilla"
        assert state.cache is not None
        from repro.cache import slicing
        B, P = state.batch, state.prompt_len
        C = d.cache_chunk
        n_chunks = P // C
        store = self.prompt_cache
        tp0 = time.perf_counter()
        hits: list = [[] for _ in range(B)]
        if store is not None and n_chunks:
            hits = [store.match(state.x[b, :P]) for b in range(B)]
        try:
            # the gang computes chunks from the common hit depth: rows
            # with deeper hits get those chunks recomputed in-batch
            # (bit-equal to their stored values — batch invariance),
            # rows at the min start there. The scheduler's hit-aware
            # admission grouping keeps gangs hit-homogeneous so the min
            # is rarely pessimistic.
            n_hit = min(len(h) for h in hits)
            if n_hit:
                state.cache = slicing.assemble_batch(
                    state.cache,
                    [[n.payload for n in hits[b][:n_hit]]
                     for b in range(B)])
            fn = self._chunk_prefill_fn()
            spans = [(c * C, (c + 1) * C) for c in range(n_hit, n_chunks)]
            if P > n_chunks * C:
                spans.append((n_chunks * C, P))   # unaligned remainder
            for t0, t1 in spans:
                pos = np.broadcast_to(
                    np.arange(t0, t1, dtype=np.int32)[None], (B, t1 - t0))
                state.cache = fn(self.params,
                                 self._put_batch(state.x[:, t0:t1]),
                                 self._put_batch(pos), state.cache,
                                 self._put_batch(np.full((B,), t0,
                                                         np.int32)))
                state.nfe += 1
                state.q_tokens += B * (t1 - t0)
                state.kv_tokens += B * (t1 - t0) * t1
            if spans:
                jax.block_until_ready(jax.tree.leaves(state.cache)[0])
                state.host_syncs += 1
            # publish the chunks this gang computed (above what each
            # row already had cached); rows repeating an earlier row's
            # prompt — pad lanes replicate row 0 — skip the extraction
            # entirely, the store would dedup their nodes anyway
            if store is not None:
                seen: set = set()
                for b in range(B):
                    key = state.x[b, :P].tobytes()
                    start = len(hits[b])
                    if n_chunks > start and key not in seen:
                        kvs = [slicing.extract_row(state.cache, b,
                                                   c * C, (c + 1) * C)
                               for c in range(start, n_chunks)]
                        store.insert(state.x[b, :P], start, kvs,
                                     parent_chain=hits[b])
                    seen.add(key)
        finally:
            # pins must die with this call even if a prefill pass
            # raises — a leaked pin makes its chunk unevictable forever
            if store is not None:
                for h in hits:
                    store.unpin(h)
        state.prefix_hit_tokens = np.full((B,), n_hit * C, np.int32)
        state.prefill_time += time.perf_counter() - tp0
        return state

    def take_rows(self, state: DecodeState, rows, cache: Any = None,
                  alloc_cache: bool = True) -> DecodeState:
        """Extract rows into a standalone state (batch compaction /
        preemption). For dkv the KV rows are gathered (its cache carries
        across blocks); every other method rewrites the cache at the
        next block refresh, so any right-shaped buffer — typically a
        reused one from the PrefixKVPool — serves as the new backing.
        ``alloc_cache=False`` defers the backing buffer entirely (a
        preempted state parked off-slot holds no KV memory); the caller
        must attach one before the next ``decode_block``."""
        rows = list(rows)
        d = self.dcfg
        sub = DecodeState(
            x=state.x[rows].copy(), committed=state.committed[rows].copy(),
            done=state.done[rows].copy(), prompt_len=state.prompt_len,
            n_blocks=state.n_blocks, blocks=state.blocks[rows].copy(),
            steps_per_block=list(state.steps_per_block))
        if state.prefix_hit_tokens is not None:
            sub.prefix_hit_tokens = state.prefix_hit_tokens[rows].copy()
        if d.method == "dkv":
            # cache_take_rows *gathers* (XLA copies) — the sub-state
            # must never alias buffers of the gang it left: the gang's
            # next fused call may donate them, and a pooled buffer may
            # be handed to another gang while this state is parked
            sub.cache = cache_take_rows(state.cache, rows)
            sub.valid_mask = state.valid_mask[rows].copy()
            sub.cached_mask = state.cached_mask[rows].copy()
        elif self.cache_carries_state:
            # prefix_cache: the prompt KV region must travel with the
            # rows (the tail refresh never rewrites it). A parked state
            # (alloc_cache=False) drops it instead — prime_prompt_kv
            # re-primes on resume, usually from the store.
            if alloc_cache or cache is not None:
                sub.cache = cache_take_rows(state.cache, rows)
        elif d.method != "vanilla":
            if cache is not None:
                sub.cache = cache
            elif alloc_cache:
                sub.cache = self._alloc_cache(len(rows), state.total_len)
        return sub

    def merge_rows(self, parts, cache: Any = None) -> DecodeState:
        """Fuse rows from several states of one shape bucket into one
        state (the scheduler's cross-gang merge). ``parts`` is a list of
        ``(state, rows)``; the rows may stand at different block indexes
        where ``mixes_blocks`` holds, else all at one. Requires
        ``batch_invariant`` (per-row results don't depend on batching)
        and excludes dkv, whose cache carries across blocks; for every
        other cached method the next block refresh rewrites the cache,
        so any right-shaped buffer (``cache``) serves as backing."""
        assert self.batch_invariant and self.dcfg.method != "dkv"
        ref = parts[0][0]
        for st, _ in parts[1:]:
            assert (st.prompt_len, st.n_blocks) == \
                (ref.prompt_len, ref.n_blocks), \
                "cross-gang merge requires one shape bucket"
        blocks = np.concatenate([st.blocks[rows] for st, rows in parts])
        assert self.mixes_blocks or len(set(blocks.tolist())) == 1, \
            "this method decodes a batch at one block index"
        sub = DecodeState(
            x=np.concatenate([st.x[rows] for st, rows in parts]),
            committed=np.concatenate(
                [st.committed[rows] for st, rows in parts]),
            done=np.concatenate([st.done[rows] for st, rows in parts]),
            prompt_len=ref.prompt_len, n_blocks=ref.n_blocks,
            blocks=blocks,
            # per-block step counts diverge across source gangs; keep
            # the elementwise max (metrics-only, like take_rows' copy)
            steps_per_block=[max(vals) for vals in itertools.zip_longest(
                *(st.steps_per_block for st, _ in parts), fillvalue=0)])
        if all(st.prefix_hit_tokens is not None for st, _ in parts):
            sub.prefix_hit_tokens = np.concatenate(
                [st.prefix_hit_tokens[rows] for st, rows in parts])
        if self.cache_carries_state:
            # prefix_cache: gather each part's rows (prompt KV travels)
            # and concatenate along the batch axis (1 for scan-stacked
            # groups, 0 for tail layers — see cache_take_rows)
            gathered = [cache_take_rows(st.cache, rows)
                        for st, rows in parts]
            sub.cache = {
                "scan": jax.tree.map(lambda *xs: jnp.concatenate(xs, 1),
                                     *[g["scan"] for g in gathered]),
                "tail": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                                     *[g["tail"] for g in gathered]),
            }
        elif self.dcfg.method != "vanilla":
            sub.cache = cache if cache is not None \
                else self._alloc_cache(sub.batch, ref.total_len)
        return sub

    def row_output(self, state: DecodeState, b: int):
        """Finalized generation for one row: tokens after the prompt,
        truncated at the first EOS (identical to ``finalize`` row b).
        Returns (tokens (gen_len,), n_generated)."""
        return eos_truncate(state.x[b, state.prompt_len:].copy(),
                            self.cfg.eos_token_id)

    # ------------------------------------------------------ block step

    def decode_block(self, state: DecodeState) -> DecodeState:
        """Decode the next block of every row that has one, each at its
        own block index, and advance those rows to their next block
        boundary (mutates and returns ``state``). No-op on a finished
        state."""
        if state.finished:
            return state
        if self.dcfg.fused:
            return self._decode_block_fused(state)
        return self._decode_block_host(state)

    def _region(self, prompt_len: int, block_idx: int):
        d = self.dcfg
        region = suffix_query_region(
            gen_start=prompt_len, gen_len=d.gen_len,
            block_size=d.block_size, block_idx=block_idx,
            window=d.effective_window if d.trailing_position
            else max(d.effective_window, 0))
        qpos = region.positions                       # (Sq,)
        if not d.trailing_position and region.trailing_pos >= 0:
            qpos = qpos[:-1]
        return region, qpos

    # ------------------------------------------------- fused device loop

    def _fused_fn(self):
        """The device-resident per-block denoise loop: refresh (where the
        method has one) + a ``lax.while_loop`` over denoise steps +
        straggler finalize + EOS early exit, compiled as ONE function.
        Each row's block comes in as data (``bidx_b``), so the program
        is specialized per (method, batch, query width) — and, for the
        methods that keep a batch at one block, per refresh ``prefix``
        (None where ``mixes_blocks``). The host calls it once per block
        and syncs once on its outputs. Its phases carry named scopes,
        so a device trace can tell them apart: ``refresh`` (the
        block-start pass with its confidence and commit),
        ``denoise_step`` (the ``while_loop`` body), ``head_confidence``
        (inside both) and ``finalize`` (straggler fill, early exit)."""
        if "decode_block" in self._fns:
            return self._fns["decode_block"]
        cfg, d = self.cfg, self.dcfg
        eos_id = cfg.eos_token_id   # the [MASK] ban lives in _conf_from_*
        K = d.block_size
        n_blocks = d.gen_len // K
        steps_cap = d.steps_per_block or K
        n_commit = max(1, K // steps_cap)
        uk = d.use_kernels
        parallel = d.parallel
        frozen = d.frozen_suffix and parallel

        def decode_block(p, x, committed, done, cache, qpos_b, bidx_b,
                         valid_mask, cached_mask, *, prefix, pstart):
            B, T = x.shape
            # a row past its last block rides along as a done lane, at
            # its last block (already committed, so nothing changes)
            dead = done | (bidx_b >= n_blocks)
            bstart_b = T - d.gen_len + jnp.minimum(bidx_b, n_blocks - 1) * K
            # a narrower row's spare query slots (-1) stand at its block
            # start; the steps keep them out of every query's keys
            qvalid_b = qpos_b >= 0
            qpos_b = jnp.where(qvalid_b, qpos_b, bstart_b[:, None])

            # Each row's block is picked from the generation region
            # viewed as (B, n_blocks, K, ...) by a one-hot over the block
            # axis: selects and a reduction, where a per-row
            # dynamic_slice would be a gather or scatter that XLA:TPU
            # runs as a serial loop.
            g0 = T - d.gen_len
            onehot = (jnp.arange(n_blocks)[None]
                      == jnp.minimum(bidx_b, n_blocks - 1)[:, None])

            def blocks_of(a):
                g = a[:, g0:g0 + n_blocks * K]
                g = g.reshape((B, n_blocks, K) + a.shape[2:])
                return g, onehot.reshape((B, n_blocks)
                                         + (1,) * (g.ndim - 2))

            def blk(a):
                """Each row's current block of a (B, T, ...) array."""
                g, m = blocks_of(a)
                if a.dtype == jnp.bool_:
                    return jnp.any(g & m, axis=1)
                # one term per row is nonzero: the sum is exact
                return jnp.sum(jnp.where(m, g, jnp.zeros((), a.dtype)),
                               axis=1)

            def set_blk(a, v):
                g, m = blocks_of(a)
                g = jnp.where(m, v[:, None], g)
                return a.at[:, g0:g0 + n_blocks * K].set(
                    g.reshape((B, n_blocks * K) + a.shape[2:]))

            def commit_tokens(x, committed, conf, toks):
                """Eq. 9/fixed-rate selection + token write for one step.
                Mirrors the host loop exactly (all rows participate; only
                the loop CONDITION excludes early-exited rows)."""
                blk_committed = blk(committed)
                blk_masked = ~blk_committed
                if parallel:
                    if d.method == "streaming":
                        r_mask = jnp.mean(blk_masked.astype(jnp.float32),
                                          axis=1)
                        tau = sched.dynamic_threshold(d.tau0, d.alpha, r_mask)
                    else:
                        tau = jnp.full((B,), d.tau0, jnp.float32)
                    commit = sched.select_tokens(conf, blk_masked, tau)
                else:
                    commit = sched.fixed_rate_select(conf, blk_masked,
                                                     n_commit)
                x = set_blk(x, jnp.where(commit, toks, blk(x)))
                committed = set_blk(committed, blk_committed | commit)
                return x, committed, commit

            vsums = jnp.zeros((steps_cap,), jnp.int32)  # dkv kv-size trace
            # telemetry carries (repro.obs): commits per device step and
            # a confidence histogram of committed tokens — scatter-adds
            # inside the compiled loop, harvested with the block's other
            # outputs, so they cost zero extra host syncs. Only rows
            # live at block start count (done rows' lanes are padding).
            counts = jnp.zeros((steps_cap,), jnp.int32)
            hist = jnp.zeros((CONF_BUCKETS,), jnp.int32)
            # calibration accumulators (repro.obs.audit): per-lane
            # commit-time confidence, plus the last step's confidence
            # map so straggler fills record the value they were forced
            # at. Carried through the while_loop and returned with the
            # block's other outputs — same single host sync.
            cconf = jnp.zeros((B, K), jnp.float32)
            lconf = jnp.zeros((B, K), jnp.float32)
            live = ~dead[:, None]

            def tally(counts, hist, step, commit, conf):
                act = commit & live
                counts = counts.at[step].add(
                    jnp.sum(act.astype(jnp.int32)))
                b_idx = jnp.clip((conf * CONF_BUCKETS).astype(jnp.int32),
                                 0, CONF_BUCKETS - 1)
                hist = hist.at[b_idx.ravel()].add(
                    act.ravel().astype(jnp.int32))
                return counts, hist

            def loop_open(committed, step):
                return (step < steps_cap) & jnp.any(~blk(committed) & live)

            if d.method == "vanilla":
                pos_T = jnp.broadcast_to(jnp.arange(T)[None], (B, T))

                def cond(c):
                    committed, step = c[1], c[2]
                    return loop_open(committed, step)

                @jax.named_scope("denoise_step")
                def body(c):
                    x, committed, step, _, counts, hist, cconf, _ = c
                    out = apply_model(cfg, p, tokens=x, positions=pos_T,
                                      use_kernels=uk)
                    conf, toks = self._conf_from_logits(blk(out.logits))
                    x, committed, commit = commit_tokens(
                        x, committed, conf, toks)
                    counts, hist = tally(counts, hist, step, commit, conf)
                    cconf = jnp.where(commit, conf, cconf)
                    return (x, committed, step + 1, toks, counts, hist,
                            cconf, conf)

                init = (x, committed, jnp.int32(0),
                        jnp.zeros((B, K), jnp.int32), counts, hist,
                        cconf, lconf)
                x, committed, steps, toks, counts, hist, cconf, lconf = \
                    jax.lax.while_loop(cond, body, init)

            elif d.method == "dkv":
                def cond(c):
                    _, committed, step = c[0], c[1], c[2]
                    return loop_open(committed, step)

                @jax.named_scope("denoise_step")
                def body(c):
                    x, committed, step, _, cache, valid_mask, cached_mask, \
                        vsums, counts, hist, cconf, _ = c
                    q_toks = jnp.take_along_axis(x, qpos_b, axis=1)
                    mix = jnp.take_along_axis(cached_mask, qpos_b, axis=1)
                    out = apply_model(cfg, p, tokens=q_toks,
                                      positions=qpos_b, mode="append",
                                      cache=cache, kv_valid=valid_mask,
                                      append_at=qpos_b, self_kv_mix=mix,
                                      use_kernels=uk)
                    conf, toks = self._conf_from_logits(out.logits[:, :K])
                    # tokens committed earlier (whose fresh KV this step
                    # was decoded-input based) are now frozen
                    newly = committed & ~cached_mask
                    cached_mask = cached_mask | newly
                    valid_mask = valid_mask | newly
                    vsums = vsums.at[step].set(
                        jnp.sum(valid_mask.astype(jnp.int32)) // B)
                    x, committed, commit = commit_tokens(
                        x, committed, conf, toks)
                    counts, hist = tally(counts, hist, step, commit, conf)
                    cconf = jnp.where(commit, conf, cconf)
                    return (x, committed, step + 1, toks, out.cache,
                            valid_mask, cached_mask, vsums, counts, hist,
                            cconf, conf)

                init = (x, committed, jnp.int32(0),
                        jnp.zeros((B, K), jnp.int32), cache,
                        valid_mask, cached_mask, vsums, counts, hist,
                        cconf, lconf)
                (x, committed, steps, toks, cache, valid_mask, cached_mask,
                 vsums, counts, hist, cconf, lconf) = \
                    jax.lax.while_loop(cond, body, init)

            else:
                # prefix / fast / streaming: block-start refresh (paper
                # §3.3) outside the loop — it has a different query shape
                # and is the only step that writes the cache.
                with jax.named_scope("refresh"):
                    if prefix is None:
                        # one width for every row: the whole sequence at
                        # its absolute positions, each row keeping as
                        # keys just its own prefix and query region, so
                        # each kept query attends what it would attend
                        # in a pass over [prefix || query region] alone
                        pos_T = jnp.broadcast_to(
                            jnp.arange(T, dtype=jnp.int32)[None], (B, T))
                        keys = (pos_T < bstart_b[:, None]) | jnp.any(
                            pos_T[:, :, None] == qpos_b[:, None, :],
                            axis=2)
                        out = apply_model(cfg, p, tokens=x, positions=pos_T,
                                          mode="encode", cache=cache,
                                          self_mask=keys, skip_head=True,
                                          use_kernels=uk)
                        blk_out = blk(out.logits)
                        if not parallel:
                            blk_out = head_logits(cfg, p, blk_out)
                        valid = bstart_b
                    else:
                        # one block for the whole batch, at ``prefix``.
                        # With prefix_cache the pass starts at the
                        # prompt boundary (pstart): the prompt KV was
                        # computed at prefill and is attended via
                        # kv_valid, never recomputed.
                        p0 = pstart if d.prefix_cache else 0
                        pref_pos = jnp.broadcast_to(
                            jnp.arange(p0, prefix, dtype=jnp.int32)[None],
                            (B, prefix - p0))
                        full_pos = jnp.concatenate([pref_pos, qpos_b],
                                                   axis=1)
                        full_toks = jnp.take_along_axis(x, full_pos, axis=1)
                        if d.prefix_cache:
                            out = apply_model(
                                cfg, p, tokens=full_toks,
                                positions=full_pos, mode="append",
                                cache=cache,
                                kv_valid=jnp.full((B,), pstart, jnp.int32),
                                skip_head=parallel, use_kernels=uk)
                            valid = bstart_b
                        elif frozen:
                            out = apply_model(
                                cfg, p, tokens=full_toks,
                                positions=full_pos, mode="append",
                                cache=cache,
                                kv_valid=jnp.zeros((B,), jnp.int32),
                                append_at=full_pos, cache_upto=prefix,
                                skip_head=True, use_kernels=uk)
                            valid = jnp.broadcast_to(
                                jnp.arange(T) < prefix, (B, T))
                            valid = valid.at[jnp.arange(B)[:, None],
                                             qpos_b[:, K:]].set(True)
                        else:
                            out = apply_model(
                                cfg, p, tokens=full_toks,
                                positions=full_pos, mode="encode",
                                cache=cache, cache_upto=prefix,
                                skip_head=parallel, use_kernels=uk)
                            valid = bstart_b
                        boff = prefix - p0
                        blk_out = out.logits[:, boff:boff + K]
                    cache = out.cache
                    if parallel:
                        conf, toks = self._conf_from_hidden(p, blk_out)
                    else:
                        conf, toks = self._conf_from_logits(blk_out)
                    x, committed, commit = commit_tokens(x, committed, conf,
                                                         toks)
                    counts, hist = tally(counts, hist, 0, commit, conf)
                    cconf = jnp.where(commit, conf, cconf)
                    lconf = conf

                step_keys = qvalid_b if prefix is None else None

                def cond(c):
                    committed, step = c[1], c[2]
                    return loop_open(committed, step)

                @jax.named_scope("denoise_step")
                def body(c):
                    x, committed, step, _, counts, hist, cconf, _ = c
                    if frozen:
                        bpos = bstart_b[:, None] + jnp.arange(
                            K, dtype=jnp.int32)[None]
                        out = apply_model(cfg, p, tokens=blk(x),
                                          positions=bpos, mode="step",
                                          cache=cache, kv_valid=valid,
                                          mesh=self.mesh,
                                          data_axes=self.data_axes,
                                          skip_head=True, use_kernels=uk)
                    else:
                        q_toks = jnp.take_along_axis(x, qpos_b, axis=1)
                        out = apply_model(cfg, p, tokens=q_toks,
                                          positions=qpos_b, mode="step",
                                          cache=cache, kv_valid=valid,
                                          self_mask=step_keys,
                                          mesh=self.mesh,
                                          data_axes=self.data_axes,
                                          skip_head=parallel,
                                          use_kernels=uk)
                    if parallel:
                        conf, toks = self._conf_from_hidden(
                            p, out.logits[:, :K])
                    else:
                        conf, toks = self._conf_from_logits(
                            out.logits[:, :K])
                    x, committed, commit = commit_tokens(
                        x, committed, conf, toks)
                    counts, hist = tally(counts, hist, step, commit, conf)
                    cconf = jnp.where(commit, conf, cconf)
                    return (x, committed, step + 1, toks, counts, hist,
                            cconf, conf)

                init = (x, committed, jnp.int32(1), toks, counts, hist,
                        cconf, lconf)
                x, committed, steps, toks, counts, hist, cconf, lconf = \
                    jax.lax.while_loop(cond, body, init)

            with jax.named_scope("finalize"):
                # straggler finalize (steps cap reached): commit the last
                # step's argmax — but never overwrite rows that early-exited
                # in a prior block (their tail is EOS-truncated territory)
                blk_x = blk(x)
                fill = ~blk(committed) & live & (steps > 0)
                fill_n = jnp.sum(fill.astype(jnp.int32))
                cconf = jnp.where(fill, lconf, cconf)
                blk_x = jnp.where(fill, toks, blk_x)
                x = set_blk(x, blk_x)
                committed = set_blk(committed, jnp.ones((B, K), jnp.bool_))
                # Early exit (paper §3.3): a block that decoded an EOS makes
                # all *subsequent* blocks skippable for that row.
                if d.early_exit:
                    hit = jnp.any(blk_x == eos_id, axis=1) & ~dead
                    n_hit = jnp.sum(hit.astype(jnp.int32))
                    done = done | hit
                else:
                    n_hit = jnp.int32(0)
            if self.executor is not None:
                # pin the output cache to the canonical placement so a
                # recycled pool buffer is sharding-identical to a fresh
                # one — without this, every (batch, block) shape traces
                # twice (fresh-path at pre-warm, recycled-path at serve)
                cache = self.executor.constrain_cache(
                    cache, x.shape[0], x.shape[1])
            return (x, committed, done, steps, n_hit, cache,
                    valid_mask, cached_mask, vsums, counts, hist, fill_n,
                    cconf)

        # The fused fn consumes and rewrites the whole cache for every
        # cached method, so its input buffer is dead on entry — donate
        # it where the backend honors donation (executor policy),
        # halving peak KV memory per gang. Never for vanilla (cache is
        # an empty pytree) and never for the host-oracle default path.
        donate = (4,) if (self.executor is not None
                          and self.executor.donate_cache
                          and d.method != "vanilla") else ()
        self._fns["decode_block"] = jax.jit(
            decode_block, static_argnames=("prefix", "pstart"),
            donate_argnums=donate)
        return self._fns["decode_block"]

    def _plan(self, state: DecodeState):
        """Where each row decodes in the next call: ``(run, eff, qpos)``
        — the rows before their last block, each such row's block, and
        the query positions of every block that occurs. A done row
        behind the earliest live row rides at that row's block, so the
        query width is always a live row's."""
        n = state.n_blocks
        run = state.blocks < n
        live = run & ~state.done
        b_min = int(state.blocks[live].min())
        eff = np.where(live, state.blocks,
                       np.clip(state.blocks, b_min, n - 1)).astype(np.int32)
        if not self.mixes_blocks:
            assert (eff == b_min).all(), (
                f"method {self.dcfg.method!r} decodes a batch at one block "
                f"index, got {sorted(set(eff.tolist()))}")
        qpos = {b: self._region(state.prompt_len, b)[1]
                for b in set(eff.tolist())}
        return run, eff, qpos

    def _fused_inputs(self, state: DecodeState, plan):
        """Device arguments and static kwargs of the fused fn for the
        next block of ``state``: each row's query positions, padded to
        the widest row's with -1, and each row's block (``n_blocks`` for
        a row past its last)."""
        run, eff, qpos = plan
        d = self.dcfg
        P, K = state.prompt_len, d.block_size
        b_min = int(eff.min())
        qpos_b = np.full((state.batch, len(qpos[b_min])), -1, np.int32)
        for i, b in enumerate(eff.tolist()):
            qpos_b[i, :len(qpos[b])] = qpos[b]
        bidx_b = np.where(run, eff, state.n_blocks).astype(np.int32)
        vm = None if state.valid_mask is None \
            else self._put_batch(state.valid_mask)
        cm = None if state.cached_mask is None \
            else self._put_batch(state.cached_mask)
        args = (self.params, self._put_batch(state.x),
                self._put_batch(state.committed),
                self._put_batch(state.done), state.cache,
                self._put_batch(qpos_b), self._put_batch(bidx_b), vm, cm)
        one_block = not self.mixes_blocks and d.method in ("prefix", "fast",
                                                           "streaming")
        static = dict(prefix=P + b_min * K if one_block else None,
                      pstart=P if d.prefix_cache else 0)
        return args, static

    def lower_block(self, state: DecodeState):
        """AOT-lower the fused fn for the next block of ``state`` without
        running it — to inspect the compiled block program (which
        kernels it calls, its memory)."""
        args, static = self._fused_inputs(state, self._plan(state))
        return self._fused_fn().lower(*args, **static)

    def _decode_block_fused(self, state: DecodeState) -> DecodeState:
        d = self.dcfg
        t_block = time.perf_counter()
        B, P = state.batch, state.prompt_len
        K = d.block_size
        T = P + d.gen_len
        steps_cap = d.steps_per_block or K
        frozen = d.frozen_suffix and d.parallel

        run, eff, qpos = plan = self._plan(state)
        live_rows = int(state.live.sum())
        # profiler-only spans (no tracer here): host->device puts, the
        # dispatch of the block program, and from the first readback
        # on, so a device trace can tell which host work its idle
        # gaps wait on
        with span(None, "decoder.inputs"):
            args, static = self._fused_inputs(state, plan)
        Sq = len(qpos[int(eff.min())])      # the gang's query width
        with span(None, "decoder.dispatch"):
            (x, committed, done, steps, n_hit, cache, vm, cm,
             vsums, counts, hist, fill_n, cconf) = self._fused_fn()(
                *args, **static)
        with span(None, "decoder.sync"):
            # the ONE host sync for this block (np.array: writable copies —
            # the scheduler and finalize mutate these buffers in place).
            # The telemetry outputs (counts/hist/fill_n) materialize with
            # the rest of this call's results — no extra sync.
            state.x = np.array(x)
            state.committed = np.array(committed)
            state.done = np.array(done)
            steps = int(steps)
            n_hit = int(n_hit)
            counts = np.asarray(counts)
            hist = np.asarray(hist)
            state.early_exits += n_hit
            state.host_syncs += 1
            state.cache = cache
            if vm is not None:
                state.valid_mask = np.array(vm)
                state.cached_mask = np.array(cm)

            state.steps_per_block.append(steps)
            state.nfe += steps
            # work each row's own block would cost alone (a narrower
            # row's spare query slots are not counted)
            for b, rows in zip(*np.unique(eff[run], return_counts=True)):
                rows, prefix_len = int(rows), P + int(b) * K
                sq = len(qpos[int(b)])
                if d.method == "vanilla":
                    state.q_tokens += steps * rows * T
                    state.kv_tokens += steps * rows * T * T
                elif d.method == "dkv":
                    state.q_tokens += steps * rows * sq
                    for vs in np.asarray(vsums)[:steps]:
                        state.kv_tokens += rows * sq * (int(vs) + sq)
                elif steps > 0:
                    # cached mode: the refresh pass covers only the
                    # generated prefix + query (the prompt is attended,
                    # not recomputed)
                    ref_q = (prefix_len - P if d.prefix_cache
                             else prefix_len) + sq
                    state.q_tokens += rows * ref_q
                    state.kv_tokens += rows * ref_q * (prefix_len + sq)
                    if frozen:
                        state.q_tokens += (steps - 1) * rows * K
                        state.kv_tokens += ((steps - 1) * rows * K
                                            * (prefix_len + sq + K))
                    else:
                        state.q_tokens += (steps - 1) * rows * sq
                        state.kv_tokens += ((steps - 1) * rows * sq
                                            * (prefix_len + sq))
            state.blocks = np.where(run, eff + 1,
                                    state.blocks).astype(np.int32)
            wall = time.perf_counter() - t_block
            state.block_stats.append(BlockStats(
                method=d.method, block_idx=int(eff.min()), batch=B,
                live_rows=live_rows, steps=steps, steps_cap=steps_cap,
                committed_per_step=[int(v) for v in counts[:steps]],
                straggler_fill=int(fill_n),
                conf_hist=[int(v) for v in hist],
                window=Sq, early_exits=n_hit, wall_s=wall,
                commit_conf=np.asarray(cconf, np.float32),
                row_blocks=np.where(run, eff, -1).tolist()))
            state.decode_time += wall
            return state

    # --------------------------------------------------- legacy host loop

    def _decode_block_host(self, state: DecodeState) -> DecodeState:
        """The per-step host loop: every denoise step round-trips
        device->host (confidence/selection in numpy) and re-uploads the
        token buffer. Validation oracle for the fused loop."""
        cfg, d = self.cfg, self.dcfg
        t_block = time.perf_counter()
        B, P = state.batch, state.prompt_len
        L, K = d.gen_len, d.block_size
        T = P + L
        steps_cap = d.steps_per_block or K
        eos_id = cfg.eos_token_id
        frozen = d.frozen_suffix and d.parallel

        x, committed, done = state.x, state.committed, state.done
        cache = state.cache
        valid_mask, cached_mask = state.valid_mask, state.cached_mask
        valid = None
        nfe = q_tokens = kv_tokens = 0

        c = state.block_idx
        if (state.blocks[state.blocks < state.n_blocks] != c).any():
            raise ValueError("the host loop decodes a batch at one block "
                             "index")
        region, qpos = self._region(P, c)
        Sq = len(qpos)
        qpos_b = np.broadcast_to(qpos[None], (B, Sq)).copy()
        bstart, bend = region.block_start, region.block_start + K

        prefix_len = bstart
        step = 0
        toks = None
        # telemetry mirror of the fused loop's device-side tally
        live = ~done[:, None]
        live_rows = int((~done).sum())
        committed_per_step: list = []
        conf_hist = np.zeros((CONF_BUCKETS,), np.int64)
        # calibration mirror of the fused loop's cconf/lconf carry
        cconf = np.zeros((B, K), np.float32)
        last_conf = None
        while step < steps_cap:
            blk_masked = ~committed[:, bstart:bend]
            if not (blk_masked & ~done[:, None]).any():
                break
            step += 1
            nfe += 1

            conf_toks = None            # parallel methods: (conf, toks)
            if d.method == "vanilla":
                q_tokens += B * T
                logits = self._encode_fn()(
                    self.params, self._put_batch(x),
                    self._put_batch(np.broadcast_to(
                        np.arange(T, dtype=np.int32)[None], (B, T))))
                blk_logits = logits[:, bstart:bend]
                kv_tokens += B * T * T
            elif d.method == "dkv":
                q_tokens += B * Sq
                q_toks = self._put_batch(x[np.arange(B)[:, None], qpos_b])
                mix = self._put_batch(
                    cached_mask[np.arange(B)[:, None], qpos_b])
                logits, cache = self._dkv_step_fn()(
                    self.params, q_toks, self._put_batch(qpos_b), cache,
                    self._put_batch(valid_mask), mix)
                blk_logits = logits[:, :K]
                # tokens committed earlier (whose fresh KV this step
                # was decoded-input based) are now frozen
                newly_frozen = committed & ~cached_mask
                cached_mask |= newly_frozen
                valid_mask |= newly_frozen
                kv_tokens += B * Sq * (valid_mask.sum() // B + Sq)
            elif step == 1 and d.prefix_cache:
                # prefix-cache tail refresh: [generated prefix || query]
                # only; the prefill-computed prompt KV is attended via
                # kv_valid=P and never recomputed (see _tail_refresh_*)
                upto = prefix_len - P
                q_tokens += B * (upto + Sq)
                full_pos = np.concatenate(
                    [np.arange(P, prefix_len, dtype=np.int32), qpos])
                full_pos = np.broadcast_to(full_pos[None], (B, upto + Sq))
                full_toks = self._put_batch(
                    x[np.arange(B)[:, None], full_pos])
                kv0 = self._put_batch(np.full((B,), P, np.int32))
                if d.parallel:
                    cf, tk, cache = self._tail_refresh_ct_fn()(
                        self.params, full_toks, self._put_batch(full_pos),
                        cache, kv0, upto=upto)
                    conf_toks = (cf, tk)
                else:
                    logits, cache = self._tail_refresh_fn()(
                        self.params, full_toks, self._put_batch(full_pos),
                        cache, kv0)
                    blk_logits = logits[:, upto:upto + K]
                valid = jnp.full((B,), prefix_len, jnp.int32)
                kv_tokens += B * (upto + Sq) * (prefix_len + Sq)
            elif step == 1:
                # block-start refresh (paper §3.3): prefix + query
                # region in one encode; caches the prefix KV (and,
                # with frozen_suffix, the suffix/trailing KV too)
                q_tokens += B * (prefix_len + Sq)
                full_pos = np.concatenate(
                    [np.arange(prefix_len, dtype=np.int32), qpos])
                full_pos = np.broadcast_to(full_pos[None],
                                           (B, prefix_len + Sq))
                full_toks = self._put_batch(
                    x[np.arange(B)[:, None], full_pos])
                if frozen:
                    cf, tk, cache = self._frozen_refresh_ct_fn()(
                        self.params, full_toks, self._put_batch(full_pos),
                        cache, upto=prefix_len)
                    conf_toks = (cf, tk)
                    vb = np.zeros((B, T), bool)
                    vb[:, :prefix_len] = True
                    for pp in qpos[K:]:
                        vb[:, pp] = True
                    valid = self._put_batch(vb)
                elif d.parallel:
                    cf, tk, cache = self._refresh_ct_fn()(
                        self.params, full_toks, self._put_batch(full_pos),
                        cache, upto=prefix_len)
                    conf_toks = (cf, tk)
                    valid = jnp.full((B,), prefix_len, jnp.int32)
                else:
                    logits, cache = self._refresh_fn()(
                        self.params, full_toks, self._put_batch(full_pos),
                        cache, upto=prefix_len)
                    blk_logits = logits[:, prefix_len:prefix_len + K]
                    valid = jnp.full((B,), prefix_len, jnp.int32)
                kv_tokens += B * (prefix_len + Sq) ** 2
            elif frozen:
                q_tokens += B * K
                bpos = np.broadcast_to(
                    np.arange(bstart, bend, dtype=np.int32)[None], (B, K))
                conf_toks = self._step_ct_fn()(
                    self.params, self._put_batch(x[:, bstart:bend]),
                    self._put_batch(bpos), cache, valid)
                kv_tokens += B * K * (prefix_len + Sq + K)
            elif d.parallel:
                q_tokens += B * Sq
                q_toks = self._put_batch(x[np.arange(B)[:, None], qpos_b])
                conf_toks = self._step_ct_fn()(
                    self.params, q_toks, self._put_batch(qpos_b), cache,
                    valid)
                kv_tokens += B * Sq * (prefix_len + Sq)
            else:
                q_tokens += B * Sq
                q_toks = self._put_batch(x[np.arange(B)[:, None], qpos_b])
                logits = self._step_fn()(
                    self.params, q_toks, self._put_batch(qpos_b), cache,
                    valid)
                blk_logits = logits[:, :K]
                kv_tokens += B * Sq * (prefix_len + Sq)

            if conf_toks is not None:
                # parallel methods: only (B, K) conf + tokens cross the
                # host boundary (fused head path; no block logits)
                conf = np.asarray(conf_toks[0])
                toks = np.asarray(conf_toks[1])
                state.host_syncs += 1
            else:
                # fixed-schedule methods: the full (B, K, V) block
                # logits cross to the host every step — the transfer
                # the fused loop eliminates
                blk_np = np.array(blk_logits, np.float32)
                state.host_syncs += 1
                state.logit_syncs += 1
                blk_np[..., cfg.mask_token_id] = -1e30  # never emit [MASK]
                conf, toks = sched.confidence_and_tokens(blk_np)
                conf, toks = np.asarray(conf), np.asarray(toks)

            if d.parallel:
                if d.method == "streaming":
                    r_mask = blk_masked.mean(axis=1, dtype=np.float32)
                    tau = np.asarray(sched.dynamic_threshold(
                        d.tau0, d.alpha, jnp.asarray(r_mask)))
                else:
                    tau = np.full((B,), d.tau0, np.float32)
                commit = np.array(sched.select_tokens(
                    jnp.asarray(conf), jnp.asarray(blk_masked),
                    jnp.asarray(tau)))
            else:
                n_commit = max(1, K // steps_cap)
                commit = np.array(sched.fixed_rate_select(
                    jnp.asarray(conf), jnp.asarray(blk_masked), n_commit))
            sel = np.where(commit)
            x[sel[0], bstart + sel[1]] = toks[sel]
            cconf[sel] = conf[sel]
            last_conf = conf
            committed[:, bstart:bend] |= commit
            act = commit & live
            committed_per_step.append(int(act.sum()))
            b_idx = np.clip((conf * CONF_BUCKETS).astype(np.int32),
                            0, CONF_BUCKETS - 1)
            np.add.at(conf_hist, b_idx[act], 1)

        state.steps_per_block.append(step)

        # finalize block: commit any stragglers (steps cap reached) —
        # rows that early-exited in a prior block keep their tail
        blk_masked = ~committed[:, bstart:bend] & ~done[:, None]
        straggler_fill = int(blk_masked.sum()) if step > 0 else 0
        if blk_masked.any() and toks is not None:
            x[:, bstart:bend] = np.where(blk_masked, toks, x[:, bstart:bend])
            if last_conf is not None:
                cconf = np.where(blk_masked, last_conf, cconf)
        committed[:, bstart:bend] = True
        # Early exit (paper S3.3): a block that decoded an EOS makes
        # all *subsequent* blocks skippable for that row.
        hits_blk = 0
        if d.early_exit:
            hit = (x[:, bstart:bend] == eos_id).any(axis=1) & ~done
            hits_blk = int(hit.sum())
            if hits_blk:
                state.early_exits += hits_blk
                done |= hit

        state.cache = cache
        state.valid_mask = valid_mask
        state.cached_mask = cached_mask
        state.block_idx = c + 1
        state.nfe += nfe
        state.q_tokens += q_tokens
        state.kv_tokens += kv_tokens
        wall = time.perf_counter() - t_block
        state.block_stats.append(BlockStats(
            method=d.method, block_idx=c, batch=B, live_rows=live_rows,
            steps=step, steps_cap=steps_cap,
            committed_per_step=committed_per_step,
            straggler_fill=straggler_fill,
            conf_hist=[int(v) for v in conf_hist],
            window=Sq, early_exits=hits_blk, wall_s=wall,
            commit_conf=cconf))
        state.decode_time += wall
        return state

    # ------------------------------------------------------ main loop

    def finalize(self, state: DecodeState) -> GenerateResult:
        """Aggregate a finished (or early-stopped) state into the
        monolithic GenerateResult: rows truncated at their first EOS."""
        P, L = state.prompt_len, self.dcfg.gen_len
        eos_id = self.cfg.eos_token_id
        gen = state.x[:, P:].copy()
        # truncate each row at first EOS (tokens after EOS don't count)
        tokens_generated = 0
        for b in range(state.batch):
            eos_pos = np.where(gen[b] == eos_id)[0]
            n = eos_pos[0] if len(eos_pos) else L
            tokens_generated += int(n)
            if len(eos_pos):
                gen[b, eos_pos[0]:] = eos_id
        wall = state.prefill_time + state.decode_time
        return GenerateResult(gen, state.nfe, list(state.steps_per_block),
                              wall, state.q_tokens, state.kv_tokens,
                              tokens_generated, state.early_exits,
                              state.prefill_time, state.host_syncs,
                              state.logit_syncs)

    def generate(self, prompt: np.ndarray) -> GenerateResult:
        """Monolithic generation: prefill + every block to completion.
        This is the synchronous (mode="batch") serving path; the
        continuous scheduler in repro.serving drives the same
        prefill/decode_block pair directly and interleaves requests at
        block boundaries."""
        t0 = time.perf_counter()
        state = self.prefill(prompt)
        while not state.finished:
            self.decode_block(state)
        res = self.finalize(state)
        res.wall_time = time.perf_counter() - t0
        return res
