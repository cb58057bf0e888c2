"""Continuous batching at diffusion-block granularity.

The unit of work is one *block* of one *gang* — a batch of requests
sharing a shape bucket ``(prompt_len, gen_len)`` that advance in
lockstep through ``DiffusionDecoder.decode_block``. Every scheduler
tick advances each live gang by one block, then harvests: finished rows
(EOS early exit or last block) emit their final chunk immediately, and
the gang is *compacted* — live rows are gathered into the next
power-of-two batch bucket, freed slots are backfilled from the waiting
queue at the same tick, and the old KV buffer returns to the
``PrefixKVPool``. Where the decoder ``mixes_blocks``, a gang's rows may
stand at different block indexes: gangs of one shape bucket merge
whatever their blocks, and a request admitted at a boundary joins a
live gang's vacated lanes before its first block. Compiled step shapes
are fixed per (bucket, batch, query width): after warmup no request
causes a recompile.

Exactness: compaction relies on ``DiffusionDecoder.batch_invariant`` —
no row's result depends on another row, for every method except dkv,
whose step-level KV freezing drifts at ulp level when the batch
changes. dkv gangs therefore keep their admitted batch until every row
finishes (matching the synchronous engine), while the other methods
shrink and backfill freely. A new gang size still rounds the matmuls
differently, so a near-tie argmax can flip (seen on the TPU with random
weights; see ``batch_invariant``).

Preemption is block-level: ``preempt(uid)`` extracts the row's
``DecodeState`` at the next block boundary, parks it without a KV
buffer, and re-admits it ahead of the waiting queue when a slot frees —
resuming at the exact block it left off.

Cancellation is distinct from preemption: ``cancel(uid)`` gives the
slot up for good and terminates the request with a *partial*
``Completion`` (whatever was committed so far, EOS/max_tokens
trimmed). A waiting or paused request is cancelled immediately; an
active row is released at the next block boundary — before the next
tick's decode, so a cancelled request never pays for another block.
The async front end (``repro.server``) drives it on client disconnect
and deadline expiry.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.cache import PrefixKVCache
from repro.cache.store import HOST_PLACEMENT
from repro.core.decoder import (DecodeConfig, DecodeState, DiffusionDecoder,
                                eos_truncate)
from repro.models.config import ModelConfig
from repro.obs.compile import CompileWatch
from repro.obs.trace import span
from repro.serving.pool import PrefixKVPool
from repro.serving.types import BlockChunk, Completion, ServeRequest


def _pow2_ge(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _pow2_le(n: int) -> int:
    assert n >= 1
    return 1 << (n.bit_length() - 1)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class Gang:
    """A batch of requests decoding in lockstep, one block per tick.
    ``requests[i] is None`` marks a padding or vacated lane."""

    def __init__(self, decoder: DiffusionDecoder, state: DecodeState,
                 requests: List[Optional[ServeRequest]]):
        self.decoder = decoder
        self.state = state
        self.requests = requests
        # rows whose final chunk has been emitted (padding lanes never emit)
        self.emitted = [r is None for r in requests]
        # state.nfe high-water mark already attributed to requests. A
        # fresh gang starts at 0 so the dkv prefill pass (counted into
        # state.nfe by prefill()) reaches the first harvest's delta;
        # compacted/resumed states restart their counters at 0 too.
        self.nfe_seen = 0
        self.syncs_seen = 0          # state.host_syncs high-water mark
        self.logit_syncs_seen = 0    # state.logit_syncs high-water mark
        # (B, K) commit-time confidences of the block drained this tick
        # (set by _drain_block_stats, consumed by _harvest same-tick)
        self.last_commit_conf = None

    @property
    def batch(self) -> int:
        return self.state.batch

    def live_rows(self) -> List[int]:
        """Rows still producing output."""
        return [i for i, r in enumerate(self.requests)
                if r is not None and not self.emitted[i]]

    def open_rows(self) -> List[int]:
        """Rows that still need future blocks (drive compaction)."""
        return [i for i, r in enumerate(self.requests)
                if r is not None and not self.state.row_finished(i)]


class BlockScheduler:
    def __init__(self, cfg: ModelConfig, params, dcfg: DecodeConfig, *,
                 max_slots: int = 8, max_gang: Optional[int] = None,
                 pool: Optional[PrefixKVPool] = None,
                 max_waiting: Optional[int] = None,
                 tokenizer=None, mesh=None, pad_pow2: bool = False,
                 executor=None, batch_multiple: Optional[int] = None,
                 merge_gangs: bool = True,
                 prefix_cache: Optional[PrefixKVCache] = None,
                 prefill_only: bool = False,
                 tracer=None, telemetry=None, block_hist=None):
        self.cfg = cfg
        self.params = params
        self.dcfg = dcfg
        self.executor = executor
        # Disaggregated serving: a prefill-only scheduler admits and
        # prefills gangs exactly like a co-located one (hit-homogeneous
        # grouping included) but never decodes a block — each primed
        # gang is dismantled into ``handoff_ready`` the same tick, its
        # chunk KV already published to the (shared) radix store, and
        # the owning EngineLoop migrates the requests to a decode-pool
        # engine (see ``take_handoffs`` / ``adopt_handoff``).
        self.prefill_only = prefill_only
        self.handoff_ready: List[ServeRequest] = []
        # busy-seconds split by phase (prefill = prefill/re-prime
        # passes, decode = decode_block walls) — pool imbalance in a
        # disaggregated fleet is visible here before it costs tok/s
        self.prefill_wall_s = 0.0
        self.decode_wall_s = 0.0
        # Gang batches sized as a multiple of the mesh's data-axis
        # extent shard evenly; any other size falls back to replicated
        # placement (never silent padding — see DecodeExecutor). The
        # scheduler therefore *rounds gang sizes up* to this multiple
        # (pad rows replicate row 0, exactly like pad_pow2 padding).
        self.batch_multiple = (batch_multiple if batch_multiple is not None
                               else (executor.data_extent
                                     if executor is not None else 1))
        self.max_slots = max(max_slots, self.batch_multiple)
        self.max_gang = min(max_gang or self.max_slots, self.max_slots)
        # pad_pow2 snaps gang sizes to a power-of-two ladder: fewest
        # compiled batch shapes (log2(max_slots) sizes), at the price of
        # pad rows that burn compute — worth it when compiles are the
        # scarce resource (large accelerator graphs). The default uses
        # exact sizes: at most max_slots distinct batch shapes, and
        # every freed row immediately stops costing FLOPs.
        self.pad_pow2 = pad_pow2
        if pool is None:
            pool = PrefixKVPool(cfg, executor=executor)
        elif pool.executor is not executor:
            # a shared pool across meshes would hand buffers placed on
            # one mesh to decoders driving another — refuse up front
            raise ValueError(
                "PrefixKVPool must be bound to the scheduler's executor "
                f"(pool.executor={pool.executor!r}, "
                f"scheduler executor={executor!r})")
        self.pool = pool
        # cross-request prefix KV store (repro.cache): like the pool,
        # one store per executor placement — chunk KV shapes/numerics
        # are mesh-specific, so a store warmed on one mesh must never
        # feed a decoder driving another
        placement = (executor.placement if executor is not None
                     else HOST_PLACEMENT)
        # vanilla has no KV cache at all — a store could never be
        # filled or read, so it is not silently carried: the scheduler
        # runs storeless (no probes, no hit-keyed admission groups)
        use_store = dcfg.prefix_cache and dcfg.method != "vanilla"
        if prefix_cache is not None and not use_store:
            raise ValueError(
                "a PrefixKVCache store needs DecodeConfig.prefix_cache "
                "and a non-vanilla method "
                f"(prefix_cache={dcfg.prefix_cache}, "
                f"method={dcfg.method!r})")
        if use_store and prefix_cache is None:
            prefix_cache = PrefixKVCache(chunk_tokens=dcfg.cache_chunk,
                                         placement=placement)
        if prefix_cache is not None:
            # a *shared* store (disaggregated pools) is keyed by mesh
            # shape, not device ids: chunk KV is host-staged numpy and
            # its numerics depend only on the mesh shape, so any
            # same-shape executor may publish and consume it
            shape_key = (executor.shape_key if executor is not None
                         else HOST_PLACEMENT)
            ok = (tuple(prefix_cache.placement) == tuple(placement)
                  or (prefix_cache.shared
                      and tuple(prefix_cache.placement) == tuple(shape_key)))
            if not ok:
                raise ValueError(
                    "PrefixKVCache must be bound to the scheduler's "
                    f"executor placement (store={prefix_cache.placement}, "
                    f"scheduler={placement}, shared needs {shape_key})")
            if prefix_cache.chunk_tokens != dcfg.cache_chunk:
                raise ValueError(
                    f"PrefixKVCache chunk {prefix_cache.chunk_tokens} != "
                    f"DecodeConfig.cache_chunk {dcfg.cache_chunk}")
        self.prefix_cache = prefix_cache if use_store else None
        self.max_waiting = max_waiting
        self.tok = tokenizer
        self.mesh = mesh if executor is None else executor.mesh
        self.merge_gangs = merge_gangs
        self.waiting: Deque[ServeRequest] = deque()
        self.paused: Deque[Tuple[ServeRequest, DecodeState,
                                 DiffusionDecoder]] = deque()
        self.gangs: List[Gang] = []
        self._decoders: Dict[int, DiffusionDecoder] = {}
        self._preempt: set = set()
        self._cancel: set = set()
        self._uid = 0
        self.last_decoded_rows = 0
        self.merges = 0            # cross-gang straggler merges performed
        self.block_programs = 0    # decode_block calls
        # ... of which advanced live rows at more than one block index
        self.mixed_block_programs = 0
        # observability (repro.obs) — all optional. ``tracer`` records
        # queue/decode/block spans on the request's async track plus
        # scheduler.* and decoder.block spans on this engine's thread
        # track (``pid`` names the track; the owning EngineLoop sets
        # it; the thread-track spans reach a profiler capture without
        # a tracer too);
        # ``telemetry`` accumulates the per-block BlockStats the decoder
        # harvests; ``block_hist`` observes per-block wall time.
        self.tracer = tracer
        self.telemetry = telemetry
        self.block_hist = block_hist
        self.pid = 0
        # innermost open async span per traced uid ("queue" | "decode"
        # | "paused") — the bookkeeping that keeps span trees balanced
        # through cancel/preempt/deadline paths
        self._span_state: Dict[int, str] = {}
        # compile ledger: every jit-dispatching call site below runs
        # through it so new compiled variants are attributed to the
        # call that built them (and flagged if they appear after the
        # startup pre-warm declared the engine warm)
        self.compile_watch = CompileWatch()

    # ------------------------------------------------------ bookkeeping

    def _decoder(self, gen_len: int) -> DiffusionDecoder:
        if gen_len not in self._decoders:
            d = dataclasses.replace(self.dcfg, gen_len=gen_len)
            self._decoders[gen_len] = DiffusionDecoder(
                self.cfg, self.params, d, mesh=self.mesh,
                executor=self.executor, prompt_cache=self.prefix_cache)
        return self._decoders[gen_len]

    def decoder_for(self, gen_len: int) -> DiffusionDecoder:
        """Public accessor for the per-``gen_len`` decoder (the engine's
        pre-warm drives it directly, outside the admission path)."""
        return self._decoder(gen_len)

    def _pad_batch(self, n: int) -> int:
        """Gang-size policy: optional pow2 ladder, then round up to the
        data-shard multiple so sharding never falls back silently."""
        padded = _pow2_ge(n) if self.pad_pow2 else n
        return _round_up(padded, self.batch_multiple)

    @property
    def slots_used(self) -> int:
        return sum(g.batch for g in self.gangs)

    @property
    def live_rows(self) -> int:
        return sum(len(g.live_rows()) for g in self.gangs)

    @property
    def idle(self) -> bool:
        return not (self.waiting or self.paused or self.gangs
                    or self.handoff_ready)

    def debug_state(self) -> dict:
        """JSON-safe snapshot of scheduler occupancy for operator
        inspection (``/debug/vars``) and flight-recorder dumps. Reads
        may come from the asyncio thread while the decode thread
        mutates — ``list()`` snapshots keep iteration safe; individual
        fields can be one tick stale, which is fine for debugging."""
        gangs = list(self.gangs)
        return {
            "waiting": len(self.waiting),
            "paused": len(self.paused),
            "prefill_only": self.prefill_only,
            "handoff_ready": len(self.handoff_ready),
            "prefill_wall_s": round(self.prefill_wall_s, 6),
            "decode_wall_s": round(self.decode_wall_s, 6),
            "slots_used": self.slots_used,
            "max_slots": self.max_slots,
            "live_rows": self.live_rows,
            "merges": self.merges,
            "pending_preempts": len(self._preempt),
            "pending_cancels": len(self._cancel),
            "jit_cache_size": self.jit_cache_size(),
            "compile": self.compile_watch.counters(),
            "gangs": [{
                "batch": g.batch,
                "live_rows": len(g.live_rows()),
                "block_idx": g.state.block_idx,
                "blocks": g.state.blocks.tolist(),
                "n_blocks": g.state.n_blocks,
                "prompt_len": g.state.prompt_len,
                "method": g.decoder.dcfg.method,
                "uids": [r.uid for r in list(g.requests)
                         if r is not None],
            } for g in gangs],
        }

    def jit_cache_size(self) -> int:
        """Compiled variants across every decoder *and* the executor's
        cache-creation fns — the quantity whose growth the CompileWatch
        ledger attributes to call sites."""
        n = sum(d.jit_cache_size() for d in self._decoders.values())
        if self.executor is not None:
            n += self.executor.jit_cache_size()
        return n

    # ------------------------------------------------------ submission

    def submit(self, prompt_tokens: np.ndarray, gen_len: int,
               max_tokens: int, trace_id: str = "") -> ServeRequest:
        """Admission control: reject (raise) beyond ``max_waiting``."""
        if self.max_waiting is not None \
                and len(self.waiting) >= self.max_waiting:
            raise RuntimeError(
                f"admission rejected: waiting queue at max_waiting="
                f"{self.max_waiting}")
        self._uid += 1
        req = ServeRequest(self._uid, np.asarray(prompt_tokens, np.int32),
                           gen_len, max_tokens, time.perf_counter(),
                           trace_id=trace_id)
        if self.tracer is not None and trace_id:
            self.tracer.async_begin(trace_id, "queue", pid=self.pid,
                                    uid=req.uid)
            self._span_state[req.uid] = "queue"
        if self.prefix_cache is not None:
            # expected hit length: reported up the stack (router
            # affinity, Completion) and the basis of hit-aware
            # admission grouping — see _group_key
            req.expected_hit_tokens = self.prefix_cache.match_len(
                req.prompt_tokens)
        self.waiting.append(req)
        return req

    def preempt(self, uid: int) -> None:
        """Vacate the request's slot at the next block boundary; the
        request resumes from the same block once a slot frees. (For the
        non-batch-invariant dkv baseline the remaining rows keep their
        lanes, so only the preempted request itself is perturbed.)
        Unknown/finished uids are ignored — a stale flag must never
        outlive its request, or it would fire on a future uid."""
        active = any(r is not None and r.uid == uid
                     for g in self.gangs for r in g.requests)
        if active:
            self._preempt.add(uid)

    def cancel(self, uid: int) -> Optional[Completion]:
        """Terminate a request wherever it lives, freeing its resources
        for good (contrast ``preempt``, which parks the state to
        resume). Waiting/paused requests are cancelled *now* and their
        partial ``Completion`` is returned. Active rows are flagged and
        released at the next block boundary — the partial ``Completion``
        comes out of the next ``tick()`` (return value ``None`` here).
        Unknown or already-finished uids return ``None`` and set no
        flag, so a stale cancel can never fire on a future uid."""
        now = time.perf_counter()
        for r in self.waiting:
            if r.uid == uid:
                self.waiting.remove(r)
                return self._make_completion(
                    r, np.zeros(0, np.int32), now, cancelled=True)
        for item in self.paused:
            req, state, decoder = item
            if req.uid == uid:
                self.paused.remove(item)
                K = decoder.dcfg.block_size
                gen = state.x[0, state.prompt_len:
                              state.prompt_len + state.block_idx * K].copy()
                return self._make_completion(req, gen, now, cancelled=True)
        for r in self.handoff_ready:
            # primed but not yet migrated to the decode pool: conclude
            # here, immediately — the EngineLoop's dispatch skips done
            # tickets, so the cancel fires exactly once
            if r.uid == uid:
                self.handoff_ready.remove(r)
                return self._make_completion(
                    r, np.zeros(0, np.int32), now, cancelled=True)
        active = any(r is not None and r.uid == uid and not g.emitted[i]
                     for g in self.gangs
                     for i, r in enumerate(g.requests))
        if active:
            self._preempt.discard(uid)   # cancel wins over preempt
            self._cancel.add(uid)
        return None

    def _apply_cancels(self):
        """Release cancel-flagged rows at the block boundary: vacate the
        lane before this tick's decode (a cancelled request never pays
        for another block), emit the partial ``Completion`` plus a
        terminal ``BlockChunk`` so streams shut down, then compact so
        freed slots are backfillable this same tick. dkv gangs keep
        their lanes (non-batch-invariant) with ``done`` masking the dead
        row, exactly like preemption."""
        chunks: List[BlockChunk] = []
        completions: List[Completion] = []
        if not self._cancel:
            return chunks, completions
        now = time.perf_counter()
        for gang in self.gangs:
            st = gang.state
            K = gang.decoder.dcfg.block_size
            P = st.prompt_len
            for i in gang.live_rows():
                req = gang.requests[i]
                if req.uid not in self._cancel:
                    continue
                self._cancel.discard(req.uid)
                bidx = int(st.blocks[i])
                gen = st.x[i, P:P + bidx * K].copy()
                completions.append(
                    self._make_completion(req, gen, now, cancelled=True))
                chunks.append(BlockChunk(req.uid, bidx,
                                         np.zeros(0, np.int32), "",
                                         True, False))
                gang.requests[i] = None
                gang.emitted[i] = True
                st.done[i] = True
        self._cancel.clear()   # flags never outlive their sweep
        self._compact()
        return chunks, completions

    # ------------------------------------------------------ span hooks

    def _trace_admit(self, req: ServeRequest) -> None:
        """Request entered a gang: close "queue" (first admission only
        — a resumed request's queue span closed long ago) and open
        "decode"."""
        if self.tracer is None or not req.trace_id:
            return
        if self._span_state.get(req.uid) == "queue":
            self.tracer.async_end(req.trace_id, "queue", pid=self.pid)
        self.tracer.async_begin(req.trace_id, "decode", pid=self.pid,
                                uid=req.uid)
        self._span_state[req.uid] = "decode"

    def _trace_finish(self, req: ServeRequest) -> None:
        """Request reached its terminal Completion: close whichever
        span is still open (decode for active/preempt-cancelled rows,
        queue for cancelled-while-waiting; a paused request has
        nothing open — its decode span closed at extraction)."""
        if self.tracer is None or not req.trace_id:
            return
        open_span = self._span_state.pop(req.uid, None)
        if open_span in ("queue", "decode"):
            self.tracer.async_end(req.trace_id, open_span, pid=self.pid)

    # ------------------------------------------------------ stealing

    def steal_waiting(self) -> Optional[ServeRequest]:
        """Give up the *newest* waiting request to an idle sibling
        engine (EngineRouter block-boundary work stealing). Newest
        first: the head of the queue is next in line for this engine's
        own backfill, while the tail would wait longest here. Closes
        the request's "queue" span on this engine's track — the thief
        opens a fresh one when it re-admits."""
        if not self.waiting:
            return None
        req = self.waiting.pop()
        if self.tracer is not None and req.trace_id \
                and self._span_state.pop(req.uid, None) == "queue":
            self.tracer.async_end(req.trace_id, "queue", pid=self.pid,
                                  stolen=True)
        return req

    def steal_paused(self) -> Optional[Tuple[ServeRequest, DecodeState]]:
        """Give up one parked (preempted) row, newest first. Only
        host-portable states leave: a dkv state pins a gathered device
        cache on this engine's mesh (and dkv is not batch-invariant
        anyway), so dkv rows always resume where they paused."""
        for item in reversed(self.paused):
            req, state, decoder = item
            if decoder.dcfg.method == "dkv" or state.cache is not None:
                continue
            self.paused.remove(item)
            self._span_state.pop(req.uid, None)
            if self.tracer is not None and req.trace_id:
                self.tracer.instant("steal_out", pid=self.pid, uid=req.uid)
            return req, state

    def adopt_paused(self, req: ServeRequest, state: DecodeState) -> int:
        """Adopt a mid-decode row stolen from a sibling engine: the
        request gets a fresh uid in this scheduler's namespace (the
        victim's uid could collide with a live one here) and parks on
        the paused deque at the exact block it left off. The normal
        resume path — pool buffer acquire plus radix-store re-prime
        when the prefix cache is on — picks it up at the next
        ``_admit``, so a stolen row decodes exactly like a row preempted
        and resumed on one engine."""
        self._uid += 1
        req.uid = self._uid
        req.stolen += 1
        if self.tracer is not None and req.trace_id:
            self.tracer.async_begin(req.trace_id, "queue", pid=self.pid,
                                    uid=req.uid, stolen=True)
            self._span_state[req.uid] = "queue"
        self.paused.append((req, state, self._decoder(req.gen_len)))
        return req.uid

    # ------------------------------------------------------ handoff

    def take_handoffs(self) -> List[ServeRequest]:
        """Drain the requests a prefill-only tick primed; the owning
        EngineLoop migrates each to a decode-pool engine."""
        out, self.handoff_ready = self.handoff_ready, []
        return out

    def adopt_handoff(self, req: ServeRequest) -> int:
        """Adopt a request primed on a prefill-pool engine: fresh uid
        in this scheduler's namespace (the prefill engine's uid could
        collide with a live one here), back onto the waiting queue with
        every lifecycle counter intact — ``submit_time`` and the
        prefill-pass nfe/syncs carry over, so the Completion reports
        true end-to-end latency. The normal admission path prefills it
        again, but the prefill engine already published every aligned
        chunk to the *shared* radix store, so this pass assembles the
        prompt KV from the store and computes only the unaligned
        remainder (O(cache_chunk), not O(prompt)) — which is exactly
        why handed-off output is bit-identical to the single-engine
        path: cached-vs-cold prefill identity holds by construction
        (see repro.cache). Bypasses ``max_waiting`` like
        ``adopt_paused``: the row was admitted once already."""
        self._uid += 1
        req.uid = self._uid
        req.handoffs += 1
        if self.tracer is not None and req.trace_id:
            self.tracer.async_begin(req.trace_id, "queue", pid=self.pid,
                                    uid=req.uid, handoff=True)
            self._span_state[req.uid] = "queue"
        if self.prefix_cache is not None:
            req.expected_hit_tokens = self.prefix_cache.match_len(
                req.prompt_tokens)
        self.waiting.append(req)
        return req.uid

    def _extract_handoffs(self) -> None:
        """Dismantle every primed gang into ``handoff_ready``: the
        chunk KV lives in the shared store now (``prefill`` published
        it), so the gang buffer goes straight back to the pool and only
        the *requests* travel — no DecodeState crosses engines. The
        prefill pass's nfe/sync deltas are attributed to each row first
        (same bookkeeping as ``_harvest``)."""
        for gang in self.gangs:
            st = gang.state
            dnfe = st.nfe - gang.nfe_seen
            dsync = st.host_syncs - gang.syncs_seen
            dlogit = st.logit_syncs - gang.logit_syncs_seen
            for req in gang.requests:
                if req is None:
                    continue
                req.nfe += dnfe
                req.host_syncs += dsync
                req.logit_syncs += dlogit
                self._trace_handoff(req)
                self.handoff_ready.append(req)
            if st.cache is not None:
                self.pool.release(st.batch, st.total_len, st.cache)
                st.cache = None
        self.gangs = []

    def _trace_handoff(self, req: ServeRequest) -> None:
        """Row leaves this engine for the decode pool: close whichever
        span is open on this track (decode, normally) tagged
        ``handoff=True``; the decode engine opens a fresh "queue" span
        at adoption — same span-continuity contract as stealing."""
        if self.tracer is None or not req.trace_id:
            return
        open_span = self._span_state.pop(req.uid, None)
        if open_span in ("queue", "decode"):
            self.tracer.async_end(req.trace_id, open_span, pid=self.pid,
                                  handoff=True)
        self.tracer.instant("handoff_out", pid=self.pid, uid=req.uid)

    # ------------------------------------------------------ merge

    def _merge_stragglers(self) -> None:
        """Cross-gang merge: gangs of one shape bucket — typically
        stragglers left ragged by early exits, cancels, split admissions
        or requests that arrived at different ticks — are fused into one
        gang before the next ``decode_block``, so N part-full block
        calls become one. Where the decoder ``mixes_blocks`` the gangs
        may stand at different block indexes; otherwise only gangs at
        the same block merge. Safe only for batch-invariant methods
        (per-row tokens don't depend on batching); dkv gangs are never
        touched. Merged rows restart their gang-level counters exactly
        like compaction (``take_rows``) does."""
        if not self.merge_gangs or len(self.gangs) < 2:
            return
        groups: Dict[tuple, List[Gang]] = {}
        for g in self.gangs:
            st = g.state
            if not g.decoder.batch_invariant or st.finished:
                continue
            if any(r is not None and r.uid in self._preempt
                   for r in g.requests):
                continue    # let preemption extract its row first
            key = (st.prompt_len, st.total_len)
            if not g.decoder.mixes_blocks:
                key += (st.block_idx,)
            groups.setdefault(key, []).append(g)
        for gs in groups.values():
            if len(gs) < 2:
                continue
            gs.sort(key=lambda g: len(g.open_rows()))
            bin_gangs: List[Gang] = []
            bin_rows = bin_slots = 0
            for g in gs:
                r = len(g.open_rows())
                # a merge may never grow the slot footprint: the padded
                # merged batch must fit inside the slots the source
                # gangs release (admission's padded<=max_slots guard
                # doesn't apply here, and pow2 padding of e.g. three
                # 1-row gangs would otherwise mint a 4th slot out of
                # thin air), and stay within the gang-size cap
                fits = (bin_rows + r <= self.max_gang
                        and self._pad_batch(bin_rows + r)
                        <= bin_slots + g.batch)
                if bin_gangs and not fits:
                    if len(bin_gangs) >= 2:
                        self._merge_bin(bin_gangs)
                    bin_gangs, bin_rows, bin_slots = [], 0, 0
                bin_gangs.append(g)
                bin_rows += r
                bin_slots += g.batch
            if len(bin_gangs) >= 2:
                self._merge_bin(bin_gangs)

    def _merge_bin(self, gangs: List[Gang]) -> None:
        decoder = gangs[0].decoder
        T = gangs[0].state.total_len
        parts: List[Tuple[DecodeState, List[int]]] = []
        reqs: List[Optional[ServeRequest]] = []
        for g in gangs:
            rows = g.open_rows()
            parts.append((g.state, rows))
            reqs.extend(g.requests[i] for i in rows)
        new_b = self._pad_batch(len(reqs))
        if new_b > len(reqs):   # pad lanes replicate the first open row
            parts.append((parts[0][0],
                          [parts[0][1][0]] * (new_b - len(reqs))))
            reqs.extend([None] * (new_b - len(reqs)))
        if decoder.cache_carries_state:
            # prefix_cache: the sources' prompt KV must be read by the
            # merge gather — merge first, release after
            state = decoder.merge_rows(parts)
            for g in gangs:
                if g.state.cache is not None:
                    self.pool.release(g.state.batch, T, g.state.cache)
                    g.state.cache = None
                self.gangs.remove(g)
        else:
            # release source buffers BEFORE acquiring the merged one:
            # their contents are never read (merge_rows only needs a
            # right-shaped backing; the next refresh rewrites it), and a
            # matching-shape release turns the acquire into a
            # guaranteed pool hit
            for g in gangs:
                if g.state.cache is not None:
                    self.pool.release(g.state.batch, T, g.state.cache)
                    g.state.cache = None
                self.gangs.remove(g)
            cache = None
            if decoder.dcfg.method != "vanilla":
                cache = self.compile_watch.watched(
                    lambda: self.pool.acquire(new_b, T),
                    self.jit_cache_size, "merge_acquire",
                    tracer=self.tracer, pid=self.pid)
            state = decoder.merge_rows(parts, cache=cache)
        self.gangs.append(Gang(decoder, state, reqs))
        self.merges += 1

    # ------------------------------------------------------ tick

    def tick(self) -> Tuple[List[BlockChunk], List[Completion]]:
        """One scheduler round: release cancelled rows → admit → merge
        (so a gang admitted at this boundary joins a live gang before
        its first block) → advance every gang one block → harvest
        chunks/completions → compact + backfill."""
        chunks, completions = self._apply_cancels()
        if self.prefill_only:
            # prefill pool: admit (prefill publishes chunk KV to the
            # shared store), dismantle into handoff_ready, then admit
            # again so slots freed by the extraction fill this tick
            for _ in range(2):
                with span(self.tracer, "scheduler.admit", pid=self.pid):
                    self._admit()
                self._extract_handoffs()
            self.last_decoded_rows = 0
            return chunks, completions
        with span(self.tracer, "scheduler.admit", pid=self.pid):
            self._admit()
        merges = self.merges
        with span(self.tracer, "scheduler.merge", pid=self.pid) as sp:
            self._merge_stragglers()
            sp.annotate(merges=self.merges - merges)
        # rows whose decode this tick actually pays for — sampled before
        # the decode loop so occupancy isn't attributed post-compaction
        self.last_decoded_rows = self.live_rows
        for gang in self.gangs:
            size0 = self.jit_cache_size()
            st = gang.state
            if not st.finished:
                self.block_programs += 1
                if len(set(st.blocks[st.live].tolist())) > 1:
                    self.mixed_block_programs += 1
            t0_ns = time.perf_counter_ns()
            # the block on this engine's track and in a profiler
            # capture; its steps and commits are known only at the end
            with span(self.tracer, "decoder.block", pid=self.pid,
                      batch=st.batch, live=int(st.live.sum()),
                      block=st.block_idx,
                      prompt_len=st.prompt_len) as sp:
                gang.decoder.decode_block(st)
                if st.block_stats:
                    last = st.block_stats[-1]
                    sp.annotate(steps=last.steps,
                                committed=last.tokens_committed)
            t1_ns = time.perf_counter_ns()
            self.decode_wall_s += (t1_ns - t0_ns) / 1e9
            self.compile_watch.observe(
                self.jit_cache_size() - size0, (t1_ns - t0_ns) / 1e9,
                "decode_block", tracer=self.tracer, pid=self.pid,
                t0_ns=t0_ns, t1_ns=t1_ns)
            self._drain_block_stats(gang)
            with span(self.tracer, "scheduler.harvest", pid=self.pid) as sp:
                c, comp = self._harvest(
                    gang, gang.state.nfe - gang.nfe_seen,
                    gang.state.host_syncs - gang.syncs_seen,
                    gang.state.logit_syncs - gang.logit_syncs_seen,
                    t0_ns=t0_ns, t1_ns=t1_ns)
                sp.annotate(chunks=len(c), completions=len(comp))
            gang.nfe_seen = gang.state.nfe
            gang.syncs_seen = gang.state.host_syncs
            gang.logit_syncs_seen = gang.state.logit_syncs
            chunks.extend(c)
            completions.extend(comp)
        with span(self.tracer, "scheduler.compact", pid=self.pid):
            self._compact()
        # backfill freed slots within the same tick so the next tick
        # decodes at full occupancy
        with span(self.tracer, "scheduler.admit", pid=self.pid):
            self._admit()
        return chunks, completions

    def _drain_block_stats(self, gang: Gang) -> None:
        """Route the BlockStats the decoder just appended: into the
        telemetry aggregator and the block-wall histogram (the
        ``decoder.block`` span in ``tick`` carries the block to the
        tracer). Drained every tick so compaction (which builds fresh
        states) never loses or double-counts a block."""
        stats = gang.state.block_stats
        gang.last_commit_conf = None
        if not stats:
            return
        gang.state.block_stats = []
        gang.last_commit_conf = stats[-1].commit_conf
        if self.telemetry is not None:
            self.telemetry.extend(stats)
        if self.block_hist is not None:
            for bs in stats:
                self.block_hist.observe(bs.wall_s)

    # ------------------------------------------------------ admission

    def _admit(self) -> None:
        free = self.max_slots - self.slots_used
        # resumed (preempted) states go first, at their original block
        while self.paused and free > 0:
            req, state, decoder = self.paused.popleft()
            if state.cache is None and decoder.dcfg.method != "vanilla":
                def _resume(state=state, decoder=decoder):
                    state.cache = self.pool.acquire(state.batch,
                                                    state.total_len)
                    if decoder.dcfg.prefix_cache:
                        # a parked state dropped its prompt KV; re-prime
                        # it (its own chunks are usually still in the
                        # store, so this is O(tail), not O(prompt))
                        decoder.prime_prompt_kv(state)
                t0 = time.perf_counter()
                self.compile_watch.watched(
                    _resume, self.jit_cache_size, "resume",
                    tracer=self.tracer, pid=self.pid)
                self.prefill_wall_s += time.perf_counter() - t0
            if req.admit_time < 0:   # resume keeps the first admission
                req.admit_time = time.perf_counter()
            self._trace_admit(req)
            self.gangs.append(Gang(decoder, state, [req]))
            free -= state.batch
        if not self.waiting:
            return
        # bucket the queue once per _admit (not per admitted gang — a
        # large backlog is exactly the continuous-batching regime)
        groups: Dict[tuple, List[ServeRequest]] = {}
        for r in self.waiting:
            groups.setdefault(self._group_key(r), []).append(r)
        admitted_ids = set()
        while free > 0:
            # Largest shape group first (mirrors the synchronous
            # engine), but never fragment a group across gangs just to
            # fill freed slots: each block call has a large fixed cost
            # (weight traffic), so splitting one would-be batch into two
            # gangs costs more than briefly idling the slots. A group is
            # admitted when its full target batch fits. (pad_pow2 mode
            # instead caps the gang at the pow2 ladder below max_slots —
            # a padded target larger than max_slots could never fit and
            # would livelock the queue.)
            admitted = False
            for bucket, group in sorted(groups.items(),
                                        key=lambda kv: -len(kv[1])):
                if not group:
                    continue
                decoder = self._decoder(bucket[1])
                n, padded = self._gang_target(len(group), free, decoder)
                if n == 0 or padded > free:
                    continue
                batch_reqs = group[:n]
                del group[:n]
                admitted_ids.update(id(r) for r in batch_reqs)
                self.gangs.append(
                    self._form_gang(decoder, bucket, batch_reqs, padded))
                admitted = True
                free = self.max_slots - self.slots_used
                break
            if not admitted:
                break
        self._admit_into_lanes(groups, admitted_ids)
        if admitted_ids:
            self.waiting = deque(r for r in self.waiting
                                 if id(r) not in admitted_ids)

    def _admit_into_lanes(self, groups: Dict[tuple, List[ServeRequest]],
                          admitted_ids: set) -> None:
        """Admit waiting requests into the lanes a live gang holds but no
        open row uses — a finished row's lane that compaction kept for
        the padded batch, or a pad lane — where its decoder
        ``mixes_blocks``: each new gang is merged into that gang at
        once, so it decodes there this tick, at block 0, and no slot is
        added. A new request then never waits for a whole gang to
        drain."""
        if not self.merge_gangs or self.prefill_only:
            return
        for g in list(self.gangs):
            st = g.state
            if not g.decoder.mixes_blocks or st.finished or any(
                    r is not None and r.uid in self._preempt
                    for r in g.requests):
                continue
            bucket = (st.prompt_len, g.decoder.dcfg.gen_len)
            group = groups.get(bucket, [])
            n_open = len(g.open_rows())
            n = min(len(group), g.batch - n_open,
                    self.max_gang - n_open)
            while n > 0 and self._pad_batch(n_open + n) > g.batch:
                n -= 1
            if n <= 0:
                continue
            reqs = group[:n]
            del group[:n]
            admitted_ids.update(id(r) for r in reqs)
            new = self._form_gang(g.decoder, bucket, reqs,
                                  self._pad_batch(n))
            self.gangs.append(new)
            # the live gang's buffer goes back to the pool last, so the
            # merged gang's acquire of that size finds it
            self._merge_bin([new, g])

    def _group_key(self, r: ServeRequest) -> tuple:
        """Admission group: shape bucket, plus — with the prefix cache
        on — the *current* cached-hit depth in chunks, so gangs form
        hit-homogeneous (a gang's prefill computes from the minimum hit
        across its rows; mixing a cold row into a warm gang would make
        every row pay the cold row's prompt). Re-queried here rather
        than frozen at submit: the cache warms while requests queue."""
        if self.prefix_cache is None:
            return r.bucket
        hit = self.prefix_cache.match_len(r.prompt_tokens)
        return r.bucket + (hit // self.dcfg.cache_chunk,)

    def _gang_target(self, group_len: int, free: int,
                     decoder: DiffusionDecoder):
        """Pick (rows to admit, padded gang batch) for one shape group.
        pow2 snapping only applies to compactable (batch-invariant)
        methods — dkv pad rows would decode until the whole gang
        finishes — while data-shard rounding applies to every method
        (sharded placement needs it regardless). The shrink loop keeps
        the padded target inside ``max_slots`` so a rounding multiple
        that doesn't divide ``max_slots`` can never livelock the
        queue."""
        pow2 = self.pad_pow2 and decoder.batch_invariant
        n = min(group_len,
                _pow2_le(min(free, self.max_gang)) if pow2
                else self.max_gang)
        while n > 0:
            padded = _round_up(_pow2_ge(n) if pow2 else n,
                               self.batch_multiple)
            if padded <= self.max_slots:
                return n, padded
            n -= 1
        return 0, 0

    def _form_gang(self, decoder: DiffusionDecoder, bucket, batch_reqs,
                   padded: int) -> Gang:
        P, gen_len = bucket[:2]   # group key may carry a hit suffix
        n = len(batch_reqs)
        prompts = np.stack(
            [r.prompt_tokens for r in batch_reqs]
            + [batch_reqs[0].prompt_tokens] * (padded - n)).astype(np.int32)
        def _build():
            cache = None
            if decoder.dcfg.method != "vanilla":
                cache = self.pool.acquire(padded, P + gen_len)
            with span(self.tracer, "scheduler.prefill", pid=self.pid,
                      batch=padded, prompt_len=P):
                return decoder.prefill(prompts, cache=cache)

        t0 = time.perf_counter()
        state = self.compile_watch.watched(
            _build, self.jit_cache_size, "prefill",
            tracer=self.tracer, pid=self.pid)
        now = time.perf_counter()
        self.prefill_wall_s += now - t0
        for i, r in enumerate(batch_reqs):
            if r.admit_time < 0:
                # a handed-off row keeps its first (prefill-pool)
                # admission stamp, like a resumed row does — queue_s
                # measures time to first admission, not handoff wait
                r.admit_time = now
            if state.prefix_hit_tokens is not None and r.handoffs == 0:
                # a handed-off row's decode-pool prefill hits the store
                # by construction (the prefill pool just published its
                # chunks); keep the prefill engine's number — it is the
                # one that measures genuine cross-request reuse
                r.cache_hit_tokens = int(state.prefix_hit_tokens[i])
            self._trace_admit(r)
        rows: List[Optional[ServeRequest]] = \
            list(batch_reqs) + [None] * (padded - n)
        return Gang(decoder, state, rows)

    # ------------------------------------------------------ harvest

    def _decode_text(self, tokens: np.ndarray) -> str:
        return self.tok.decode(tokens) if self.tok is not None else ""

    def _make_completion(self, req: ServeRequest, gen: np.ndarray,
                         now: float, cancelled: bool = False) -> Completion:
        """Terminal record from a raw generated region. EOS-truncates
        (``eos_truncate``, the same policy as ``row_output``), then
        trims to the *requested* ``max_tokens`` — ``gen_len`` is
        block-rounded, and the surplus must never leave the engine."""
        gen, n_tok = eos_truncate(np.asarray(gen, np.int32),
                                  self.cfg.eos_token_id)
        gen = gen[:req.max_tokens]
        n_tok = min(n_tok, req.max_tokens)
        req.finish_time = now
        admit = req.admit_time if req.admit_time >= 0 else now
        first = req.first_block_time if req.first_block_time >= 0 else now
        self._trace_finish(req)
        conf = (np.concatenate(req.commit_conf).astype(np.float32)
                if req.commit_conf else None)
        K = self.dcfg.block_size
        return Completion(
            uid=req.uid, text=self._decode_text(gen), tokens=gen,
            latency_s=now - req.submit_time, nfe=req.nfe,
            ttfb_s=first - req.submit_time,
            queue_s=admit - req.submit_time,
            n_tokens=n_tok, n_blocks=req.blocks_decoded,
            max_tokens=req.max_tokens, cancelled=cancelled,
            host_syncs=req.host_syncs, logit_syncs=req.logit_syncs,
            cache_hit_tokens=req.cache_hit_tokens,
            expected_hit_tokens=req.expected_hit_tokens,
            trace_id=req.trace_id,
            prompt_tokens=req.prompt_tokens,
            commit_conf=conf,
            stolen=req.stolen > 0,
            handed_off=req.handoffs > 0,
            early_exited=req.blocks_decoded * K < req.gen_len)

    def _harvest(self, gang: Gang, dnfe: int, dsync: int = 0,
                 dlogit: int = 0, t0_ns: Optional[int] = None,
                 t1_ns: Optional[int] = None):
        st = gang.state
        K = gang.decoder.dcfg.block_size
        P = st.prompt_len
        eos = self.cfg.eos_token_id
        now = time.perf_counter()
        chunks: List[BlockChunk] = []
        completions: List[Completion] = []
        for i, req in enumerate(gang.requests):
            if req is None or gang.emitted[i]:
                continue
            req.nfe += dnfe
            req.host_syncs += dsync
            req.logit_syncs += dlogit
            if req.first_block_time < 0:
                req.first_block_time = now
            finished = st.row_finished(i)
            # the block this row just decoded (each live row advanced one)
            bidx = int(st.blocks[i]) - 1
            bstart = P + bidx * K
            if bidx >= 0:   # a zero-block request decodes nothing
                req.blocks_decoded += 1
                toks = st.x[i, bstart:bstart + K].copy()
                if gang.last_commit_conf is not None:
                    req.commit_conf.append(np.asarray(
                        gang.last_commit_conf[i], np.float32))
                # chunk *text* is what network consumers concatenate:
                # clamp it to the requested max_tokens (gen_len is
                # block-rounded) and mute blocks after an EOS block so
                # joined stream text always equals Completion.text
                allowed = max(0, min(K, req.max_tokens - bidx * K))
                if req.eos_seen:
                    allowed = 0
                text = self._decode_text(toks[:allowed])
                if bool((toks[:allowed] == eos).any()):
                    req.eos_seen = True
                chunks.append(BlockChunk(req.uid, bidx, toks, text,
                                         finished,
                                         bool((toks == eos).any())))
                if self.tracer is not None and req.trace_id \
                        and t0_ns is not None:
                    # the decoded block, attributed to each live
                    # request's async track with the gang's bounds
                    self.tracer.async_span(
                        req.trace_id, f"block {bidx}", t0_ns, t1_ns,
                        pid=self.pid, nfe_delta=dnfe)
            if finished:
                gang.emitted[i] = True
                self._preempt.discard(req.uid)  # flags die with request
                self._cancel.discard(req.uid)
                completions.append(self._make_completion(
                    req, st.x[i, P:].copy(), now))
        return chunks, completions

    # ------------------------------------------------------ compaction

    def _compact(self) -> None:
        kept: List[Gang] = []
        for gang in self.gangs:
            st = gang.state
            T = st.total_len
            # block-level preemption: extract flagged rows first
            for i in list(gang.open_rows()):
                req = gang.requests[i]
                if req.uid in self._preempt:
                    self._preempt.discard(req.uid)
                    sub = gang.decoder.take_rows(st, [i], alloc_cache=False)
                    req.preempted += 1
                    if self.tracer is not None and req.trace_id:
                        self.tracer.async_end(req.trace_id, "decode",
                                              pid=self.pid)
                        self.tracer.instant("preempt", pid=self.pid,
                                            uid=req.uid)
                        self._span_state[req.uid] = "paused"
                    self.paused.append((req, sub, gang.decoder))
                    gang.requests[i] = None
                    gang.emitted[i] = True
                    # if the gang can't compact (dkv), stop the vacated
                    # lane from driving further denoise steps — done
                    # rows no longer extend the block loop, and no
                    # other row reads this lane's state
                    st.done[i] = True
            open_rows = gang.open_rows()
            if not open_rows:
                if st.cache is not None:
                    self.pool.release(st.batch, T, st.cache)
                continue
            if gang.decoder.batch_invariant:
                new_b = _round_up(_pow2_ge(len(open_rows)) if self.pad_pow2
                                  else len(open_rows), self.batch_multiple)
                if new_b < st.batch:
                    rows = open_rows + [open_rows[0]] * \
                        (new_b - len(open_rows))
                    cache = None
                    if gang.decoder.dcfg.method != "vanilla" \
                            and not gang.decoder.cache_carries_state:
                        # a state-carrying cache (prefix_cache prompt
                        # region) is gathered by take_rows itself; a
                        # pooled buffer would be dead weight
                        cache = self.compile_watch.watched(
                            lambda new_b=new_b: self.pool.acquire(new_b, T),
                            self.jit_cache_size, "compact_acquire",
                            tracer=self.tracer, pid=self.pid)
                    new_state = gang.decoder.take_rows(st, rows, cache=cache)
                    if st.cache is not None:
                        self.pool.release(st.batch, T, st.cache)
                    reqs = [gang.requests[i] for i in open_rows] \
                        + [None] * (new_b - len(open_rows))
                    ng = Gang(gang.decoder, new_state, reqs)
                    kept.append(ng)
                    continue
            kept.append(gang)
        self.gangs = kept
