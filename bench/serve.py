"""The system under test, built the way a user runs it, and the
clients that drive it over loopback HTTP.

``HttpFrontend -> EngineLoop -> ContinuousEngine (BlockScheduler) ->
DiffusionDecoder`` fused block program -> Pallas kernels, with the
weights the benchmark made. Clients speak ``POST /v1/completions``
with ``stream: true`` and time each SSE block event on arrival.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import List, Optional

from bench import traffic as traffic_mod

# How long after the window closes the clients wait for requests still
# in flight, whose answers the correctness check may sample.
GRACE_S = 60.0


@dataclasses.dataclass
class Request:
    index: int
    due: float                  # when it was due (open loop), else 0
    sent: float = 0.0
    status: int = 0
    blocks: List[int] = dataclasses.field(default_factory=list)
    block_t: List[float] = dataclasses.field(default_factory=list)
    final: Optional[dict] = None
    error: str = ""

    @property
    def due_or_sent(self) -> float:
        """Where its latency starts: when it was due (open loop) or
        sent (closed loop)."""
        return self.due if self.due > 0 else self.sent

    @property
    def done(self) -> bool:
        return self.status == 200 and self.final is not None \
            and self.final.get("finish_reason") in ("stop", "length")


def build_engine(cfg, executor, config: dict, max_tokens: int):
    """The program's engine with the configuration's engine settings."""
    from repro.core.decoder import DecodeConfig
    from repro.data.tokenizer import ByteTokenizer
    from repro.serving import ContinuousEngine, PrefixKVPool
    from repro.serving.types import round_up_blocks

    e = config["engine"]
    dcfg = DecodeConfig(
        method=e["method"], gen_len=round_up_blocks(max_tokens,
                                                    cfg.block_size),
        block_size=cfg.block_size, window=e["window"], tau0=e["tau0"],
        alpha=e["alpha"], early_exit=e["early_exit"],
        use_kernels=e["use_kernels"], prefix_cache=e["prefix_cache"])
    return ContinuousEngine(
        cfg, executor.params, dcfg, max_slots=e["max_slots"],
        pad_pow2=e["pad_pow2"], batch_multiple=e["batch_multiple"],
        executor=executor,
        pool=PrefixKVPool(cfg, max_free=1, executor=executor),
        tokenizer=ByteTokenizer(cfg.vocab_size))


def gang_sizes(engine) -> list:
    """Every gang batch the scheduler can form for one shape bucket."""
    s = engine.scheduler
    return sorted({1} | {s._pad_batch(n) for n in range(1, s.max_gang + 1)})


def warm(engine, prompt_len: int, max_tokens: int) -> dict:
    """Compile and run every block variant of the cell's one shape
    bucket at every gang size it can form: the program's pre-warm, then
    each later block once more from a freshly allocated KV buffer.

    The second part is a stopgap for a fault of the program: a merge or
    a compaction hands a gang at a later block such a buffer when the
    pool has none free, and ``ContinuousEngine.prewarm``, which feeds
    every later block the previous block's output, compiles no such
    variant, so a user's first such gang compiles while serving. Delete
    it once ``prewarm`` covers these variants (PERF.md, Open
    questions)."""
    import numpy as np

    from repro.serving.types import round_up_blocks
    gen = round_up_blocks(max_tokens, engine.dcfg.block_size)
    sizes = gang_sizes(engine)
    rep = engine.prewarm([(prompt_len, gen)], batch_sizes=sizes)
    dec = engine.scheduler.decoder_for(gen)
    t0, n0 = time.perf_counter(), engine.scheduler.jit_cache_size()
    for B in sizes:
        for b in range(1, gen // engine.dcfg.block_size):
            state = dec.prefill(np.ones((B, prompt_len), np.int32),
                                cache=engine.executor.init_cache(
                                    B, prompt_len + gen))
            state.block_idx = b
            dec.decode_block(state)
            del state
    rep["variants"] += engine.scheduler.jit_cache_size() - n0
    rep["seconds"] += time.perf_counter() - t0
    return rep


async def _stream(host, port, req: Request, prompt: str,
                  max_tokens: int) -> None:
    from repro.server import client
    req.sent = time.perf_counter()
    try:
        stream = await client.SSEStream.open(
            host, port, {"prompt": prompt, "max_tokens": max_tokens})
        req.status = stream.status
        if stream.status != 200:
            req.error = str(stream.error)
            return
        try:
            async for event in stream.events():
                if "block" in event:
                    req.block_t.append(time.perf_counter())
                    req.blocks.append(int(event["block"]))
                elif "finish_reason" in event:
                    req.final = event
        finally:
            await stream.close()
    except (ConnectionError, OSError, asyncio.IncompleteReadError) as e:
        req.error = repr(e)


async def _closed(host, port, mix, seed, t0, seconds, out) -> None:
    t_end = t0 + seconds

    async def client(c: int) -> None:
        k = 0
        while time.perf_counter() < t_end:
            req = Request(index=c + mix["clients"] * k, due=0.0)
            out.append(req)
            await _stream(host, port, req,
                          traffic_mod.prompt(seed, req.index,
                                             mix["prompt_bytes"]),
                          mix["max_tokens"])
            k += 1

    await asyncio.gather(*(client(c) for c in range(mix["clients"])))


async def _open(host, port, mix, seed, t0, seconds, out) -> None:
    tasks = []
    for i, at in enumerate(traffic_mod.open_arrivals(mix["rate_per_s"],
                                                     seconds, seed)):
        due = t0 + at
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        req = Request(index=i, due=due)
        out.append(req)
        tasks.append(asyncio.create_task(_stream(
            host, port, req, traffic_mod.prompt(seed, i,
                                                mix["prompt_bytes"]),
            mix["max_tokens"])))
    await asyncio.gather(*tasks)


async def _drive(engine, mix, seed, seconds, hooks) -> dict:
    from repro.server import EngineLoop, HttpFrontend
    frontend = await HttpFrontend(EngineLoop(engine), port=0).start()
    out: List[Request] = []
    ok = False
    try:
        hooks.window_open()
        t0 = time.perf_counter()
        run = {"closed": _closed, "open": _open}[mix["loop"]]
        task = asyncio.create_task(run(frontend.host, frontend.port, mix,
                                       seed, t0, seconds, out))
        await asyncio.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        t1 = time.perf_counter()
        hooks.window_closed()
        try:
            await asyncio.wait_for(asyncio.shield(task), GRACE_S)
        except asyncio.TimeoutError:
            task.cancel()
        ok = True
    finally:
        await frontend.shutdown(drain=ok, timeout_s=GRACE_S)
    return {"requests": out, "t0": t0, "t1": t1}


def drive(engine, mix: dict, seed: int, seconds: float, hooks) -> dict:
    """Serve the mix for ``seconds`` and wait for what is in flight.
    ``hooks.window_open()``/``window_closed()`` run at the window's
    edges (counter snapshots, the profiler)."""
    traffic_mod.validate(mix)
    return asyncio.run(_drive(engine, mix, seed, seconds, hooks))
