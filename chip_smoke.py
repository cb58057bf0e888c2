"""Smoke check of the served path on a TPU at LLaDA-8B widths.

Drives the stack a user runs — ``HttpFrontend -> EngineLoop ->
ContinuousEngine -> DecodeExecutor`` — over loopback HTTP with seeded
random weights at the published LLaDA-8B widths (d_model 4096, 32 heads
x 128, d_ff 12288, vocab 126,464, SwiGLU, block 32, float32), with the
depth cut to ``N_LAYERS`` of 32. Requests use the ``streaming`` method,
the fused denoise loop and the compiled Pallas kernels.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # 4 engines, one per chip, vs 1

One chip: kernel-vs-reference logits at full width (and a control with
the attention inputs in bf16, which must fail the bound), the gang
witness (a row's logits alone and inside a padded gang of 4), then >= 8
concurrent HTTP requests (256-token prompts, max_tokens 256), then the
serving invariants (one host sync per block, no compile after pre-warm,
both kernels compiled into the block program). Four chips: the same
requests served by one engine and by four engines behind the router,
every gang padded to 4 rows on both sides; every chip must serve,
tokens must match, and each chip must hold one copy of the params.

The figures printed on the way are smoke figures, not benchmark
numbers. The last line of stdout is ``{"ok": true, "device": {...}}``;
any failure exits non-zero without it. Without a TPU it refuses to run.
The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``results/compile_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import sys
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "llada-8b"
# The TPU compiler refuses the 8-layer batch-8 block program (16.64 GiB
# of 15.75 GiB HBM): at the default matmul precision it keeps a bf16
# copy of every weight as temp next to the float32 params.
N_LAYERS = 6
PROMPT_LEN = 256
MAX_TOKENS = 256
N_REQUESTS = 8
MAX_SLOTS = 8
# --chips 4: a shorter generation keeps the four engines' pre-warm
# (compiled once per chip) inside the time limit. Every gang is padded
# to FLEET_GANG rows on both sides: on the TPU a row's logits depend on
# the gang's size at the rounding level (the gang witness measures it),
# so tokens can only be compared between runs with equal gang shapes.
# Within one shape, a row's tokens must not depend on its gang mates.
FLEET_MAX_TOKENS = 64
FLEET_GANG = 4
SERVE_TIMEOUT_S = 600.0
# Kernel vs reference logits, both under float32 matmuls: relative L2
# error ||kernel - ref|| / ||ref||. The two differ only in summation
# order (online softmax over KV tiles), ~1e-6 on the chip. Rounding the
# kernel's q, k and v to bf16 (8 mantissa bits, everything else
# float32) must land above the bound: kernel_parity measures that
# control and run_one_chip checks it.
LOGIT_RTOL = 1e-4
KERNELS = ("block_attention", "confidence_argmax")


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def model_config(n_layers: int = None):
    from repro.models import get_config
    return get_config(ARCH, n_layers=n_layers or N_LAYERS, reps=0)


def make_prompts(n: int, length: int, seed: int) -> list:
    """Printable-ASCII prompts: one byte-tokenizer token per char, so
    every prompt lands in the same ``length`` shape bucket."""
    rng = np.random.default_rng(seed)
    return ["".join(map(chr, rng.integers(32, 127, length)))
            for _ in range(n)]


def make_engine(cfg, executor, max_tokens: int, max_slots: int,
                batch_multiple: int = None):
    from repro.core.decoder import DecodeConfig
    from repro.data.tokenizer import ByteTokenizer
    from repro.serving import ContinuousEngine, PrefixKVPool
    dcfg = DecodeConfig(method="streaming", gen_len=max_tokens,
                        block_size=cfg.block_size, use_kernels=True)
    # pad_pow2: gang sizes 1/2/4/8 only, so pre-warm covers every
    # shape admission, compaction and merges can produce; a
    # batch_multiple of max_slots pads every gang to one size. One free
    # KV buffer kept for reuse: the chip has no room to park one per
    # size.
    return ContinuousEngine(cfg, executor.params, dcfg,
                            max_slots=max_slots, pad_pow2=True,
                            batch_multiple=batch_multiple,
                            executor=executor,
                            pool=PrefixKVPool(cfg, max_free=1,
                                              executor=executor),
                            tokenizer=ByteTokenizer(cfg.vocab_size))


def prewarm(engines, prompt_len: int, max_tokens: int) -> list:
    """Pre-warm every engine, one thread each (the compiles of
    different chips overlap)."""
    from repro.serving.types import round_up_blocks
    gen = round_up_blocks(max_tokens, engines[0].dcfg.block_size)
    with ThreadPoolExecutor(len(engines)) as pool:
        return list(pool.map(lambda e: e.prewarm([(prompt_len, gen)]),
                             engines))


def _rel_err(a, b) -> float:
    import jax.numpy as jnp
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@contextlib.contextmanager
def _attention_inputs_in_bf16(on: bool):
    """While tracing, round the attention kernel's q, k and v to bf16
    (and back): the control that ``LOGIT_RTOL`` must reject."""
    import jax.numpy as jnp

    from repro.kernels import ops
    real = ops.block_attention
    if on:
        def rnd(x):
            return x.astype(jnp.bfloat16).astype(x.dtype)

        def rounded(q, k, v, *args, **kw):
            return real(rnd(q), rnd(k), rnd(v), *args, **kw)
        ops.block_attention = rounded
    try:
        yield
    finally:
        ops.block_attention = real


def _forwards(cfg, prompt_len: int, gen_len: int):
    """Jitted prefill and first denoise step of the streaming method
    (``apply_model``): ``prefill(params, toks, uk) -> (logits, cache)``
    and ``step(params, cache, batch, uk, bf16_attention) -> logits``;
    ``uk`` routes attention to the Pallas kernel."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core.decoder import DecodeConfig
    from repro.core.suffix import suffix_query_region
    from repro.models.model import apply_model, init_cache

    P, T = prompt_len, prompt_len + gen_len
    d = DecodeConfig(method="streaming", gen_len=gen_len,
                     block_size=cfg.block_size)
    region = suffix_query_region(
        gen_start=P, gen_len=gen_len, block_size=cfg.block_size,
        block_idx=0, window=d.effective_window)
    positions = np.asarray(region.positions, np.int32)

    @functools.partial(jax.jit, static_argnames="uk")
    def prefill(p, toks, uk):
        out = apply_model(cfg, p, tokens=toks, mode="encode",
                          cache=init_cache(cfg, toks.shape[0], T),
                          use_kernels=uk)
        return out.logits, out.cache

    @functools.partial(jax.jit,
                       static_argnames=("batch", "uk", "bf16_attention"))
    def step(p, cache, batch, uk, bf16_attention=False):
        qpos = jnp.broadcast_to(jnp.asarray(positions)[None],
                                (batch, len(positions)))
        with _attention_inputs_in_bf16(bf16_attention):
            return apply_model(
                cfg, p, tokens=jnp.full(qpos.shape, cfg.mask_token_id,
                                        jnp.int32),
                positions=qpos, mode="step", cache=cache,
                kv_valid=jnp.full((batch,), P, jnp.int32),
                use_kernels=uk).logits

    return prefill, step


def kernel_parity(cfg, params, prompt_len: int = PROMPT_LEN,
                  gen_len: int = MAX_TOKENS, batch: int = 2,
                  seed: int = 0) -> dict:
    """One prefill and one denoise-step forward with the Pallas kernels
    and with the jnp reference on the same inputs, all under float32
    matmuls; returns the relative L2 error of the kernel logits, and of
    the kernel step with its q, k and v rounded to bf16 (the control)."""
    import jax
    import jax.numpy as jnp

    prefill, step = _forwards(cfg, prompt_len, gen_len)
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, 256, (batch, prompt_len)),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref, cache = prefill(params, toks, False)
        ker, _ = prefill(params, toks, True)
        err_prefill = _rel_err(ker, ref)
        del ref, ker
        ref = step(params, cache, batch, False)
        err_step = _rel_err(step(params, cache, batch, True), ref)
        err_bf16 = _rel_err(step(params, cache, batch, True, True), ref)
    return {"prefill": err_prefill, "step": err_step,
            "step_bf16_attention": err_bf16}


def gang_witness(cfg, params, prompt_len: int = PROMPT_LEN,
                 gen_len: int = MAX_TOKENS, gang: int = FLEET_GANG,
                 seed: int = 0) -> dict:
    """One row's first-step logits (kernels on) alone and as row 0 of
    two gangs of ``gang`` rows with different mates, one padded as the
    scheduler pads (row 0 repeated). Per matmul precision ("highest":
    float32; "default": what serving runs, bf16 passes on TPU):
    ``cross_row``, the largest |difference| of row 0 between the two
    gangs, is nonzero only if rows leak into each other; ``gang_size``
    is the relative L2 difference between the row alone and in a gang,
    and ``argmax_agree`` the share of its positions whose argmax is the
    same in both."""
    import jax
    import jax.numpy as jnp

    prefill, step = _forwards(cfg, prompt_len, gen_len)
    rows = np.random.default_rng(seed).integers(
        0, 256, (2 * gang - 2, prompt_len))
    gangs = {"alone": rows[:1],
             "padded": np.concatenate([rows[:gang - 1], rows[:1]]),
             "other": np.concatenate([rows[:1], rows[gang - 1:]])}
    out = {}
    for prec in ("highest", "default"):
        scope = jax.default_matmul_precision(prec) if prec == "highest" \
            else contextlib.nullcontext()
        row0 = {}
        with scope:
            for name, toks in gangs.items():
                _, cache = prefill(params, jnp.asarray(toks, jnp.int32),
                                   True)
                row0[name] = np.asarray(step(params, cache, len(toks),
                                             True)[0])
                del cache
        alone, padded = row0["alone"], row0["padded"]
        out[prec] = {
            "cross_row": float(np.abs(padded - row0["other"]).max()),
            "gang_size": _rel_err(alone, padded),
            "argmax_agree": float(np.mean(alone.argmax(-1)
                                          == padded.argmax(-1)))}
    return out


def _metric(text: str, name: str) -> float:
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise SmokeFailure(f"/metrics has no {name}")


async def _serve(engines, prompts, max_tokens: int, timeout_s: float):
    from repro.server import EngineLoop, EngineRouter, HttpFrontend, client
    loops = [EngineLoop(e, index=i) for i, e in enumerate(engines)]
    front = EngineRouter(loops) if len(loops) > 1 else loops[0]
    frontend = await HttpFrontend(front, port=0).start()
    host, port = frontend.host, frontend.port
    ok = False
    try:
        t0 = time.perf_counter()
        results = await asyncio.wait_for(asyncio.gather(*[
            client.complete(host, port, {"prompt": p,
                                         "max_tokens": max_tokens})
            for p in prompts]), timeout_s)
        wall = time.perf_counter() - t0
        _, _, body = await client.request(host, port, "GET", "/metrics")
        ok = True
    finally:
        await frontend.shutdown(drain=ok, timeout_s=60.0)
    return results, wall, body.decode()


def serve_and_check(engines, prompts, max_tokens: int,
                    timeout_s: float = SERVE_TIMEOUT_S) -> dict:
    """Serve ``prompts`` concurrently over loopback HTTP through one
    engine or a router over several, and check every request finished
    normally, one host sync per block, and no compile after pre-warm.
    Returns tokens per prompt and per-engine figures."""
    served0 = [e.metrics.snapshot()["requests"] for e in engines]
    results, wall, metrics = asyncio.run(
        _serve(engines, prompts, max_tokens, timeout_s))
    tokens, finish = {}, Counter()
    for prompt, (status, _, doc) in zip(prompts, results):
        check(status == 200, f"HTTP {status}: {doc}")
        finish[doc["finish_reason"]] += 1
        check(doc["finish_reason"] in ("stop", "length"),
              f"request ended with {doc['finish_reason']!r}")
        check(len(doc["tokens"]) <= max_tokens,
              f"{len(doc['tokens'])} tokens > max_tokens {max_tokens}")
        tokens[prompt] = doc["tokens"]
    snaps = [e.metrics.snapshot() for e in engines]
    served = [s["requests"] - s0 for s, s0 in zip(snaps, served0)]
    check(sum(served) == len(prompts), f"engines served {served}")
    syncs = [s["host_syncs_per_block"] for s, n in zip(snaps, served) if n]
    check(all(v == 1.0 for v in syncs), f"host_syncs_per_block {syncs}")
    post_warm = _metric(metrics, "repro_post_warm_compiles_total")
    check(post_warm == 0, f"{post_warm:g} compiles after pre-warm")
    return {"tokens": tokens, "finish": dict(finish), "served": served,
            "wall_s": wall,
            "n_tokens": sum(doc["n_tokens"] for _, _, doc in results),
            "host_syncs_per_block": syncs,
            "post_warm_compiles": post_warm,
            "compile_seconds": sum(s["compile_seconds"] for s in snaps)}


def compile_block(engine, prompt_len: int, max_tokens: int, batch: int):
    """The engine's compiled fused block program for its first block."""
    from repro.serving.types import round_up_blocks
    gen = round_up_blocks(max_tokens, engine.dcfg.block_size)
    dec = engine.scheduler.decoder_for(gen)
    T = prompt_len + gen
    state = dec.prefill(np.ones((batch, prompt_len), np.int32),
                        cache=engine.pool.acquire(batch, T))
    try:
        return dec.lower_block(state).compile()
    finally:
        engine.pool.release(batch, T, state.cache)


def _gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


def _param_bytes(params) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(params))


def run_one_chip(cfg, seed: int) -> None:
    import jax

    from repro.launch.mesh import make_submeshes
    from repro.serving import DecodeExecutor

    t0 = time.perf_counter()
    ex = DecodeExecutor.random_init(cfg, make_submeshes(1)[0], seed=seed)
    print(f"params: {_gib(_param_bytes(ex.params))} "
          f"({time.perf_counter() - t0:.1f}s to init on device)")

    err = kernel_parity(cfg, ex.params, seed=seed)
    print(f"kernel vs reference logits (rel L2, f32 matmuls): "
          f"prefill {err['prefill']:.3e}, step {err['step']:.3e}; "
          f"bound {LOGIT_RTOL:.0e}; control with bf16 attention inputs "
          f"{err['step_bf16_attention']:.3e}")
    check(err["prefill"] <= LOGIT_RTOL and err["step"] <= LOGIT_RTOL,
          f"kernel logits off the reference: {err}")
    check(err["step_bf16_attention"] > LOGIT_RTOL,
          f"bf16 attention passes the bound: {err}")

    wit = gang_witness(cfg, ex.params, seed=seed)
    for prec, w in wit.items():
        print(f"gang witness ({prec} matmuls): row 0 across gang mates "
              f"max |diff| {w['cross_row']:.3e}; alone vs in a gang of "
              f"{FLEET_GANG}: rel L2 {w['gang_size']:.3e}, argmax agrees "
              f"at {w['argmax_agree']:.3f} of positions")
    check(all(w["cross_row"] == 0.0 for w in wit.values()),
          f"a row's logits depend on its gang mates: {wit}")
    check(wit["highest"]["gang_size"] <= LOGIT_RTOL,
          f"a row's logits change with the gang size beyond rounding: "
          f"{wit}")

    eng = make_engine(cfg, ex, MAX_TOKENS, MAX_SLOTS)
    (rep,) = prewarm([eng], PROMPT_LEN, MAX_TOKENS)
    print(f"prewarm: {rep['variants']} variants, batch sizes "
          f"{rep['batch_sizes']}, {rep['seconds']:.1f}s")

    out = serve_and_check([eng], make_prompts(N_REQUESTS, PROMPT_LEN, seed),
                          MAX_TOKENS)
    print(f"served {sum(out['served'])} requests over HTTP: finish "
          f"{out['finish']}, {out['n_tokens']} tokens in "
          f"{out['wall_s']:.2f}s = {out['n_tokens'] / out['wall_s']:.1f} "
          f"tok/s; host_syncs_per_block {out['host_syncs_per_block']}, "
          f"post-warm compiles {out['post_warm_compiles']:g}")

    from repro.kernels.ops import compiled_kernels
    prog = compile_block(eng, PROMPT_LEN, MAX_TOKENS, batch=MAX_SLOTS)
    found = compiled_kernels(prog.as_text())
    mem = prog.memory_analysis()
    print(f"tpu_custom_call kernels in the block program: {sorted(found)}; "
          f"its memory analysis: arguments "
          f"{_gib(mem.argument_size_in_bytes)}, temp "
          f"{_gib(mem.temp_size_in_bytes)}, aliased "
          f"{_gib(mem.alias_size_in_bytes)}")
    check(set(KERNELS) <= found, f"block program calls only {found}")

    from repro.obs.compile import persistent_cache_counters
    pc = persistent_cache_counters()
    print(f"compile: {out['compile_seconds']:.1f}s in variant-building "
          f"calls; persistent cache {pc['hits']} hits, "
          f"{pc['misses']} misses")
    for d in jax.devices():
        print(f"peak_bytes_in_use {d}: "
              f"{_gib(d.memory_stats()['peak_bytes_in_use'])}")


def run_four_chips(cfg, seed: int) -> None:
    import jax

    from repro.launch.mesh import make_submeshes
    from repro.serving import DecodeExecutor

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, have {len(jax.devices())}")
    executors = [DecodeExecutor.random_init(cfg, m, seed=seed)
                 for m in make_submeshes(4)]
    pbytes = _param_bytes(executors[0].params)
    for ex in executors:
        (d,) = ex.mesh.devices.flat
        used = d.memory_stats()["bytes_in_use"]
        print(f"{d}: {_gib(used)} in use after placing "
              f"{_gib(pbytes)} of params")
        check(pbytes <= used < 1.1 * pbytes,
              f"{d} holds {_gib(used)}, not one copy of the params")
    engines = [make_engine(cfg, ex, FLEET_MAX_TOKENS, FLEET_GANG,
                           batch_multiple=FLEET_GANG)
               for ex in executors]
    reps = prewarm(engines, PROMPT_LEN, FLEET_MAX_TOKENS)
    print(f"prewarm: {[r['variants'] for r in reps]} variants, batch "
          f"sizes {reps[0]['batch_sizes']}, "
          f"{max(r['seconds'] for r in reps):.1f}s")

    prompts = make_prompts(N_REQUESTS, PROMPT_LEN, seed)
    one = serve_and_check(engines[:1], prompts, FLEET_MAX_TOKENS)
    four = serve_and_check(engines, prompts, FLEET_MAX_TOKENS)
    for name, out in (("1 engine", one), ("4 engines", four)):
        print(f"{name}: served {out['served']}, {out['n_tokens']} tokens "
              f"in {out['wall_s']:.2f}s, finish {out['finish']}")
    check(all(n >= 1 for n in four["served"]),
          f"a chip served nothing: {four['served']}")
    same = sum(one["tokens"][p] == four["tokens"][p] for p in prompts)
    print(f"tokens identical to the 1-engine run: {same}/{len(prompts)}")
    check(same == len(prompts), "4-engine tokens differ from 1 engine")
    for ex in executors:
        (d,) = ex.mesh.devices.flat
        peak = d.memory_stats()["peak_bytes_in_use"]
        print(f"peak_bytes_in_use {d}: {_gib(peak)}")
        check(peak < 2 * pbytes, f"{d} peaked at {_gib(peak)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        import jax

        from repro.launch.host import enable_compile_cache
        from repro.obs.log import setup_logging
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}",
              file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}; no CPU "
              "fallback", file=sys.stderr)
        return 1
    setup_logging(level="warning")
    enable_compile_cache(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                         or os.path.join(ROOT, "results", "compile_cache"))
    print("smoke figures, not benchmark numbers")
    print(f"device: {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}")
    cfg = model_config()
    print(f"model: {ARCH} widths, {cfg.n_layers} of 32 layers, "
          f"{cfg.dtype}, random weights (seed {args.seed})")
    try:
        if args.chips == 4:
            run_four_chips(cfg, args.seed)
        else:
            run_one_chip(cfg, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
