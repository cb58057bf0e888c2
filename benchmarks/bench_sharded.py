"""Mesh-parallel serving benchmark: decode throughput vs data-shard
count, and 1/2/4 engine loops behind one HTTP front end.

    PYTHONPATH=src python benchmarks/bench_sharded.py \
        [--quick] [--out results/BENCH_sharded.json]

Process model: the parent never imports jax. Every measured
configuration runs in its OWN subprocess whose environment comes from
``repro.launch.host.budget_env`` — per-engine XLA intra-op thread
budget (``cores // engines``), 8 forced host devices, CPU platform.
XLA env is process-global and read once at backend init, so this is
the only honest way to compare engine counts: N engines measured under
the thread budget N engines would actually serve with.

Compile discipline: children enable the persistent compilation cache
(shared across the engine-count sweep, so config 2 reuses config 1's
XLA work) and pre-warm every (shape bucket x method x batch) fused
variant through ``ContinuousEngine.prewarm`` BEFORE the request burst
starts. The measurement window therefore contains zero compiles —
``post_warm_compiles`` is asserted 0 per engine and recorded in the
JSON. The seed benchmark compiled inside the window, per engine, which
is exactly the 1 -> 2 -> 4 engine collapse this PR removes.

Workload: fixed seed (recorded in the JSON) generates the SAME
request mix for every engine count — fixed-length arithmetic prompts,
a synchronized loopback request burst. Per-engine decode-busy seconds,
queue-wait seconds, and steal counts come straight from
``ServeMetrics.snapshot`` (first-class since this PR; the old
trace-replay attribution is gone).

Numbers on a host mesh measure *placement + host-budget overhead*, not
chip speedup — 8 fake devices share one physical CPU. The benchmark's
job is (a) proving the budgeted multi-engine path end to end and (b)
giving real accelerators a ready-made harness.
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

HOST_DEVICES = 8
WORKLOAD_SEED = 3          # also the params PRNG seed: one knob, recorded


def make_workload(seed, clients, per_client):
    """The request mix, identical for every engine count: fixed-length
    single-digit arithmetic prompts (length-12 byte prompts -> one
    shape bucket, so pre-warm covers the whole workload)."""
    rng = np.random.default_rng(seed)
    digits = rng.integers(0, 10, (clients, per_client, 4))
    return [[f"Q:{a}{b}+{c}{d}=? A:" for (a, b, c, d) in row]
            for row in digits]


# --------------------------------------------------------------- child

def child_decode(spec):
    """Decode throughput vs data shards, one process for the sweep
    (shard counts share a decoder compile cache; no serving threads)."""
    import jax
    from repro.core.decoder import DecodeConfig, DiffusionDecoder
    from repro.launch.mesh import make_host_mesh
    from repro.models import get_config, init_params
    from repro.serving import DecodeExecutor

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(spec["seed"]))
    dcfg = DecodeConfig(method="streaming", gen_len=32, block_size=8,
                        window=16)
    rng = np.random.default_rng(spec["seed"])
    prompts = rng.integers(0, 200, (spec["batch"], 10)).astype(np.int32)
    out = []
    for d in spec["shards"]:
        ex = (None if d == 1 else
              DecodeExecutor(cfg, params, make_host_mesh(d, 1)))
        dec = DiffusionDecoder(cfg, params if ex is None else None, dcfg,
                               executor=ex)
        dec.generate(prompts.copy())              # warmup + compile
        t0 = time.perf_counter()
        toks = blocks = 0
        for _ in range(spec["reps"]):
            r = dec.generate(prompts.copy())
            toks += r.tokens_generated
            blocks += len(r.steps_per_block)
        wall = time.perf_counter() - t0
        out.append({"data_shards": d, "batch": spec["batch"],
                    "tok_per_s": round(toks / wall, 2),
                    "ms_per_block": round(1e3 * wall / max(blocks, 1), 2),
                    "devices": 1 if ex is None else len(ex.placement)})
    return {"decode_scaling": out, "n_devices": len(jax.devices()),
            "backend": jax.default_backend()}


async def _burst(host, port, workload, max_tokens):
    """Fire every request concurrently from t0. A closed loop would let
    an N-engine config admit each arrival instantly (queue-wait ~0) and
    decode batch-1 gangs while the 1-engine config batches its backlog
    at max_slots — the rows would measure gang amortization, not engine
    scaling. With the full mix in flight up front, every engine count
    forms the same max_slots-sized gangs over the same requests."""
    from repro.server import client as C

    lat = []

    async def one(p):
        t0 = time.perf_counter()
        status, _, doc = await C.complete(
            host, port, {"prompt": p, "max_tokens": max_tokens})
        assert status == 200, status
        lat.append(time.perf_counter() - t0)

    t0 = time.perf_counter()
    await asyncio.gather(*[one(p) for row in workload for p in row])
    return lat, time.perf_counter() - t0


def child_engines(spec):
    """One engine-count configuration: budgeted process (env set by the
    parent), persistent compile cache, pre-warm, then the request burst."""
    import jax
    from repro.core.decoder import DecodeConfig, round_up_blocks
    from repro.data.tokenizer import ByteTokenizer
    from repro.launch import host as host_budgeting
    from repro.launch.mesh import make_submeshes
    from repro.models import get_config, init_params
    from repro.obs.compile import persistent_cache_counters
    from repro.serving import ContinuousEngine, DecodeExecutor, percentile
    from repro.server import EngineLoop, EngineRouter, HttpFrontend

    n = spec["engines"]
    host_budgeting.enable_compile_cache(spec["cache_dir"])
    budget = host_budgeting.compute_host_budget(n)

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(spec["seed"]))
    dcfg = DecodeConfig(method="streaming", gen_len=32, block_size=8,
                        window=16)
    tok = ByteTokenizer(cfg.vocab_size)
    workload = make_workload(spec["seed"], spec["clients"],
                             spec["per_client"])
    gen_len = round_up_blocks(spec["max_tokens"], dcfg.block_size)
    bucket = (len(tok.encode(workload[0][0])), gen_len)

    meshes = make_submeshes(n, 1, 1)
    engines = [ContinuousEngine(
        cfg, params, dcfg, max_slots=4, tokenizer=tok,
        executor=DecodeExecutor(cfg, params, m), host_budget=budget)
        for m in meshes]
    t0 = time.perf_counter()
    prewarm = [e.prewarm([bucket]) for e in engines]
    prewarm_s = time.perf_counter() - t0
    loops = [EngineLoop(e, max_pending=64, idle_poll_s=0.002, index=i)
             for i, e in enumerate(engines)]
    front = loops[0] if n == 1 else EngineRouter(loops,
                                                steal=spec["steal"])

    async def run():
        fe = await HttpFrontend(front, port=0).start()
        try:
            lat, wall = await _burst(fe.host, fe.port, workload,
                                     spec["max_tokens"])
        finally:
            await fe.shutdown(drain=True, timeout_s=60)
        snaps = [e.metrics.snapshot() for e in engines]
        toks = sum(e.metrics.total_tokens for e in engines)
        return {
            "engines": n, "clients": spec["clients"],
            "requests": sum(len(row) for row in workload),
            "intra_op_threads": budget.intra_op,
            "tok_per_s": round(toks / wall, 2),
            "latency_p50_ms": round(1e3 * percentile(lat, 50), 1),
            "latency_p99_ms": round(1e3 * percentile(lat, 99), 1),
            "prewarm_s": round(prewarm_s, 2),
            "prewarm_variants": sum(r["variants"] for r in prewarm),
            "persistent_cache": dict(persistent_cache_counters()),
            "per_engine": [{
                "requests": s["requests"],
                "decode_busy_s": round(s["busy_time_s"], 3),
                "queue_wait_s": round(s["queue_wait_s"], 3),
                "steals_in": s["steals_in"],
                "steals_out": s["steals_out"],
                "compile_misses": s["compile_misses"],
                "post_warm_compiles": s["post_warm_compiles"],
            } for s in snaps],
        }

    rec = asyncio.run(run())
    post = sum(e["post_warm_compiles"] for e in rec["per_engine"])
    assert post == 0, (
        f"{post} compile(s) inside the measurement window — pre-warm "
        f"missed a variant (see repro_post_warm_compiles_total)")
    return rec


# -------------------------------------------------------------- parent

def _spawn(mode, spec, engines_for_budget):
    """Run one child config in a fresh budgeted process; its last
    stdout line is the JSON result."""
    from repro.launch import host as host_budgeting
    budget = host_budgeting.compute_host_budget(engines_for_budget)
    env = host_budgeting.budget_env(budget, host_devices=HOST_DEVICES,
                                   platform="cpu")
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", mode,
         "--spec", json.dumps(spec)],
        env=env, capture_output=True, text=True, timeout=3000)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise RuntimeError(f"child {mode} {spec} failed")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: fewer shard counts and requests")
    ap.add_argument("--out", default="results/BENCH_sharded.json")
    ap.add_argument("--cache-dir", default="results/compile_cache",
                    help="persistent XLA compile cache shared across "
                         "the engine-count sweep")
    ap.add_argument("--no-steal", action="store_true",
                    help="disable block-boundary work stealing")
    ap.add_argument("--child", default="", choices=["", "decode",
                                                    "engines"])
    ap.add_argument("--spec", default="{}", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        fn = child_decode if args.child == "decode" else child_engines
        print(json.dumps(fn(json.loads(args.spec))))
        return

    shards = (1, 2) if args.quick else (1, 2, 4)
    engine_counts = (1, 2) if args.quick else (1, 2, 4)
    # full mode: enough concurrent clients that EVERY engine count can
    # form max_slots-sized gangs (16 clients / 4 engines = 4 rows each)
    # — otherwise small fleets win on batch amortization alone and the
    # comparison measures workload shape, not the serving stack
    clients = 2 if args.quick else 16
    per_client = 2

    print("== decode throughput vs data shards ==")
    dec = _spawn("decode", {"seed": WORKLOAD_SEED, "shards": list(shards),
                            "batch": 8,
                            "reps": 1 if args.quick else 3},
                 engines_for_budget=1)
    for r in dec["decode_scaling"]:
        print(f"  decode data={r['data_shards']}: {r['tok_per_s']} tok/s "
              f"({r['ms_per_block']} ms/block)")

    print("== engine loops behind one front end (budgeted processes) ==")
    engines = []
    for n in engine_counts:
        rec = _spawn("engines", {
            "seed": WORKLOAD_SEED, "engines": n, "clients": clients,
            "per_client": per_client, "max_tokens": 16,
            "cache_dir": os.path.abspath(args.cache_dir),
            "steal": not args.no_steal}, engines_for_budget=n)
        print(f"  engines={n} ({rec['intra_op_threads']} thread(s) each): "
              f"{rec['tok_per_s']} tok/s "
              f"p50={rec['latency_p50_ms']}ms "
              f"p99={rec['latency_p99_ms']}ms "
              f"split={[e['requests'] for e in rec['per_engine']]} "
              f"busy={[e['decode_busy_s'] for e in rec['per_engine']]} "
              f"steals={sum(e['steals_in'] for e in rec['per_engine'])} "
              f"prewarm={rec['prewarm_s']}s")
        engines.append(rec)

    doc = {"arch": "tiny", "method": "streaming",
           "workload_seed": WORKLOAD_SEED,
           "n_devices": dec["n_devices"], "backend": dec["backend"],
           "host_cores": os.cpu_count(),
           "steal": not args.no_steal,
           "note": ("host-mesh CPU run: subprocess-per-config with "
                    "per-engine thread budgets (repro.launch.host), "
                    "persistent compile cache + pre-warm (zero compiles "
                    "inside the measurement window); real scaling needs "
                    "real chips"),
           "decode_scaling": dec["decode_scaling"],
           "engine_scaling": engines}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {args.out}")
    append_history(args.out, doc)


if __name__ == "__main__":
    main()
