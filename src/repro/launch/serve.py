"""Serving launcher: load a checkpoint (or train briefly), start the
engine in continuous or synchronous-batch mode, and serve synthetic
requests with the selected method.

    PYTHONPATH=src python -m repro.launch.serve --arch tiny \
        --method streaming --n 32 --mode continuous \
        [--ckpt results/bench_model] [--stream]

or serve over HTTP (SSE streaming, /healthz, /metrics):

    PYTHONPATH=src python -m repro.launch.serve --arch tiny --http 8000
    curl -N localhost:8000/v1/completions \
        -d '{"prompt": "Q:12+34=? A:", "max_tokens": 16, "stream": true}'

or mesh-parallel / multi-engine (one EngineLoop per submesh, requests
routed least-loaded; on CPU use --force-host-devices to fake chips):

    PYTHONPATH=src python -m repro.launch.serve --arch tiny --http 8000 \
        --mesh 2,1 --engines 2 --force-host-devices 4

Multi-engine hosts should also budget and pre-warm (repro.launch.host):

    ... --engines 2 --host-threads-per-engine 2 \
        --compile-cache-dir results/compile_cache --prewarm 16:32

or disaggregate prefill from decode (one shared prefix store; primed
requests hand off prefill pool -> decode pool at admission):

    PYTHONPATH=src python -m repro.launch.serve --arch tiny --http 8000 \
        --prefix-cache --pool prefill:1,decode:2

Quality auditing + post-mortems (repro.obs.audit, HTTP mode):

    ... --http 8000 --audit-rate 0.05 --audit-oracle auto \
        --flight-dir results/flight --slo-ttfb-p50-ms 500
"""
from __future__ import annotations

import argparse
import os

import numpy as np


def _parse_mesh(s: str):
    try:
        data, model = (int(v) for v in s.split(","))
    except ValueError:
        raise SystemExit(f"--mesh wants 'data,model' ints, got {s!r}")
    return data, model


def _parse_pool(s: str):
    """``"prefill:N,decode:M"`` -> {"prefill": N, "decode": M}."""
    sizes = {"prefill": 0, "decode": 0}
    for part in s.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            role, n = part.split(":")
            sizes[role.strip()] += int(n)
        except (ValueError, KeyError):
            raise SystemExit(
                f"--pool wants 'prefill:N,decode:M', got {part!r}")
    if sizes["decode"] < 1:
        raise SystemExit("--pool needs at least one decode engine "
                         "(prefill-only engines can never finish a "
                         "request)")
    if sizes["prefill"] < 1:
        raise SystemExit("--pool without a prefill engine is plain "
                         "--engines; drop the flag")
    return sizes


def _parse_prewarm(s: str):
    """``"P:G[,P:G...]"`` -> [(prompt_len, gen_len), ...]."""
    buckets = []
    for part in s.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            p, g = (int(v) for v in part.split(":"))
        except ValueError:
            raise SystemExit(
                f"--prewarm wants 'P:G[,P:G...]' ints, got {part!r}")
        buckets.append((p, g))
    return buckets


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--method", default="streaming",
                    choices=["vanilla", "dkv", "prefix", "fast", "streaming"])
    ap.add_argument("--mode", default="continuous",
                    choices=["continuous", "batch"])
    ap.add_argument("--n", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--max-slots", type=int, default=8,
                    help="continuous mode: concurrent decode lanes")
    ap.add_argument("--window", type=int, default=16)
    ap.add_argument("--tau0", type=float, default=0.9)
    ap.add_argument("--alpha", type=float, default=0.3)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--train-steps", type=int, default=600)
    ap.add_argument("--stream", action="store_true",
                    help="print per-block chunks as they commit")
    ap.add_argument("--use-kernels", action="store_true",
                    help="route attention/confidence through the Pallas "
                         "kernels (compiled on TPU, interpreted elsewhere)")
    ap.add_argument("--host-loop", action="store_true",
                    help="legacy per-step host denoise loop instead of "
                         "the fused device-resident loop")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request prefix KV cache (repro.cache): "
                         "chunk-aligned prompt prefill, radix-tree "
                         "content matching, cache-affinity routing "
                         "with --engines > 1")
    ap.add_argument("--cache-chunk", type=int, default=16,
                    help="prefix-cache chunk size in prompt tokens")
    ap.add_argument("--cache-bytes", type=int, default=256 << 20,
                    help="prefix-cache byte budget per engine (LRU "
                         "eviction beyond it)")
    ap.add_argument("--http", type=int, default=0, metavar="PORT",
                    help="serve over HTTP on this port instead of the "
                         "synthetic in-process workload (continuous "
                         "mode only; Ctrl-C drains gracefully)")
    ap.add_argument("--http-host", default="127.0.0.1")
    ap.add_argument("--max-pending", type=int, default=64,
                    help="HTTP mode: bounded admission queue; beyond "
                         "this, POSTs get 429 + Retry-After")
    ap.add_argument("--mesh", default="", metavar="DATA,MODEL",
                    help="per-engine mesh dims, e.g. 2,2: batch shards "
                         "over the data axis, attention/FFN over model "
                         "(DecodeExecutor placement layer); empty = "
                         "single-device")
    ap.add_argument("--engines", type=int, default=1, metavar="N",
                    help="engine loops, one per disjoint submesh, "
                         "behind one HTTP front end (least-loaded "
                         "routing; HTTP mode only for N > 1)")
    ap.add_argument("--pool", default="", metavar="prefill:N,decode:M",
                    help="disaggregated engine pools: N prefill-only "
                         "engines prime prompt KV into ONE shared "
                         "prefix store and hand each request off to "
                         "one of M decode engines (implies --engines "
                         "N+M; needs --http and --prefix-cache)")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    help="fake this many host devices via XLA_FLAGS "
                         "(CI/demo; must be >= engines * data * model)")
    ap.add_argument("--host-threads-per-engine", type=int, default=0,
                    metavar="T",
                    help="XLA:CPU intra-op threads each engine's "
                         "dispatches may use; 0 = cores // engines "
                         "(repro.launch.host, applied before jax init)")
    ap.add_argument("--compile-cache-dir", default="", metavar="DIR",
                    help="JAX persistent compilation cache: restarts "
                         "and sibling engine processes reuse compiled "
                         "fused-block variants instead of recompiling")
    ap.add_argument("--prewarm", default="", metavar="P:G[,P:G...]",
                    help="compile every fused-block variant for these "
                         "(prompt_len:gen_len) shape buckets on every "
                         "engine BEFORE the HTTP front end admits "
                         "traffic; later compiles log loudly and count "
                         "in repro_post_warm_compiles_total")
    ap.add_argument("--no-steal", action="store_true",
                    help="disable block-boundary work stealing between "
                         "engine loops (--engines > 1)")
    ap.add_argument("--trace-dir", default="", metavar="DIR",
                    help="record request span trees + decode timelines "
                         "and write Chrome-trace JSON (Perfetto-"
                         "loadable) into DIR on shutdown (HTTP mode)")
    ap.add_argument("--trace-flush-s", type=float, default=0.0,
                    metavar="S",
                    help="with --trace-dir: also rewrite trace.json "
                         "atomically every S seconds, so a crashed run "
                         "keeps its trace up to the last flush")
    ap.add_argument("--audit-rate", type=float, default=0.0,
                    metavar="FRAC",
                    help="shadow-audit this fraction of completed "
                         "requests: re-decode on a low-priority lane "
                         "through the host-loop oracle and/or a cold "
                         "(cache-bypass) path and compare tokens "
                         "bit-for-bit (repro.obs.audit; 0 = off)")
    ap.add_argument("--audit-oracle", default="auto",
                    choices=["host", "cold", "both", "auto"],
                    help="audit lanes: 'host' flips the fused loop, "
                         "'cold' bypasses the prefix cache, 'both' runs "
                         "each, 'auto' picks every lane the engine "
                         "config supports")
    ap.add_argument("--flight-dir", default="", metavar="DIR",
                    help="flight recorder: on SLO breach, audit "
                         "divergence, crash, or GET /debug/flight, dump "
                         "trace ring buffers + metrics snapshot + "
                         "scheduler/gang state under DIR")
    ap.add_argument("--metrics-log", default="", metavar="PATH",
                    help="append every time-series recorder sample "
                         "(repro.obs.series) as a JSON line to PATH "
                         "(HTTP mode; the in-memory ring behind "
                         "/debug/timeline and /console is always on)")
    ap.add_argument("--metrics-interval-s", type=float, default=0.5,
                    metavar="S",
                    help="recorder sampling interval per engine "
                         "(0 disables the recorders entirely)")
    ap.add_argument("--slo-ttfb-p50-ms", type=float, default=0.0,
                    help="SLO watchdog: rolling TTFB p50 target in ms "
                         "(breach dumps a flight recording; 0 = off)")
    ap.add_argument("--slo-token-latency-ms", type=float, default=0.0,
                    help="SLO watchdog: rolling per-token latency p50 "
                         "target in ms (0 = off)")
    ap.add_argument("--slo-goodput-tok-s", type=float, default=0.0,
                    help="SLO watchdog: rolling completed-tokens/s "
                         "floor (0 = off)")
    ap.add_argument("--profile-blocks", type=int, default=0, metavar="N",
                    help="capture a jax.profiler trace over the first "
                         "N decoded blocks (written under --trace-dir, "
                         "or results/profile), without Python function "
                         "tracing: it shows the program's decoder.*, "
                         "scheduler.*, engine.* and loop.wait spans, as "
                         "the chip benchmark's capture does")
    ap.add_argument("--log-level", default="info",
                    choices=["debug", "info", "warning", "error"])
    ap.add_argument("--log-json", action="store_true",
                    help="JSON-lines log records instead of text")
    args = ap.parse_args()

    from repro.obs.log import setup_logging
    setup_logging(level=args.log_level, json_mode=args.log_json)

    # flag validation up front — nothing below may cost the user a
    # training run or N param placements before a SystemExit
    if args.engines > 1 and not args.http:
        raise SystemExit("--engines N > 1 needs --http (the router lives "
                         "in the HTTP front end)")
    if args.mesh and not args.http and args.mode != "continuous":
        raise SystemExit("--mesh needs continuous mode or --http (the "
                         "placement layer drives the continuous engine; "
                         "the legacy batch engine is single-device)")
    if args.prefix_cache and args.method == "vanilla":
        raise SystemExit("--prefix-cache has no effect with --method "
                         "vanilla (no KV cache to reuse)")
    pool_sizes = _parse_pool(args.pool) if args.pool else None
    if pool_sizes is not None:
        if not args.http:
            raise SystemExit("--pool needs --http (the prefill->decode "
                             "handoff rides the EngineRouter in the "
                             "HTTP front end)")
        if not args.prefix_cache:
            raise SystemExit("--pool needs --prefix-cache (primed "
                             "prompt KV travels through the shared "
                             "prefix store)")
        n_pool = pool_sizes["prefill"] + pool_sizes["decode"]
        if args.engines not in (1, n_pool):
            raise SystemExit(f"--pool {args.pool} implies --engines "
                             f"{n_pool}, got --engines {args.engines}")
        args.engines = n_pool
    slo_targets = {"ttfb_p50_s": args.slo_ttfb_p50_ms / 1e3,
                   "token_latency_s": args.slo_token_latency_ms / 1e3,
                   "goodput_tok_s": args.slo_goodput_tok_s}
    if not args.http:
        for flag, on in (("--audit-rate", args.audit_rate > 0),
                         ("--flight-dir", bool(args.flight_dir)),
                         ("--slo-*", any(slo_targets.values())),
                         ("--trace-flush-s", args.trace_flush_s > 0),
                         ("--metrics-log", bool(args.metrics_log))):
            if on:
                raise SystemExit(f"{flag} needs --http (the audit/SLO/"
                                 "flight layer rides the HTTP serving "
                                 "loop)")
    if not 0.0 <= args.audit_rate <= 1.0:
        raise SystemExit(f"--audit-rate wants [0, 1], got "
                         f"{args.audit_rate}")
    if args.trace_flush_s > 0 and not args.trace_dir:
        raise SystemExit("--trace-flush-s needs --trace-dir (it "
                         "rewrites DIR/trace.json periodically)")
    mesh_dims = _parse_mesh(args.mesh) if args.mesh else None
    prewarm_buckets = _parse_prewarm(args.prewarm) if args.prewarm else []

    # host env knobs (thread budget, fake devices) must land before the
    # first jax backend init — repro.launch.host is the one sanctioned
    # XLA-env mutation point (scripts/test.sh lint enforces this)
    from repro.launch import host as host_budgeting
    budget = host_budgeting.compute_host_budget(
        args.engines, args.host_threads_per_engine)
    pool_budgets = None
    if pool_sizes is not None:
        pool_budgets = host_budgeting.compute_pool_budgets(
            pool_sizes, args.host_threads_per_engine)
    host_budgeting.apply_host_budget(budget)
    if args.force_host_devices:
        host_budgeting.force_host_device_count(args.force_host_devices)

    import jax

    if args.compile_cache_dir:
        host_budgeting.enable_compile_cache(args.compile_cache_dir)
        print(f"persistent compile cache at {args.compile_cache_dir}")
    print(f"host budget: {budget.describe()}")
    if pool_budgets is not None:
        for role in ("prefill", "decode"):
            print(f"pool {role}: {pool_budgets[role].describe()}")
    from repro.core.decoder import DecodeConfig
    from repro.core.engine import ServingEngine
    from repro.data.synthetic import ArithmeticDataset
    from repro.data.tokenizer import ByteTokenizer
    from repro.models import get_config, init_params
    from repro.training import checkpoint
    from repro.training.train import TrainConfig, train

    if mesh_dims is not None:
        # jax is up: the device-count precondition costs nothing to
        # check here, and failing inside make_submeshes would waste a
        # checkpoint restore or a whole training run first
        need = args.engines * mesh_dims[0] * mesh_dims[1]
        if len(jax.devices()) < need:
            raise SystemExit(
                f"--mesh {args.mesh} x --engines {args.engines} needs "
                f"{need} devices, have {len(jax.devices())} "
                f"(--force-host-devices {need} fakes them on CPU)")

    cfg = get_config(args.arch, block_size=8)
    if args.ckpt:
        params = checkpoint.restore(args.ckpt, jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0))))
    else:
        params, _ = train(cfg, TrainConfig(steps=args.train_steps,
                                           batch_size=32, seq_len=44))
    d = DecodeConfig(method=args.method, gen_len=args.gen_len, block_size=8,
                     window=args.window, tau0=args.tau0, alpha=args.alpha,
                     use_kernels=args.use_kernels, fused=not args.host_loop,
                     prefix_cache=args.prefix_cache,
                     cache_chunk=args.cache_chunk)
    tok = ByteTokenizer(cfg.vocab_size)

    # placement: one DecodeExecutor per engine submesh (None = the
    # single-device path); params are placed per mesh, caches are born
    # sharded, gang batches shard over the data axis. Several engines
    # on a host with enough devices get one device each even without
    # --mesh. The params go through host memory first, so each device
    # holds exactly one copy (the default device none extra).
    executors = [None] * args.engines
    if mesh_dims is None and args.engines > 1:
        if args.engines <= len(jax.devices()):
            mesh_dims = (1, 1)
        elif jax.default_backend() != "cpu":
            raise SystemExit(
                f"--engines {args.engines} needs one device each, have "
                f"{len(jax.devices())} (pass --mesh to place them)")
        else:
            print(f"all {args.engines} engines run on the default CPU "
                  "device (the host budget splits its cores)")
    if mesh_dims is not None:
        from repro.launch.mesh import make_submeshes
        from repro.serving import DecodeExecutor
        params = jax.device_get(params)
        executors = [DecodeExecutor(cfg, params, m)
                     for m in make_submeshes(args.engines, *mesh_dims)]

    # disaggregated pools share ONE store: the prefill pool publishes
    # chunk KV into it, the decode pool's admission prefill finds the
    # full hit. Keyed by mesh *shape* (numerics are placement-shape-
    # dependent, not device-id-dependent), so every same-shape engine
    # may read it.
    shared_store = None
    if pool_sizes is not None:
        from repro.cache import HOST_PLACEMENT, PrefixKVCache
        shared_store = PrefixKVCache(
            chunk_tokens=args.cache_chunk, max_bytes=args.cache_bytes,
            placement=(executors[0].shape_key
                       if executors[0] is not None else HOST_PLACEMENT),
            shared=True)

    def make_engine(ex, role: str = "both"):
        from repro.serving import ContinuousEngine
        store = None
        if args.prefix_cache:
            if shared_store is not None:
                store = shared_store
            else:
                # one store per engine (placement-bound, like the KV
                # pool); the router's cache-affinity policy relies on
                # that split
                from repro.cache import HOST_PLACEMENT, PrefixKVCache
                store = PrefixKVCache(
                    chunk_tokens=args.cache_chunk,
                    max_bytes=args.cache_bytes,
                    placement=ex.placement if ex is not None
                    else HOST_PLACEMENT)
        return ContinuousEngine(cfg, params if ex is None else ex.params,
                                d, max_slots=args.max_slots,
                                tokenizer=tok, executor=ex,
                                prefix_cache=store,
                                prefill_only=(role == "prefill"),
                                host_budget=(pool_budgets[role]
                                             if pool_budgets is not None
                                             else budget))

    tracer = None
    if args.trace_dir:
        from repro.obs.trace import Tracer
        tracer = Tracer()

    def attach_profiler(engine):
        if args.profile_blocks > 0:
            # jax.profiler traces are process-global: exactly one
            # engine may own the capture window
            from repro.obs.profiler import BlockProfiler
            engine.profiler = BlockProfiler(
                args.trace_dir or "results/profile", args.profile_blocks)

    def export_trace():
        if tracer is not None:
            path = os.path.join(args.trace_dir, "trace.json")
            tracer.export(path)
            print(f"chrome trace written to {path} "
                  f"(open in ui.perfetto.dev)")

    def prewarm_all(engines):
        if not prewarm_buckets:
            return
        # sequential, before the front end opens admission: every
        # (shape bucket x method x placement) fused-block variant is
        # compiled now, so steady-state traffic never pays a compile
        for i, eng in enumerate(engines):
            rep = eng.prewarm(prewarm_buckets)
            print(f"engine-{i} prewarmed {rep['variants']} variant(s) "
                  f"over {len(rep['buckets'])} bucket(s) in "
                  f"{rep['seconds']:.1f}s")

    if args.http:
        from repro.server import run as run_http
        roles = None
        if pool_sizes is not None:
            roles = (["prefill"] * pool_sizes["prefill"]
                     + ["decode"] * pool_sizes["decode"])
        engines = [make_engine(ex, roles[i] if roles else "both")
                   for i, ex in enumerate(executors)]
        attach_profiler(engines[0])
        prewarm_all(engines)
        audit = None
        if args.audit_rate > 0:
            from repro.obs import AuditConfig
            audit = AuditConfig(sample_rate=args.audit_rate,
                                oracle=args.audit_oracle)
        watchdog = None
        if any(slo_targets.values()):
            from repro.obs import SLOWatchdog
            watchdog = SLOWatchdog(
                **{k: (v or None) for k, v in slo_targets.items()})
        flight = None
        if args.flight_dir:
            from repro.obs import FlightRecorder
            flight = FlightRecorder(args.flight_dir, tracer=tracer)
        flusher = None
        if tracer is not None and args.trace_flush_s > 0:
            from repro.obs import TraceFlusher
            flusher = TraceFlusher(
                tracer, os.path.join(args.trace_dir, "trace.json"),
                interval_s=args.trace_flush_s).start()
        try:
            run_http(engines if len(engines) > 1 else engines[0],
                     host=args.http_host, port=args.http,
                     max_pending=args.max_pending, tracer=tracer,
                     steal=not args.no_steal, audit=audit,
                     watchdog=watchdog, flight=flight, roles=roles,
                     metrics_interval_s=args.metrics_interval_s,
                     metrics_log=args.metrics_log or None)
        finally:
            if flusher is not None:
                flusher.stop(final_flush=False)
            export_trace()
        return
    ds = ArithmeticDataset(tok, seq_len=44)
    samples = ds.eval_set(args.n)
    if args.mode == "continuous":
        eng = make_engine(executors[0])
        if tracer is not None:
            eng.set_tracer(tracer, "engine-0")
        attach_profiler(eng)
        prewarm_all([eng])
        for s in samples:
            eng.submit(s.prompt, max_tokens=args.gen_len,
                       trace_id=tracer.new_trace_id()
                       if tracer is not None else "")
        if args.stream:
            done = []
            eng.on_chunk(None, lambda ch: print(
                f"  uid={ch.uid} block={ch.block_idx} "
                f"{'[done] ' if ch.finished else ''}{ch.text!r}"))
            while not eng.scheduler.idle:
                done.extend(eng.step())
        else:
            done = eng.run_to_completion()
        snap = eng.metrics.snapshot()
        hits = sum(int(c.text.strip() == s.answer)
                   for c, s in zip(sorted(done, key=lambda c: c.uid), samples))
        print(f"mode=continuous method={args.method} served={len(done)} "
              f"acc={hits/len(done):.2f} tok/s={snap['throughput_tok_s']:.1f} "
              f"p50={snap['latency_p50_s']*1e3:.0f}ms "
              f"p99={snap['latency_p99_s']*1e3:.0f}ms "
              f"ttfb_p50={snap['ttfb_p50_s']*1e3:.0f}ms "
              f"occ={snap['mean_occupancy']:.2f} "
              f"merges={snap['gang_merges']} "
              + (f"cache_hit_toks={snap['prefix_cache_hit_tokens']} "
                 if args.prefix_cache else "") +
              f"syncs/blk={snap['host_syncs_per_block']:.2f} "
              f"steps/blk={snap['device_steps_per_block']:.2f} "
              f"jit_cache={eng.jit_cache_size()}")
        export_trace()
        return
    eng = ServingEngine(cfg, params, d, mode="batch")
    for s in samples:
        eng.submit(s.prompt, max_tokens=args.gen_len)
    done = eng.run_to_completion()
    hits = sum(int(c.text.strip() == s.answer)
               for c, s in zip(sorted(done, key=lambda c: c.uid), samples))
    print(f"mode=batch method={args.method} served={len(done)} "
          f"acc={hits/len(done):.2f} tok/s={eng.throughput:.1f}")


if __name__ == "__main__":
    main()
