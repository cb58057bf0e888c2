"""One run of one cell: set up, serve the window, read the metrics,
check the answers, and return the result line.

Set-up (``setup_s``, from process start to the window's start): the
persistent compile cache at ``results/compile_cache/`` in the
checkout, the weights made on the device from the seed, the engine,
and a warm-up that compiles and runs every block variant of the
cell's one shape bucket at every gang size. The window: the traffic
mix over loopback HTTP for ``seconds``; with ``trace`` the profiler
records it. After it: the peak device memory, the program's counters,
the metrics, then the program is freed and the plain reference checks
a sample of the answers.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
from typing import Callable, List, Optional

from bench import cells, correct, devtrace, serve, weights

CACHE_DIR = os.path.join(cells.ROOT, "results", "compile_cache")
TRACE_DIR = os.path.join(cells.ROOT, "results", "bench_trace")


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""
    cell: cells.Cell
    dims: dict
    peaks: Optional[dict]
    t0: float                     # window start, perf_counter seconds
    t1: float
    setup_s: float
    requests: List[serve.Request]
    before: dict                  # ServeMetrics snapshots at the edges
    after: dict
    window_requests: list         # RequestMetrics completed in the window
    prompt_len: int
    gen_len: int
    trace: Optional[dict] = None  # devtrace.collect of the window
    reduced: Optional[dict] = None  # devtrace.reduce of it

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1


class _Hooks:
    def __init__(self, engine, trace: bool, trace_dir: str):
        self.engine = engine
        self.trace = trace
        self.trace_dir = trace_dir
        self.before = self.after = None
        self.n0 = self.n1 = 0
        self._ann = None

    def window_open(self):
        import jax
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            # no Python function tracing: it would slow the host path
            # whose idle gaps the trace is there to show
            opts.python_tracer_level = 0
            opts.raise_error_on_start_failure = True
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.before = self.engine.metrics.snapshot()
        self.n0 = len(self.engine.metrics.requests)

    def window_closed(self):
        import jax
        self.after = self.engine.metrics.snapshot()
        self.n1 = len(self.engine.metrics.requests)
        if self.trace:
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()


def _annotate_blocks(engine, prompt_len: int) -> None:
    """Wrap the decoder's ``decode_block`` in a profiler annotation
    carrying the block's shape, so the trace reduction can give each
    block's kernels their FLOPs and bytes. Only ``--trace 1`` runs."""
    import jax
    dec = engine.scheduler.decoder_for(engine.dcfg.gen_len)
    inner = dec.decode_block

    def decode_block(state):
        live = int((~state.done).sum())
        with jax.profiler.TraceAnnotation(
                "bench.block", batch=state.batch, live=live,
                block=state.block_idx, prompt_len=prompt_len):
            return inner(state)
    dec.decode_block = decode_block


@dataclasses.dataclass
class System:
    """The set-up system under test and the weights it was given."""
    params: object
    executor: object
    engine: object
    dims: dict
    peaks: Optional[dict]


def setup(cell: cells.Cell, seed: int, devices, log=sys.stderr,
          compile_cache: bool = True) -> System:
    """Weights from the seed on the device, the engine, and the warm-up
    of the cell's one shape bucket at every gang size."""
    import jax

    from repro.launch.host import enable_compile_cache
    from repro.launch.mesh import make_submeshes
    from repro.launch.sharding import SpecBuilder
    from repro.models import get_config
    from repro.serving import DecodeExecutor

    config, mix = cell.config, cell.traffic
    dims = cells.model_dims(config)
    dev = devices[0]
    peaks = cells.peaks(dev.device_kind) if dev.platform == "tpu" else None
    if compile_cache:
        enable_compile_cache(CACHE_DIR)
    cfg = get_config(config["arch"], reps=0,
                     **cells.program_overrides(config))
    mesh = make_submeshes(1, devices=devices[:cell.chips])[0]
    shardings = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s),
        SpecBuilder(cfg, mesh, mode="serve").params(),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    dtype = {"float32": jax.numpy.float32,
             "bfloat16": jax.numpy.bfloat16}[cfg.param_dtype]
    params = weights.make(dims, seed, dtype, shardings)
    executor = DecodeExecutor(cfg, params, mesh)
    engine = serve.build_engine(cfg, executor, config, mix["max_tokens"])
    rep = serve.warm(engine, mix["prompt_bytes"], mix["max_tokens"])
    from repro.obs.compile import persistent_cache_counters
    pc = persistent_cache_counters()
    print(f"warm-up: {rep['variants']} variants at gang sizes "
          f"{rep['batch_sizes']} in {rep['seconds']:.1f}s; persistent "
          f"compile cache {pc['hits']} hits, {pc['misses']} misses",
          file=log)
    return System(params, executor, engine, dims, peaks)


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool,
        t_proc: float, devices, log=sys.stderr,
        break_program: Optional[Callable] = None,
        compile_cache: bool = True, control: bool = False) -> dict:
    """``break_program(engine)``, for tests only, plants a fault in the
    timed path after warm-up; tests also leave the process-wide compile
    cache off. With ``control`` (``bench/control.py`` only) the
    reference in the next lower precision stands in the program's place
    in the check, so that ``correct`` is the control's; the program's
    own readings come along as ``program_readings``."""
    mix, dev = cell.traffic, devices[0]
    sut = setup(cell, seed, devices, log, compile_cache)
    params, executor, engine = sut.params, sut.executor, sut.engine
    dims, peaks = sut.dims, sut.peaks
    del sut
    prompt_len = mix["prompt_bytes"]
    if trace:
        _annotate_blocks(engine, prompt_len)
    if break_program is not None:
        break_program(engine)

    hooks = _Hooks(engine, trace, os.path.join(TRACE_DIR, cell.name))
    out = serve.drive(engine, mix, seed, seconds, hooks)
    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    mem_peak = max((s.get("peak_bytes_in_use", 0) for s in stats),
                   default=0)
    r = Run(cell=cell, dims=dims, peaks=peaks,
            t0=out["t0"], t1=out["t1"],
            setup_s=out["t0"] - t_proc, requests=out["requests"],
            before=hooks.before, after=hooks.after,
            window_requests=engine.metrics.requests[hooks.n0:hooks.n1],
            prompt_len=prompt_len, gen_len=engine.dcfg.gen_len)
    print(f"set-up {r.setup_s:.1f}s", file=log)
    if trace:
        r.trace = devtrace.collect(devtrace.load(hooks.trace_dir))
        r.reduced = devtrace.reduce(r.trace)

    metric_defs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in metric_defs:
        value = cells.load_reader(m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    compiles = (hooks.after["post_warm_compiles"]
                - hooks.before["post_warm_compiles"])
    summary = correct.summarize_requests(r, log)

    # the program's state goes before the reference runs: its buffers
    # and programs would otherwise share the chip with the reference
    hooks.engine = None
    del engine, executor, out
    gc.collect()
    checks, program = correct.check(r, params, seed, log, control)
    checks["compiles_in_window"] = {"value": compiles, "limit": 0}
    checks["failed_requests"] = {"value": summary["failed"], "limit": 0}
    ok = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": mem_peak}
    result = {"correct": ok, "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = r.reduced["busy_s"]
        device["window_s"] = r.reduced["window_s"]
        result["breakdown"] = {
            "device_ops": [list(x) for x in r.reduced["device_ops"]],
            "idle_gaps": [list(x) for x in r.reduced["idle_gaps"]]}
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=log)
    if control:
        result["program_readings"] = program
    result["checks"] = checks
    return result
