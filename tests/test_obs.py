"""Observability layer tests (`repro.obs`): tracer ring buffers and
Chrome-trace export, request span-tree well-formedness across the
request lifecycle (finish, cancel, preempt/resume, deadline), per-block
decode telemetry invariants for every method (fused and host loops,
with zero extra host syncs), ServeMetrics thread-safety under a
decode-thread/scrape-thread hammer, Prometheus histogram exposition,
and structured JSON logging."""
import asyncio
import contextlib
import io
import json
import logging
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.decoder import DecodeConfig
from repro.data.tokenizer import ByteTokenizer
from repro.models import get_config, init_params
from repro.obs.log import get_logger, setup_logging
from repro.obs.metrics import Histogram, device_memory_stats
from repro.obs.telemetry import (CONF_BUCKETS, BlockStats,
                                 TelemetryAggregator)
from repro.obs.trace import Tracer, request_tree, span
from repro.serving import ContinuousEngine
from repro.serving.metrics import RequestMetrics, ServeMetrics

CFG = get_config("tiny")
PARAMS = init_params(CFG, jax.random.PRNGKey(3))
TOK = ByteTokenizer(CFG.vocab_size)
PROMPT = "Q:12+34=? A:"
TEST_TIMEOUT_S = 300
METHODS = ["vanilla", "dkv", "prefix", "fast", "streaming"]


def _dcfg(method="streaming", gen_len=16, fused=True):
    return DecodeConfig(method=method, gen_len=gen_len, block_size=8,
                        window=4, tau0=0.5, fused=fused)


def _engine(method="streaming", gen_len=16, fused=True, max_slots=4,
            tracer=None):
    return ContinuousEngine(CFG, PARAMS, _dcfg(method, gen_len, fused),
                            max_slots=max_slots, tokenizer=TOK,
                            tracer=tracer)


def _run(coro):
    asyncio.run(asyncio.wait_for(coro, TEST_TIMEOUT_S))


# ------------------------------------------------------------ tracer core


def test_tracer_complete_events_and_clock():
    tr = Tracer()
    with tr.span("work", pid=0, tag="x"):
        time.sleep(0.002)
    evs = [e for e in tr.events() if e.get("ph") == "X"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["name"] == "work"
    assert ev["args"] == {"tag": "x"}
    assert ev["dur"] >= 1500                 # >= 1.5ms in microseconds
    assert ev["ts"] >= 0                     # monotonic since birth


def test_tracer_null_span_helper():
    with span(None, "ignored"):              # tracer off: no-op context
        pass
    tr = Tracer()
    with span(tr, "kept"):
        pass
    assert any(e.get("name") == "kept" for e in tr.events())


def _capture(tmp_path, work):
    """Host events of a CPU ``jax.profiler`` capture around ``work``:
    ``{name: [(start_ns, end_ns, stats), ...]}`` from ``/host:CPU``."""
    import glob

    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats)))
    return out


def test_span_lands_in_profiler_capture(tmp_path):
    """One call, two sinks: inside a capture, ``span(None, ...)`` and
    ``span(tracer, ...)`` both land on the host plane with their args
    (``pid`` left out), nested as entered, with ``annotate`` args
    added at the end; ``span(tracer, ...)`` still fills the ring."""
    tr = Tracer()
    pid = tr.process("engine-0")

    def work():
        with span(tr, "decoder.block", pid=pid, batch=8, live=3) as blk:
            with span(None, "decoder.sync"):
                time.sleep(0.002)
            blk.annotate(steps=5, committed=7)
        with span(None, "loop.wait") as w:
            w.annotate(note="idle")

    evs = _capture(tmp_path, work)
    ((b0, b1, bst),) = evs["decoder.block"]
    ((s0, s1, sst),) = evs["decoder.sync"]
    assert bst == {"batch": 8, "live": 3, "steps": 5, "committed": 7}
    assert sst == {}
    assert b0 <= s0 < s1 <= b1 and s1 - s0 >= 1.5e6
    assert evs["loop.wait"][0][2] == {"note": "idle"}
    (ring,) = [e for e in tr.events() if e.get("ph") == "X"]
    assert ring["name"] == "decoder.block" and ring["pid"] == pid
    assert ring["args"] == {"batch": 8, "live": 3, "steps": 5,
                            "committed": 7}
    assert ring["dur"] >= 1500


def test_block_profiler_captures_spans_without_python_tracing(tmp_path):
    """``--profile-blocks``' capture holds the program's spans and no
    Python function events (``$file.py:line name``), which would slow
    the host path between blocks it is there to show."""
    import glob

    from jax.profiler import ProfileData

    from repro.obs.profiler import BlockProfiler
    prof = BlockProfiler(str(tmp_path), n_blocks=2)
    prof.tick(1)                                 # starts the capture
    assert prof.active
    with span(None, "scheduler.admit"):
        sum(range(100))
    prof.tick(1)                                 # second block: stops
    assert prof.done and not prof.active
    (path,) = glob.glob(str(tmp_path / "jax_profile" / "**" /
                            "*.xplane.pb"), recursive=True)
    names = [ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events]
    assert "scheduler.admit" in names
    assert not [n for n in names if ".py:" in n]


def test_scheduler_tick_spans_in_profiler_capture(tmp_path):
    """A served block with no tracer attached still shows the block
    boundary in a capture: ``decoder.block`` with its shape and, at
    exit, steps and commits; the decoder's three sub-spans inside it;
    the scheduler's and the engine's spans around it."""
    eng = _engine(max_slots=2)
    eng.submit(PROMPT, max_tokens=16)

    def work():
        while not eng.scheduler.idle:
            eng.step()

    evs = _capture(tmp_path, work)
    for name in ("scheduler.merge", "scheduler.admit", "scheduler.prefill",
                 "scheduler.harvest", "scheduler.compact", "engine.publish",
                 "decoder.inputs", "decoder.dispatch", "decoder.sync"):
        assert name in evs, name
    blocks = evs["decoder.block"]
    assert len(blocks) == 2
    for (b0, b1, st), sync in zip(blocks, evs["decoder.sync"]):
        assert {"batch", "live", "block", "prompt_len", "steps",
                "committed"} <= set(st)
        assert 1 <= st["steps"] <= 8 and b0 <= sync[0] < sync[1] <= b1
    assert [st["block"] for _, _, st in blocks] == [0, 1]


def test_tracer_ring_capacity_drops_oldest():
    tr = Tracer(capacity_per_thread=8)
    for i in range(20):
        tr.instant(f"ev{i}")
    evs = [e for e in tr.events() if e.get("ph") == "i"]
    assert len(evs) == 8
    assert evs[-1]["name"] == "ev19"         # newest kept
    assert tr.dropped == 12                  # oldest evicted


def test_trace_ids_unique():
    tr = Tracer()
    ids = {tr.new_trace_id() for _ in range(100)}
    assert len(ids) == 100


def test_request_tree_nesting_and_errors():
    tr = Tracer()
    tid = tr.new_trace_id()
    t = time.perf_counter_ns()
    tr.async_begin(tid, "request", t_ns=t)
    tr.async_begin(tid, "queue", t_ns=t + 10)
    tr.async_end(tid, "queue", t_ns=t + 20)
    tr.async_begin(tid, "decode", t_ns=t + 20)   # ties: e before b
    tr.async_end(tid, "decode", t_ns=t + 50)
    tr.async_end(tid, "request", t_ns=t + 60)
    tree = request_tree(tr.request_events(tid))
    assert [(name, depth) for name, depth, _, _ in tree] == \
        [("request", 0), ("queue", 1), ("decode", 1)]
    assert all(dur is not None for _, _, _, dur in tree)
    with pytest.raises(ValueError):          # unclosed span
        request_tree([{"ph": "b", "name": "a", "ts": 1.0}])
    with pytest.raises(ValueError):          # end without begin
        request_tree([{"ph": "e", "name": "a", "ts": 1.0}])


def test_chrome_trace_export_schema(tmp_path):
    tr = Tracer()
    pid = tr.process("engine-0")
    tr.name_thread("decode", pid=pid)
    with tr.span("block", pid=pid):
        pass
    tid = tr.new_trace_id()
    t = time.perf_counter_ns()
    tr.async_span(tid, "request", t, t + 1000, pid=pid)
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    phases = {"M", "X", "b", "e", "i"}
    for e in evs:
        assert e["ph"] in phases
        assert isinstance(e["name"], str)
        assert isinstance(e["pid"], int)
        if e["ph"] != "M":
            assert isinstance(e["ts"], float)
        if e["ph"] == "X":
            assert e["dur"] > 0
        if e["ph"] in ("b", "e"):
            assert e["cat"] == "request" and e["id"] == tid
    # metadata first: process/thread names precede all timed events
    kinds = [e["ph"] for e in evs]
    assert kinds[: kinds.count("M")] == ["M"] * kinds.count("M")
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"frontend", "engine-0", "decode"} <= names


def test_trace_flusher_periodic_and_final(tmp_path):
    from repro.obs.trace import TraceFlusher

    tr = Tracer()
    path = str(tmp_path / "trace.json")
    fl = TraceFlusher(tr, path, interval_s=0.05).start()
    with tr.span("early"):
        pass
    deadline = time.time() + 5.0
    while fl.flushes == 0 and time.time() < deadline:
        time.sleep(0.02)
    assert fl.flushes >= 1
    early = json.loads(open(path).read())["traceEvents"]
    assert any(e.get("name") == "early" for e in early)
    with tr.span("late"):
        pass
    fl.stop()  # final_flush=True picks up spans after the last tick
    assert not fl._thread.is_alive()
    late = json.loads(open(path).read())["traceEvents"]
    assert any(e.get("name") == "late" for e in late)


def test_trace_flusher_stop_without_final_flush(tmp_path):
    from repro.obs.trace import TraceFlusher

    tr = Tracer()
    path = str(tmp_path / "trace.json")
    fl = TraceFlusher(tr, path, interval_s=60.0).start()
    with tr.span("never-flushed"):
        pass
    fl.stop(final_flush=False)
    assert not fl._thread.is_alive()
    import os
    assert not os.path.exists(path)  # no tick fired, no final write


# ------------------------------------------------- span trees (lifecycle)


def _finish_tree(tracer, trace_id):
    """The request's rebuilt span tree (raises if malformed)."""
    return request_tree(tracer.request_events(trace_id))


def test_span_tree_normal_lifecycle():
    tr = Tracer()
    eng = _engine()
    eng.set_tracer(tr, "engine-0")
    tid = tr.new_trace_id()
    eng.submit(PROMPT, max_tokens=13, trace_id=tid)
    eng.run_to_completion()
    tree = _finish_tree(tr, tid)
    names = [name for name, _, _, _ in tree]
    assert names[0] == "request"
    assert "queue" in names and "decode" in names
    blocks = [n for n in names if n.startswith("block ")]
    assert blocks == ["block 0", "block 1"]  # 16 gen tokens / 8
    depth = dict((n, d) for n, d, _, _ in tree)
    assert depth["queue"] == 1 and depth["decode"] == 1
    assert depth["block 0"] == 2             # nested under decode


def test_span_tree_cancel_while_waiting():
    tr = Tracer()
    eng = _engine(max_slots=1)
    eng.set_tracer(tr, "engine-0")
    t1 = tr.new_trace_id()
    t2 = tr.new_trace_id()
    eng.submit(PROMPT, max_tokens=13, trace_id=t1)
    u2 = eng.submit(PROMPT, max_tokens=13, trace_id=t2)
    eng.step()                               # admits only the first
    comp = eng.cancel(u2)                    # still waiting
    assert comp is not None and comp.cancelled
    eng.run_to_completion()
    tree = _finish_tree(tr, t2)              # well-formed despite cancel
    names = [n for n, _, _, _ in tree]
    assert names[0] == "request" and "decode" not in names
    _finish_tree(tr, t1)                     # survivor unaffected


def test_span_tree_cancel_while_active():
    tr = Tracer()
    eng = _engine(gen_len=32)
    eng.set_tracer(tr, "engine-0")
    tid = tr.new_trace_id()
    uid = eng.submit(PROMPT, max_tokens=32, trace_id=tid)
    eng.step()                               # first block decodes
    assert eng.cancel(uid) is None           # active: finishes next tick
    eng.run_to_completion()
    tree = _finish_tree(tr, tid)
    names = [n for n, _, _, _ in tree]
    assert "decode" in names                 # opened AND closed


def test_span_tree_preempt_resume():
    tr = Tracer()
    eng = _engine(gen_len=32)
    eng.set_tracer(tr, "engine-0")
    tid = tr.new_trace_id()
    uid = eng.submit(PROMPT, max_tokens=32, trace_id=tid)
    eng.step()
    eng.preempt(uid)                         # park at block boundary
    eng.run_to_completion()                  # resumes and finishes
    tree = _finish_tree(tr, tid)
    decodes = [n for n, _, _, _ in tree if n == "decode"]
    assert len(decodes) == 2                 # one per residency
    evs = tr.request_events(tid)
    assert evs[0]["name"] == "request"
    assert evs[-1]["name"] == "request"      # outermost closes last


def test_span_tree_deadline_via_engine_loop():
    from repro.server import EngineLoop, ServerRequest
    tr = Tracer()
    eng = _engine(gen_len=32)
    loop = EngineLoop(eng, idle_poll_s=0.005, tracer=tr, index=0)
    loop.start()
    done = threading.Event()
    out = {}

    def deliver(event):
        kind, payload = event
        if kind == "done":
            out["comp"] = payload
            done.set()

    ticket = loop.submit(ServerRequest(prompt=PROMPT, max_tokens=32,
                                       timeout_s=0.05), deliver)
    assert ticket.trace_id
    assert done.wait(TEST_TIMEOUT_S)
    loop.close(drain=True)
    assert out["comp"].cancelled
    assert ticket.cancel_reason == "deadline"
    _finish_tree(tr, ticket.trace_id)        # tree balanced after expiry


# ------------------------------------------------- per-block telemetry


@pytest.mark.parametrize("method", METHODS)
def test_block_stats_consistency(method):
    """sum(committed_per_step) + straggler_fill == live_rows * K for
    every decoded block, and the confidence histogram counts exactly
    the step-committed tokens."""
    eng = _engine(method)
    eng.submit(PROMPT, max_tokens=16)
    eng.run_to_completion()
    summ = eng.telemetry.summary()
    assert summ, "telemetry must populate"
    K = eng.dcfg.block_size
    total_tokens = 0
    for key, row in summ.items():
        assert key.startswith(f"{method}/")
        assert row["blocks"] == 1
        committed = sum(row["committed_per_step"]) + row["straggler_fill"]
        assert committed == 1 * K            # one live row per block
        assert sum(row["conf_hist"]) == sum(row["committed_per_step"])
        assert len(row["conf_hist"]) == CONF_BUCKETS
        assert 0 < row["steps_mean"] <= row["steps_cap_mean"]
        total_tokens += committed
    assert total_tokens == 16
    tot = eng.telemetry.totals()
    assert tot["blocks"] == 2
    assert 0.0 <= tot["steps_saved_frac"] < 1.0


def test_telemetry_zero_extra_host_syncs():
    """Acceptance: telemetry rides the fused loop's single per-block
    sync — host_syncs_per_block stays exactly 1."""
    eng = _engine()
    eng.submit(PROMPT, max_tokens=16)
    eng.run_to_completion()
    snap = eng.metrics.snapshot()
    assert snap["host_syncs_per_block"] == 1.0
    assert eng.telemetry.blocks == 2         # and telemetry still filled


def test_fused_host_telemetry_parity():
    """The fused loop's in-carry tallies agree with the host loop's
    directly-measured ones on identical work."""
    rows = {}
    for fused in (True, False):
        eng = _engine(fused=fused)
        eng.submit(PROMPT, max_tokens=16)
        eng.run_to_completion()
        rows[fused] = eng.telemetry.summary()
    assert rows[True].keys() == rows[False].keys()
    for key in rows[True]:
        f, h = rows[True][key], rows[False][key]
        assert f["committed_per_step"] == h["committed_per_step"], key
        assert f["straggler_fill"] == h["straggler_fill"], key
        l1 = sum(abs(a - b) for a, b in zip(f["conf_hist"],
                                            h["conf_hist"]))
        assert l1 <= 4, (key, f["conf_hist"], h["conf_hist"])


def test_telemetry_aggregator_accumulates():
    agg = TelemetryAggregator()
    bs = BlockStats(method="streaming", block_idx=0, batch=2, live_rows=2,
                    steps=3, steps_cap=8, committed_per_step=[10, 4, 2],
                    straggler_fill=0, conf_hist=[0] * 9 + [16], window=4,
                    early_exits=2, wall_s=0.5)
    agg.add(bs)
    agg.add(bs)
    assert bs.tokens_committed == 16 and bs.nfe == 6
    row = agg.summary()["streaming/0"]
    assert row["blocks"] == 2
    assert row["committed_per_step"] == [20, 8, 4]
    tot = agg.totals()
    assert tot["tokens"] == 32
    assert tot["steps_saved_frac"] == pytest.approx(1 - 6 / 16)


# ------------------------------------------------- ServeMetrics safety


def test_serve_metrics_thread_safety_hammer():
    """Regression: the decode thread mutates while the asyncio thread
    scrapes — snapshots must never crash or tear (requests list length
    vs aggregate counters computed from it)."""
    m = ServeMetrics(max_slots=4)
    N = 3000
    stop = threading.Event()
    errors = []

    def writer():
        for i in range(N):
            m.add_request(RequestMetrics(
                uid=i, queue_s=0.001, ttfb_s=0.01, latency_s=0.1,
                n_tokens=8, nfe=16, n_blocks=1, host_syncs=1))
            m.sample_tick(2, 0.001)
        stop.set()

    def reader():
        while not stop.is_set():
            try:
                snap = m.snapshot()
                # internally consistent: derived values match the copy
                assert snap["requests"] >= 0
                assert snap["tokens"] == snap["requests"] * 8
                _ = m.throughput, m.mean_occupancy, m.total_blocks
            except Exception as e:           # pragma: no cover
                errors.append(e)
                return

    threads = [threading.Thread(target=writer)] + \
        [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors
    snap = m.snapshot()
    assert snap["requests"] == N
    assert m.hist_ttfb.count == N


# ------------------------------------------------- histograms / metrics


def test_histogram_buckets_sum_count():
    h = Histogram("x_seconds", "test", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    counts, s, n = h.snapshot()
    assert counts == [1, 1, 1, 1]            # one per bucket + +Inf
    assert n == 4 and s == pytest.approx(55.55)
    lines = h.prometheus()
    assert 'x_seconds_bucket{le="0.1"} 1' in lines
    assert 'x_seconds_bucket{le="1.0"} 2' in lines      # cumulative
    assert 'x_seconds_bucket{le="+Inf"} 4' in lines
    assert any(line.startswith("x_seconds_count 4") for line in lines)
    labeled = h.prometheus('engine="1"')
    assert 'x_seconds_bucket{engine="1",le="0.1"} 1' in labeled


def test_histogram_merge_requires_same_bounds():
    a = Histogram("x", "t", buckets=(1.0, 2.0))
    b = Histogram("x", "t", buckets=(1.0, 2.0))
    a.observe(0.5)
    b.observe(1.5)
    a.merge(b)
    counts, _, n = a.snapshot()
    assert counts == [1, 1, 0] and n == 2
    with pytest.raises(ValueError):
        a.merge(Histogram("x", "t", buckets=(5.0,)))


def test_device_memory_stats_cpu_safe():
    assert device_memory_stats() == {}       # CPU backend: empty, no raise


# ------------------------------------------------- structured logging


def test_json_logging_fields():
    buf = io.StringIO()
    setup_logging(level="debug", json_mode=True, stream=buf)
    log = get_logger("repro.test.obs")
    log.info("block decoded", extra={"uid": 7, "engine": 0,
                                     "gang": [7, 8], "trace_id": "t-1"})
    line = buf.getvalue().strip()
    doc = json.loads(line)
    assert doc["msg"] == "block decoded"
    assert doc["level"] == "INFO"
    assert doc["logger"] == "repro.test.obs"
    assert doc["uid"] == 7 and doc["engine"] == 0
    assert doc["gang"] == [7, 8] and doc["trace_id"] == "t-1"
    # reconfigure to text: handler replaced, not stacked
    buf2 = io.StringIO()
    setup_logging(level="info", json_mode=False, stream=buf2)
    assert len(logging.getLogger("repro").handlers) == 1
    log.info("plain", extra={"uid": 9})
    assert "plain" in buf2.getvalue() and "uid=9" in buf2.getvalue()
    setup_logging(level="warning", stream=io.StringIO())  # quiet again


def test_library_loggers_under_repro_namespace():
    from repro.server import http, loop, router
    for mod in (http, loop, router):
        assert mod.log.name.startswith("repro.")


# ------------------------------------------------- HTTP integration


@contextlib.asynccontextmanager
async def _traced_server(**kw):
    from repro.server import EngineLoop
    from repro.server.http import HttpFrontend
    tr = Tracer()
    eng = _engine(tracer=None, **kw)
    loop = EngineLoop(eng, max_pending=16, idle_poll_s=0.005,
                      tracer=tr, index=0)
    frontend = await HttpFrontend(loop, port=0, tracer=tr).start()
    try:
        yield frontend, eng, tr
    finally:
        await frontend.shutdown(drain=True, timeout_s=30)


def test_http_trace_header_and_block():
    from repro.server import client as C

    async def main():
        async with _traced_server() as (fe, eng, tr):
            status, headers, doc = await C.complete(
                fe.host, fe.port,
                {"prompt": PROMPT, "max_tokens": 13, "trace": True})
            assert status == 200
            tid = headers["x-repro-trace-id"]
            assert tid and doc["trace_id"] == tid
            evs = doc["trace"]["events"]
            assert evs and all(e["id"] == tid for e in evs)
            names = {e["name"] for e in evs}
            assert {"http", "request", "queue", "decode"} <= names
            # opt-out: no trace block, header still present
            status, headers2, doc2 = await C.complete(
                fe.host, fe.port, {"prompt": PROMPT, "max_tokens": 13})
            assert "trace" not in doc2
            assert headers2["x-repro-trace-id"] == doc2["trace_id"]
        # after drain: full tree incl. the http span is well-formed
        tree = request_tree(tr.request_events(tid))
        names = [n for n, _, _, _ in tree]
        assert names[0] == "http"
        assert names[1] == "request"
    _run(main())


def test_http_untraced_server_has_no_trace_fields():
    from repro.server import EngineLoop
    from repro.server import client as C
    from repro.server.http import HttpFrontend

    async def main():
        eng = _engine()
        loop = EngineLoop(eng, max_pending=16, idle_poll_s=0.005)
        fe = await HttpFrontend(loop, port=0).start()
        try:
            status, headers, doc = await C.complete(
                fe.host, fe.port,
                {"prompt": PROMPT, "max_tokens": 13, "trace": True})
            assert status == 200
            assert "x-repro-trace-id" not in headers
            assert "trace_id" not in doc and "trace" not in doc
        finally:
            await fe.shutdown(drain=True, timeout_s=30)
    _run(main())


def test_server_request_validates_trace_flag():
    from repro.server.types import BadRequest, ServerRequest
    assert ServerRequest.from_json(
        {"prompt": "x", "trace": True}).trace is True
    assert ServerRequest.from_json({"prompt": "x"}).trace is False
    with pytest.raises(BadRequest):
        ServerRequest.from_json({"prompt": "x", "trace": 1})


def test_metrics_exposition_histograms_and_telemetry():
    from repro.server import EngineLoop
    from repro.server.http import HttpFrontend
    eng = _engine()
    eng.submit(PROMPT, max_tokens=16)
    eng.run_to_completion()
    text = HttpFrontend(EngineLoop(eng))._metrics_text()
    for family in ("repro_ttfb_seconds", "repro_queue_wait_seconds",
                   "repro_block_wall_seconds", "repro_nfe_per_token"):
        assert f"{family}_bucket" in text
        assert f"{family}_count" in text
    assert "repro_decode_blocks_total 2" in text
    assert "repro_decode_steps_total" in text
    assert "repro_decode_confidence_total" in text
    assert 'bucket="0.9-1.0"' in text
    # exposition parses: every non-comment line is "name{labels} value"
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name_part, _, value = line.rpartition(" ")
        float(value)
        assert name_part
