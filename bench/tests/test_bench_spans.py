"""The program's spans on synthetic traces: device idle split by the
span the host was in, and the block program's phases found from its
outermost ``%while``, as the three span readers report them."""
import types

import pytest

from bench import cells, spans

W = ("bench.window", 0, 1000, {})


def _run(device, host):
    return types.SimpleNamespace(trace={"device": device, "host": host})


def _read(metric, device, host):
    return cells.load_reader(metric)(_run(device, host))


def _busy(*iv):
    return [(f"%fusion.{i} = f32[2] fusion(x)", s, e - s)
            for i, (s, e) in enumerate(iv)]


def test_idle_gap_split_across_two_spans():
    """One gap of 200 ns: 100 under harvest, 50 under publish, 50
    under no span. Only the first two are the block boundary's."""
    device = {"/device:TPU:0": _busy((0, 100), (300, 1000))}
    host = [W, ("scheduler.harvest", 50, 150, {}),
            ("engine.publish", 200, 50, {})]
    idle = spans.idle_by_span({"device": device, "host": host})
    assert idle == pytest.approx({"scheduler.harvest": 100e-9,
                                  "engine.publish": 50e-9,
                                  "unattributed": 50e-9})
    assert _read("device.idle_pct.boundary", device, host) == \
        pytest.approx(15.0)


def test_idle_under_loop_wait_and_bare_block_does_not_count():
    """Waiting for work and the block span itself are not host work at
    the boundary; a sub-span nested in the block is, and names it."""
    device = {"/device:TPU:0": _busy((0, 100), (200, 300), (600, 1000))}
    host = [W, ("loop.wait", 100, 100, {}),
            ("decoder.block", 250, 400, {"batch": 8}),
            ("decoder.sync", 500, 150, {}),
            ("bench.block", 250, 400, {})]
    idle = spans.idle_by_span({"device": device, "host": host})
    # 300..500 under the block alone, 500..600 under its sync
    assert idle == pytest.approx({"loop.wait": 100e-9,
                                  "decoder.block": 200e-9,
                                  "decoder.sync": 100e-9})
    assert _read("device.idle_pct.boundary", device, host) == \
        pytest.approx(10.0)


def test_idle_averaged_over_planes():
    host = [W, ("scheduler.admit", 0, 1000, {})]
    device = {"/device:TPU:0": _busy((0, 800)),
              "/device:TPU:1": _busy((0, 600))}
    assert _read("device.idle_pct.boundary", device, host) == \
        pytest.approx(30.0)


def test_a_program_without_spans_reports_nothing():
    device = {"/device:TPU:0": _busy((0, 100), (300, 1000))}
    host = [W, ("bench.block", 0, 500, {"batch": 8, "block": 0}),
            ("PjitFunction(f)", 100, 200, {})]
    for m in ("device.idle_pct.boundary", "decoder.refresh_ms",
              "decoder.step_ms"):
        assert _read(m, device, host) is None
        assert cells.load_reader(m)(types.SimpleNamespace(trace=None)) \
            is None


def _block_ops(t0, loop_len, refresh=(20, 30), nested=True):
    """One block program starting at ``t0``: two refresh ops (one
    inside a head loop), the denoise loop with ops and a nested loop
    inside it, then a finalize op."""
    a, b = refresh
    ops = [("%fusion.1 = f32[2] fusion(x)", t0, a),
           ("%while.3 = (s32[]) while(...)", t0 + a, b),
           ("%fusion.4 = f32[2] fusion(y)", t0 + a, b),
           ("%while.9 = (s32[8]) while(...)", t0 + a + b, loop_len),
           ("%block_attention.2 = f32[1] custom-call(q)", t0 + a + b,
            loop_len // 2),
           ("%fusion.7 = f32[2] fusion(z)", t0 + a + b + loop_len, 5)]
    if nested:
        ops.append(("%while.11 = (s32[]) while(...)",
                    t0 + a + b + loop_len // 2, loop_len // 4))
    return ops


def test_phases_from_the_outermost_while():
    """The refresh is what starts before the block's outermost loop
    (the head loop inside the refresh is not it, nor the loop nested
    in the steps); the steps are the loop's own duration; the
    finalize after it counts in neither."""
    device = {"/device:TPU:0": _block_ops(10, 400)}
    host = [W, ("decoder.block", 5, 500,
                {"batch": 8, "block": 0, "steps": 5, "committed": 256})]
    assert _read("decoder.refresh_ms", device, host) == \
        pytest.approx(50e-6)
    assert _read("decoder.step_ms", device, host) == \
        pytest.approx(400 / 4 * 1e-6)


def test_two_gangs_in_one_tick():
    """Two block programs back to back, each a decoder.block span with
    its own steps; a block outside the window is left out, and so is
    an op that starts outside every block."""
    device = {"/device:TPU:0": _block_ops(10, 200, refresh=(30, 30))
              + _block_ops(400, 90, refresh=(10, 10))
              + [("%fusion.99 = f32[2] fusion(w)", 700, 50)]
              + _block_ops(1100, 999)}
    host = [W,
            ("decoder.block", 0, 350, {"batch": 8, "steps": 5}),
            ("decoder.block", 390, 250, {"batch": 4, "steps": 4}),
            ("decoder.block", 1050, 1200, {"batch": 8, "steps": 9})]
    assert _read("decoder.refresh_ms", device, host) == \
        pytest.approx((60 + 20) / 2 * 1e-6)
    assert _read("decoder.step_ms", device, host) == \
        pytest.approx((200 + 90) / ((5 - 1) + (4 - 1)) * 1e-6)


def test_step_time_needs_a_step_after_the_refresh():
    """A block whose refresh committed everything ran no denoise step:
    no step time to report, while its refresh still counts."""
    device = {"/device:TPU:0": _block_ops(10, 4, nested=False)}
    host = [W, ("decoder.block", 5, 100, {"batch": 8, "steps": 1})]
    assert _read("decoder.step_ms", device, host) is None
    assert _read("decoder.refresh_ms", device, host) == \
        pytest.approx(50e-6)
