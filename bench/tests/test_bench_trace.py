"""The trace reduction on synthetic events: idle share, kernel time by
name, the breakdown's gaps and their host labels, and the per-block
kernel time the roofline reader uses."""
import pytest

from bench import devtrace


def _events():
    # window 0..100 ns; two device planes (averaged); host spans
    ops = [("%while.2 = (f32[2]) while(...)", 0, 90),
           ("%fusion.1 = f32[2] fusion(x)", 0, 20),
           ("%block_attention.14 = f32[1,8] custom-call(q, k, v)", 20, 10),
           ("%convolution.3 = f32[2] convolution(a, b)", 50, 30),
           ("%block_attention = f32[1,8] custom-call(q, k, v)", 85, 5)]
    return {"device": {"/device:TPU:0": ops,
                       "/device:TPU:1": [("%fusion.1 = f32[2] fusion(x)",
                                          0, 100)]},
            "host": [("bench.window", 0, 100, {}),
                     ("bench.block", 0, 45, {"batch": 2, "block": 0}),
                     ("harvest", 30, 20, {}),
                     ("PjitFunction(f)", 80, 20, {})]}


def test_union_and_gaps():
    assert devtrace.union_ns([(0, 5), (3, 8), (10, 12)]) == 10
    assert devtrace.gaps([(0, 5), (3, 8), (10, 12)], 0, 15) == \
        [(8, 10), (12, 15)]


def test_reduce_busy_idle_and_kernels():
    red = devtrace.reduce(_events())
    assert red["window_s"] == pytest.approx(100e-9)
    # plane 0 busy 0..90 under the loop; plane 1 busy 100 ns
    assert red["busy_s"] == pytest.approx((90 + 100) / 2 * 1e-9)
    # kernel matched by its op name, averaged over planes
    assert red["kernel_s"]["block_attention"] == pytest.approx(15 / 2 * 1e-9)
    names = [n for n, _ in red["device_ops"]]
    assert names[0] == "%fusion.1" and "%while.2" not in names
    gaps = dict((round(s * 1e9), n) for n, s in red["idle_gaps"])
    assert gaps == {10: "PjitFunction(f)"}


def test_gap_label_prefers_the_shortest_covering_host_event():
    host = [("bench.window", 0, 100, {}), ("bench.block", 0, 45, {}),
            ("harvest", 30, 20, {})]
    # 30..50 lies under harvest (20 ns) and bench.block: shortest wins
    assert devtrace.label_gap(host, 30, 50) == "harvest"
    assert devtrace.label_gap(host, 60, 70) == "no host event"


def test_short_op_names():
    assert devtrace.op_name("%fusion.3 = f32[2] fusion(x)") == "%fusion.3"
    assert devtrace.is_container("%while.63 = (s32[4]) while(...)")
    assert not devtrace.is_container("%fusion.3 = f32[2] fusion(x)")


def test_blocks_and_kernel_time_in_a_block():
    ev = _events()
    (blk,) = devtrace.blocks(ev["host"])
    assert blk["batch"] == 2 and (blk["start_ns"], blk["end_ns"]) == (0, 45)
    calls = devtrace.kernel_calls(ev, "block_attention")
    # block_attention at 20 ns on plane 0 starts inside; 85 does not
    assert devtrace.time_in(calls, 0, 45, planes=2) == \
        pytest.approx(10 / 2 * 1e-9)
    assert not devtrace.matches("%block_attention_grad.1 = x", "block_attention")


def test_reduce_needs_the_window():
    with pytest.raises(ValueError):
        devtrace.reduce({"device": {}, "host": []})


def test_roofline_reader_by_hand():
    """One block of 2 lanes in the window: the kernel's 1 ms against the
    least time of its calls, FLOPs over peak or bytes over bandwidth."""
    import types

    from bench import cells, flops
    from bench.families import dense_gqa
    m = {"family": "dense_gqa", "d": 8, "heads": 4, "kv_heads": 2,
         "head_dim": 4, "d_ff": 16, "vocab": 10, "layers": 2, "block": 4,
         "window": 4, "dtype_bytes": 4}
    peaks = {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e8}
    ev = {"device": {"/device:TPU:0": [
              ("%block_attention.1 = f32[2] custom-call(q)", 10, 600_000),
              ("%block_attention.1 = f32[2] custom-call(q)", 700_000,
               400_000)]},
          "host": [("bench.window", 0, 2_000_000, {}),
                   ("bench.block", 5, 1_500_000,
                    {"batch": 2, "block": 1, "live": 2})]}
    reqs = [types.SimpleNamespace(n_blocks=2, nfe=8)]
    run = types.SimpleNamespace(dims=m, peaks=peaks, trace=ev,
                                window_requests=reqs, prompt_len=6,
                                gen_len=12)
    want = 0.0
    for count, sq, skv in flops.block_passes(m, 6, 12, 1, 4.0):
        want += count * 2 * 2 * max(
            dense_gqa.attention_flops(m, sq, skv) / 1e9,
            dense_gqa.attention_bytes(m, sq, skv) / 1e8)
    got = cells.load_reader("block_attention_roofline")(run)
    assert got == pytest.approx(100.0 * want / 1e-3)
