"""FLOP and byte functions against hand counts at tiny shapes."""
import pytest

from bench import flops
from bench.families import dense_gqa

# d 8, 4 query heads over 2 KV heads of 4, d_ff 16, vocab 10, 2 layers
M = {"family": "dense_gqa", "d": 8, "heads": 4, "kv_heads": 2,
     "head_dim": 4, "d_ff": 16, "vocab": 10, "layers": 2, "block": 4,
     "window": 4, "dtype_bytes": 4}


def test_dense_layer_flops_by_hand():
    # q 8x16, k and v 8x8 each, o 16x8, mlp 3 x 8x16: 2 FLOPs a MAC
    macs = 8 * 16 + 2 * 8 * 8 + 16 * 8 + 3 * 8 * 16
    assert dense_gqa.dense_layer_flops(M, sq=3) == 2 * 3 * macs


def test_attention_counts_valid_keys_and_every_query_head():
    # QK^T and PV: 2 x (sq * skv * heads * head_dim) MACs
    assert dense_gqa.attention_flops(M, sq=3, skv=5) == 4 * 3 * 5 * 4 * 4


def test_attention_bytes_read_kv_once_per_kv_head():
    # q in and out per query head; K and V per KV head, not per query head
    q_out = 2 * 3 * 4 * 4
    kv = 2 * 5 * 2 * 4
    assert dense_gqa.attention_bytes(M, sq=3, skv=5) == 4 * (q_out + kv)
    mha = dict(M, kv_heads=4)
    assert dense_gqa.attention_bytes(mha, 3, 5) > \
        dense_gqa.attention_bytes(M, 3, 5)


@pytest.mark.parametrize("b, expect", [
    (0, 4 + 4 + 1),    # block, window of 4, trailing position
    (1, 4 + 4),        # the window reaches the end: no trailing slot
    (2, 4),            # last block: nothing after it
])
def test_query_len_prunes_the_suffix(b, expect):
    assert flops.query_len(M, prompt_len=6, gen_len=12, block_idx=b) == expect


def test_block_passes_and_flops():
    passes = flops.block_passes(M, prompt_len=6, gen_len=12, block_idx=1,
                                steps=4)
    # refresh over prefix 10 + region 8; three steps of the region
    assert passes == [(1.0, 18, 18), (3.0, 8, 18)]
    per = lambda sq, skv: (dense_gqa.dense_layer_flops(M, sq)  # noqa: E731
                           + dense_gqa.attention_flops(M, sq, skv))
    want = 2 * (per(18, 18) + 3 * per(8, 18)) + 4 * 2 * 4 * 8 * 10
    assert flops.block_flops(M, 6, 12, 1, 4) == pytest.approx(want)
