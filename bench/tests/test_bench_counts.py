"""FLOP and byte counts against hand sums at the cells' real shapes."""
import pytest

from bench import counts
from bench.model import load_config

V = 152064  # 151936 padded to a multiple of 256


def test_qwen3_8b_dense_counts_and_tp4_share():
    c = load_config("qwen3-8b")
    d, ff, L = 4096, 12288, 36
    layer = d * 6144 + 4096 * d + 3 * d * ff
    p = counts.weight_params(c)
    assert p["matmul"] == L * layer + d * V
    assert p["embed"] == V * d
    assert p["norms"] == L * (2 * d + 2 * 128) + d
    total = sum(p.values())
    assert total == pytest.approx(8.19e9, rel=0.01)  # published size
    assert counts.weight_bytes(c) == 2 * total
    assert counts.kv_bytes_per_token(c) == 2 * 2 * 36 * 8 * 128
    per_chip = counts.decode_weight_bytes_per_chip(c, 4)
    assert per_chip == 2 * (p["matmul"] / 4 + p["norms"])
    assert counts.decode_min_bytes_per_chip(c, 4, 1000) == pytest.approx(
        per_chip + 1000 * 147456 / 4)


def test_qwen3_4b_fact_counts():
    c = load_config("qwen3-4b-fact")
    b, L, d = 128, 36, 2560
    # butterfly qkv: 2560 -> 6144 padded to 8192, 64 blocks, 6 factors of
    # 2 * 8192 * 128; out: 4096 -> 2560 padded to 4096, 5 factors
    qkv = 6 * 2 * 8192 * b
    out = 5 * 2 * 4096 * b
    # pixelfly over 16384 (128 blocks, 8 per block-row) plus rank 16
    pix = 128 * 8 * b * b
    mlp = 3 * pix + 16 * (d + 9728) * 3
    p = counts.weight_params(c)
    assert p["matmul"] == L * (qkv + out + mlp) + d * V
    assert counts.weight_bytes(c) == 6507330560
    assert counts.decode_weight_bytes_per_chip(c, 1) == 2 * (
        p["matmul"] + p["norms"])


def test_token_and_prefill_flops():
    c = load_config("qwen3-8b")
    m = counts.weight_params(c)["matmul"]
    per_pos = 4 * 36 * 32 * 128
    assert counts.decode_token_flops(c, 100) == 2 * m + per_pos * 100
    # a causal prefill of n tokens attends to 1 + 2 + ... + n positions
    assert counts.prefill_flops(c, 3) == 3 * 2 * m + per_pos * 6


def test_pool_leaves_room_for_prefill_insert_and_decode():
    from bench.harness import MEMORY_MARGIN, pool_bytes
    # the compiled sizes of qwen3-4b-fact's 8 x 1024 prefill and 32-slot
    # decode step for a v5e, and that chip's allocator limit
    limit, weights = 16_909_999_800, 6_507_330_560
    prog = {"prefill_temp": 4_490_840_576, "prefill_out": 1_811_940_352,
            "decode_temp": 822_784}
    pool = pool_bytes(limit, weights, prog)
    room = limit * (1 - MEMORY_MARGIN)
    assert weights + pool + prog["prefill_temp"] + prog["prefill_out"] <= room
    # the insert: two pools, the prefill's caches, a copy of one of their
    # leaves, the scatter's temporary of that leaf, and a further copy of
    # the caches for what a v5e showed beyond those
    insert = weights + 2 * pool + 3 * prog["prefill_out"]
    assert insert <= room
    assert weights + 2 * pool + prog["decode_temp"] <= room
    # the insert binds here, to within a few bytes
    assert insert >= room - 4
