"""Byte and FLOP arithmetic against hand counts and the stored model."""
import jax
import pytest

import shapes
import traffic
from conftest import BENCH

TINY = traffic.load(BENCH / "tests" / "data" / "tiny.json")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_stream_bytes_by_hand():
    # 4-bit base of a (1536, 256) weight: block 512, 512/8 = 64 words
    # per block and column, 3 blocks, 256 columns, 4 bytes a word
    assert shapes.stream_bytes(1536, 256, 4) == 3 * 64 * 256 * 4
    # a 3-bit delta is a 2-bit plane (512/16 = 32 words) and a 1-bit
    # plane (512/32 = 16 words)
    assert shapes.stream_bytes(1536, 256, 3) == 3 * (32 + 16) * 256 * 4
    assert shapes.stream_widths((8, 6, 4)) == (4, 3, 3)
    assert shapes.weight_bytes(1536, 256, (8, 6, 4), 2) == (
        1536 * 256 * 10 // 8)


def test_delta_bytes_match_the_stored_ladder():
    from repro.api import NestQuantStore, QuantRecipe, quantize
    from weights import program_params
    s = shapes.Sizes.from_config(TINY)
    params = jax.jit(lambda: program_params(0, s, TINY["weights"]))()
    store = NestQuantStore(quantize(params, QuantRecipe(bits=(8, 6, 4))))
    for k in range(2):
        assert shapes.delta_bytes(s.quantized_leaves(), (8, 6, 4),
                                  k) == store.delta_bytes(k)


def test_token_and_decode_flops_by_hand():
    s = shapes.Sizes.from_config(TINY)     # d 64, ff 128, 8/4 heads x 16
    per_layer = 64 * 128 + 2 * 64 * 64 + 128 * 64 + 3 * 64 * 128
    matmul = 2 * (2 * per_layer + 64 * 512)
    assert shapes.token_flops(s, 10) == matmul + 2 * 4 * 8 * 16 * 10
    # rows of (prompt, answer): answer 3 uses steps 0 and 1, answer 1 none
    want = shapes.token_flops(s, 6) + shapes.token_flops(s, 7)
    assert shapes.decode_flops(s, [(5, 3), (9, 1)], 4) == want


def test_matmul_least_time_takes_the_larger_bound():
    t = shapes.matmul_least_s(32, 1536, 8960, (8, 6, 4), 2, PEAKS)
    nbytes = 1536 * 8960 * 10 // 8 + 4 * 8960 + 2 * 32 * 1536 + 2 * 32 * 8960
    assert t == pytest.approx(nbytes / 819e9)
    big = shapes.matmul_least_s(16384, 1536, 8960, (8, 6, 4), 2, PEAKS)
    assert big == pytest.approx(2 * 16384 * 1536 * 8960 / 197e12)
