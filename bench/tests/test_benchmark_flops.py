"""Operations and bytes from the configurations' shapes, against hand
counts."""
import json
import os

import tinyroot
import flops


def _cfg(name):
    with open(os.path.join(tinyroot.BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_internlm2_projections():
    c = _cfg("internlm2-1.8b")
    assert flops.projections(c) == [
        ("wqkv", 2048, 4096), ("wo", 2048, 2048), ("gate", 2048, 8192),
        ("up", 2048, 8192), ("down", 8192, 2048)]
    assert flops.layer_matmul_params(c) == 62_914_560
    assert flops.matmul_params(c) == 1_509_949_440
    # the qkv GEMM of a 32-lane decode step in bf16
    assert flops.gemm_cost(32, 2048, 4096, 2) == (
        2 * 32 * 2048 * 4096, 2 * (32 * 2048 + 2048 * 4096 + 32 * 4096))


def test_granite_projections():
    # Granite-3.0-8B's published widths, ten of its forty layers
    c = {"hidden_size": 4096, "intermediate_size": 12800,
         "num_attention_heads": 32, "num_key_value_heads": 8,
         "head_dim": 128, "num_hidden_layers": 10, "vocab_size": 49155}
    k, n = 4096, (32 + 2 * 8) * 128
    assert flops.projections(c)[0] == ("wqkv", k, n)
    assert flops.layer_matmul_params(c) == (
        4096 * 6144 + 4096 * 4096 + 3 * 4096 * 12800) == 199_229_440
    fl, by = flops.decode_gemm_cost(c, 64, 2)
    assert fl == 10 * 2 * 64 * 199_229_440
    assert by == 10 * 2 * (199_229_440 + 64 * (4096 + 4096 + 4096 + 4096
                                               + 12800) + 64 * (6144 + 4096
                                               + 12800 + 12800 + 4096))
    assert flops.head_flops(c) == 2 * 4096 * 49155


def test_chunk_is_the_sum_of_its_tokens():
    c = _cfg("internlm2-1.8b")
    whole = flops.chunk_flops(c, 64, 32, last=True)
    parts = sum(flops.token_flops(c, p, logits=(p == 95))
                for p in range(64, 96))
    assert whole == parts


def test_paged_attention_cost_by_hand():
    c = _cfg("internlm2-1.8b")
    # one lane: 32 queries at positions 0..31, page 16 -> 2 pages
    fl, by = flops.paged_attention_cost(c, [(0, 32)], 16, 2)
    assert fl == 24 * 4 * 16 * 128 * (32 * 33 // 2)
    assert by == 24 * 2 * (2 * 2 * 16 * 8 * 128 + 2 * 32 * 16 * 128)
    assert flops.least_seconds(2e12, 1e9, 1e12, 1e12) == 2.0
    assert flops.least_seconds(1e12, 4e12, 1e12, 1e12) == 4.0
