"""The flop and byte counts against shapes worked by hand."""
import _paths  # noqa: F401

import flops

CFG = {"hidden_size": 4096, "num_attention_heads": 32,
       "num_key_value_heads": 4, "intermediate_size": 11008,
       "vocab_size": 64000, "num_hidden_layers": 24}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_decode_attention_counts_live_tokens():
    # 32 heads x 128 x 1000 tokens x 4 (QK and PV, 2 flops each)
    f, b = flops.decode_attention(CFG, 1000)
    assert f == 4 * 32 * 128 * 1000 == 16_384_000
    # K and V: 2 x 4 kv heads x 128 x 1000 x 2 bytes; q bf16 + acc fp32
    assert b == 2 * 4 * 128 * 1000 * 2 + 32 * 128 * 6 == 2_072_576


def test_prefill_attention_is_causal():
    # chunk of 3 queries at positions 10, 11, 12 sees 11 + 12 + 13 keys
    f, b = flops.prefill_attention(CFG, 10, 3)
    assert f == 4 * 32 * 128 * 36
    assert b == 2 * 4 * 128 * 13 * 2 + 2 * 32 * 128 * 3 * 2
    # a chunk from 0 sees the triangle
    assert flops.prefill_attention(CFG, 0, 4)[0] == 4 * 32 * 128 * 10


def test_token_flops_yi_stage():
    per_layer = (4096 * 4096 * 2 + 4096 * 512 * 2) + 3 * 4096 * 11008
    assert flops.dense_params_per_layer(CFG) == per_layer == 173_015_040
    weights = 24 * per_layer + 4096 * 64000
    assert flops.token_flops(CFG, 1) == 2 * weights + 4 * 24 * 32 * 128
    assert flops.token_flops(CFG, 2) - flops.token_flops(CFG, 1) == 393_216


def test_least_time_names_its_bound():
    t, bound = flops.least_time(197e12, 1.0, PEAKS)
    assert (round(t, 9), bound) == (1.0, "compute")
    t, bound = flops.least_time(1.0, 819e9, PEAKS)
    assert (round(t, 9), bound) == (1.0, "memory")
