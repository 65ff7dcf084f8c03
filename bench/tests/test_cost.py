"""Operation and byte counts against hand counts for one small shape,
and the peak table's refusal of an unknown chip."""
from __future__ import annotations

import pytest

from bench import cost

CFG = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
       "vocab_size": 128}


def test_flops_by_hand():
    # per layer: q 8x8, k and v 8x4 each, o 8x8, MLP 3 x 8x16 -> 576 MACs
    assert cost.layer_matmul_flops(CFG) == 2 * 2 * 576
    assert cost.logits_flops(CFG) == 2 * 8 * 128
    # QK^T and PV: 2 heads x 4 dims x 10 keys x 2 layers, 2 FLOPs each
    assert cost.attention_flops(CFG, 10) == 4 * 2 * 2 * 4 * 10
    assert cost.decode_token_flops(CFG, 10) == 2304 + 640 + 2048
    # 3 tokens behind 5 cached ones attend to 6, 7 and 8 keys
    assert cost.prefill_flops(CFG, 3, 5) == 3 * 2304 + 16 * 21 * 4 + 2048


def test_bytes_by_hand():
    per_layer = 2 * 8 + 8 * (2 * 8 + 2 * 4) + 3 * 8 * 16
    assert cost.param_bytes(CFG) == 4 * (2 * per_layer + 128 * 8 + 8)
    assert cost.kv_row_bytes(CFG) == 4 * 2 * 2 * 1 * 4
    # weights once, 10 + 20 rows read, one row written per lane
    assert cost.decode_step_bytes(CFG, [10, 20]) == 8864 + 64 * 32


def test_peaks_known_and_unknown():
    pk = cost.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cost.peaks("TPU v9 imaginary")


def test_qkv_bias_counts_by_hand():
    cfg = {**CFG, "qkv_bias": True}
    # per layer: q 8, k and v 4 each -> 16 bias elements, one add each
    assert cost.layer_matmul_flops(cfg) == 2 * 2 * 576 + 2 * 16
    assert cost.decode_token_flops(cfg, 10) == 2336 + 640 + 2048
    per_layer = 2 * 8 + 8 * (2 * 8 + 2 * 4) + 3 * 8 * 16 + 16
    assert cost.param_bytes(cfg) == 4 * (2 * per_layer + 128 * 8 + 8)
    assert cost.kv_row_bytes(cfg) == cost.kv_row_bytes(CFG)
