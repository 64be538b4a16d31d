"""The work counts against numbers worked by hand at both widths."""

import json

import pytest

from _paths import ROOT
from bench import work

QWEN2_1_5B = json.loads((ROOT / "bench/configs/qwen2-1.5b.json").read_text())
# qwen2-7B's published widths at 14 of its 28 layers
QWEN2_7B_L14 = {"reference": "qwen2", "num_hidden_layers": 14,
                "hidden_size": 3584,
                "num_attention_heads": 28, "num_key_value_heads": 4,
                "head_dim": 128, "intermediate_size": 18944,
                "vocab_size": 152064, "bayesian_head": {"mc_samples": 10}}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("config, rows, flops, byts", [
    # 4 * 24 * 1536 * 151936 + 2 * 10 * 24 * 151936
    # 2 * 1536 * 151936 * 4 + 24 * 1536 * 2 + 24 * 5 * 4
    (QWEN2_1_5B, 24, 22_476_804_096, 1_867_063_776),
    # 4 * 8 * 3584 * 152064 + 2 * 10 * 8 * 152064
    # 2 * 3584 * 152064 * 4 + 8 * 3584 * 2 + 8 * 5 * 4
    (QWEN2_7B_L14, 8, 17_464_246_272, 4_360_036_512),
])
def test_head(config, rows, flops, byts):
    assert work.head(config, rows) == (flops, byts)


@pytest.mark.parametrize("config, flops, byts", [
    # 4 * H 12 * hd 128 * 1000 * L 28;  2 (K, V) * Hkv 2 * 128 * 2 B * 1000 * 28
    (QWEN2_1_5B, 172_032_000, 28_672_000),
    # 4 * 28 * 128 * 1000 * 14;  2 * 4 * 128 * 2 * 1000 * 14
    (QWEN2_7B_L14, 200_704_000, 28_672_000),
])
def test_decode_attention_at_depth_1000(config, flops, byts):
    assert work.decode_attention(config, 1000) == (flops, byts)


@pytest.mark.parametrize("config, per_layer, flops", [
    # 1536^2 (q) + 2 * 1536 * 256 (k, v) + 1536^2 (o) + 3 * 1536 * 8960
    (QWEN2_1_5B, 46_792_704, 2_620_391_424),
    # 3584^2 + 2 * 3584 * 512 + 3584^2 + 3 * 3584 * 18944
    (QWEN2_7B_L14, 233_046_016, 6_525_288_448),
])
def test_body_flops(config, per_layer, flops):
    assert flops == 2 * per_layer * config["num_hidden_layers"]
    assert work.body_flops_per_token(config) == flops


def test_decode_token_flops_adds_body_attention_and_head_row():
    want = 2_620_391_424 + 172_032_000 + (4 * 1536 * 151936 + 2 * 10 * 151936)
    assert work.decode_token_flops(QWEN2_1_5B, 1000) == want


def test_seconds_at_peak_takes_the_larger_bound():
    # 1.867 GB at 819 GB/s beats 22.5 GFLOP at 197 TFLOP/s
    assert work.seconds_at_peak(22_476_804_096, 1_867_063_776, PEAK) == \
        pytest.approx(1_867_063_776 / 819e9)
    assert work.seconds_at_peak(197e12, 1.0, PEAK) == pytest.approx(1.0)
