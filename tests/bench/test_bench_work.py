"""The yardstick's work functions against counts made by hand at a small
shape, and the table of peaks."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.yardstick import peaks, work  # noqa: E402

# 2 layers, d 64, 4 query heads on 2 KV heads of width 16, d_ff 96,
# vocab 100; keys coded 4+4 bits in groups of 8, float32 stats, 2-byte values
MODEL = {"num_hidden_layers": 2, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "intermediate_size": 96, "vocab_size": 100}
KV = {"method": "polar", "rho_bits": 4, "theta_bits": 4, "group_size": 8,
      "stat_bytes": 4, "value_bytes": 2}
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def test_page_bytes():
    # codes 8 tokens x 8 pairs x 1 byte + 4 stats x 8 pairs x 4 bytes
    # + values 8 tokens x 16 x 2 bytes
    assert work.page_bytes(MODEL, KV) == 64 + 128 + 256


def test_decode_attention_counts_flushed_pages_only():
    # contexts 7, 8, 20: 0, 1 and 2 whole pages of 8 tokens
    f, b = work.decode_attention(MODEL, KV, [7, 8, 20])
    pages = 0 + 1 + 2
    per_layer_flops = 4 * 4 * 16 * 8 * pages        # 4 heads x 16 x 8 tokens
    per_layer_bytes = 2 * 448 * pages + 3 * 4 * 16 * 4 * 2
    assert f == 2 * per_layer_flops
    assert b == 2 * per_layer_bytes


def test_prefill_attention():
    # one chunk of 8 tokens after a prefix of 16: 8 x 16 + 36 causal keys
    f, b = work.prefill_attention(MODEL, KV, [(16, 8)])
    assert f == 2 * 4 * 4 * 16 * (8 * 16 + 36)
    assert b == 2 * (2 * 448 * 2 + 4 * 8 * 16 * (2 * 4 + 2 * 2))


def test_model_flops():
    layer = 64 * 16 * (2 * 4 + 2 * 2) + 3 * 64 * 96 + 2 * 64
    assert work.layer_params(MODEL) == layer
    dense, head = 2 * layer * 2, 2 * 64 * 100
    attn = 4 * 4 * 16 * 2
    # two decode tokens at contexts 10 and 30, one chunk (0, 8)
    got = work.model_flops(MODEL, [10, 30], [(0, 8)])
    want = (2 * (dense + head) + attn * 40) + (8 * dense + head + attn * 36)
    assert got == pytest.approx(want)


def test_roofline_share_names_its_bound():
    share, bound = work.roofline_share(2e9, 1e6, 0.004, PEAK)
    assert bound == "flops" and share == pytest.approx(50.0)
    share, bound = work.roofline_share(1e6, 2e6, 0.004, PEAK)
    assert bound == "bytes" and share == pytest.approx(50.0)


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
