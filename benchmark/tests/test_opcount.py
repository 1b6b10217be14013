"""The operation count against a hand count for one tiny configuration."""

import pytest

from benchmark import opcount

TINY = {"d_model": 8, "n_heads": 2, "n_layers": 3, "d_ff": 16, "max_len": 4}


def test_flops_per_span_hand_count():
    # per layer: q, k, v, out = 4 * 8*8 = 256 MACs; ffn 2 * 8*16 = 256 MACs
    # 3 layers * 512 MACs * 2 = 3072; embedder cont 3*8 + head 8 = 32 MACs
    assert opcount.flops_per_span(TINY) == 3072 + 64


def test_attention_hand_count():
    # a piece of 3 spans: q k^T 3*3*8 MACs, a v 3*3*8 MACs, 3 layers, 2/MAC
    assert opcount.attention_flops(TINY, 3) == 2 * 3 * 2 * 9 * 8


def test_needed_counts_real_spans_only():
    got = opcount.flops_needed(TINY, [3, 1])
    want = 4 * opcount.flops_per_span(TINY) \
        + opcount.attention_flops(TINY, 3) + opcount.attention_flops(TINY, 1)
    assert got == want


def test_published_sizes():
    vit_l = {"d_model": 1024, "n_layers": 24, "d_ff": 4096}
    assert opcount.flops_per_span(vit_l) == pytest.approx(0.604e9, rel=1e-2)
    vit_h = {"d_model": 1280, "n_layers": 32, "d_ff": 5120}
    assert opcount.flops_per_span(vit_h) == pytest.approx(1.258e9, rel=1e-2)


def test_unknown_device_kind_is_an_error():
    assert opcount.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        opcount.peaks("TPU v99")
