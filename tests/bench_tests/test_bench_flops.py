"""The FLOP and byte functions against hand counts at 345M shapes."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import flops, peaks  # noqa: E402

L, H, F, V, S = 24, 1024, 4096, 50304, 1024


def test_train_flops_gpt2_345m_by_hand():
    b = 8
    tokens = b * S
    qkv = 2 * tokens * H * 3 * H
    attn = 2 * 2 * b * (S * S / 2) * H
    proj = 2 * tokens * H * H
    mlp = 2 * 2 * tokens * H * F
    head = 2 * tokens * H * V
    want = 3 * (L * (qkv + attn + proj + mlp) + head)
    got = flops.train_flops_per_step(L, H, F, V, b, S, causal=True)
    assert got == pytest.approx(want, rel=1e-12)
    # 6 x parameters x tokens is the familiar floor (attention on top)
    n = L * (12 * H * H + 13 * H) + V * H + S * H + 2 * H
    assert 354e6 < n < 356e6 and 1.0 < got / (6 * n * tokens) < 1.15


def test_non_causal_attention_needs_twice_the_pairs():
    c = flops.flash_attention_step(L, 16, 64, 8, S, True)
    n = flops.flash_attention_step(L, 16, 64, 8, S, False)
    assert n[0] == pytest.approx(2 * c[0]) and n[1] == c[1]
    # six matmuls of 2*b*n*s*s/2*d each, per layer
    assert c[0] == pytest.approx(L * 6 * 2 * 8 * 16 * (S * S / 2) * 64)
    # 12 tensors of b*n*s*d bf16 elements, per layer
    assert c[1] == pytest.approx(L * 12 * 8 * 16 * S * 64 * 2)


def test_bert_step_by_hand_is_the_non_causal_count():
    b, s, v = 16, 512, 30592
    tokens = b * s
    per_layer = (2 * tokens * H * 3 * H + 2 * 2 * b * s * s * H
                 + 2 * tokens * H * H + 2 * 2 * tokens * H * F)
    want = 3 * (L * per_layer + 2 * tokens * H * v)
    got = flops.train_flops_per_step(L, H, F, v, b, s, causal=False)
    assert got == pytest.approx(want, rel=1e-12)


def test_roofline_share_names_the_roof_and_never_reads_zero():
    pk = peaks.peaks_for("TPU v5 lite")
    share, roof = flops.roofline_share(197e12, 1.0, 2.0, pk)
    assert roof == "flops" and share == pytest.approx(50.0)
    share, roof = flops.roofline_share(1.0, 819e9, 4.0, pk)
    assert roof == "bytes" and share == pytest.approx(25.0)
    assert flops.roofline_share(1.0, 1.0, 0.0, pk) is None


def test_an_unknown_device_is_an_error_not_a_default():
    assert peaks.peaks_for("TPU v5 lite").flops_per_s == 197e12
    with pytest.raises(ValueError):
        peaks.peaks_for("cpu")
