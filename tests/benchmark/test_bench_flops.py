"""The counts of required operations against hand-worked values."""

import json
import os

import pytest

from benchmark import flops
from benchmark.references import resnet, transformer_lm

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("seq", [512, 2048, 8192])
def test_lm365m_flops_per_token(seq):
    # 72 L h^2 + 6 h V + 6 L s h with L=24, h=1024, V=30522:
    # 1,811,939,328 + 187,527,168 = 1,999,466,496 (the issue's 2.00e9).
    want = 1_999_466_496 + 6 * 24 * seq * 1024
    assert transformer_lm.flops_per_token(
        config("lm365m.json"), seq) == want
    assert abs(want - (2.00e9 + 6 * 24 * seq * 1024)) / want < 5e-4


def test_lm365m_old_count_overstates():
    # bench.py's 6 N + 12 L s h with N = 364.9M parameters.
    cfg = config("lm365m.json")
    for seq, over in ((512, 1.13), (2048, 1.21), (8192, 1.44)):
        old = 6 * 364.9e6 + 12 * 24 * seq * 1024
        new = transformer_lm.flops_per_token(cfg, seq)
        assert old / new == pytest.approx(over, abs=0.01)


def test_attention_counts():
    fwd, bwd = flops.attention_flops(2, 16, 8192, 64, causal=True)
    # 2 products of s x s x d per head forward, 4 backward, half kept.
    assert fwd == 2 * (2 * 2 * 16 * 8192 * 8192 * 64) // 2
    assert bwd == 2 * fwd
    full = flops.attention_flops(2, 16, 8192, 64, causal=False)
    assert full == (2 * fwd, 2 * bwd)
    rd, wr = flops.attention_bytes(2, 16, 8192, 64)
    assert (rd, wr) == (4 * 2 * 16 * 8192 * 64 * 2, 8 * 2 * 16 * 8192 * 64 * 2)


def test_resnet50_multiply_adds():
    layers = dict((n, m) for n, m, _ in resnet.layer_macs(
        config("resnet50.json")))
    assert layers["conv_init"] == 112 * 112 * 7 * 7 * 3 * 64
    # First block: 1x1 64->64, 3x3 64->64, 1x1 64->256, projection, at 56.
    assert layers["stage0.block0.conv1x1a"] == 56 * 56 * 64 * 64
    assert layers["stage0.block0.conv3x3"] == 56 * 56 * 9 * 64 * 64
    assert layers["stage0.block0.conv1x1b"] == 56 * 56 * 64 * 256
    assert layers["stage0.block0.conv_proj"] == 56 * 56 * 64 * 256
    # v1.5: the first 1x1 of a down-sampling block runs before the stride.
    assert layers["stage1.block0.conv1x1a"] == 56 * 56 * 256 * 128
    assert layers["stage1.block0.conv3x3"] == 28 * 28 * 9 * 128 * 128
    assert layers["dense"] == 2048 * 1000
    assert len(layers) == 1 + 16 * 3 + 4 + 1
    assert sum(layers.values()) == 4_089_184_256     # "4.1 G" is MACs


def test_resnet50_flops_per_image():
    cfg = config("resnet50.json")
    macs = sum(m for _, m, _ in resnet.layer_macs(cfg))
    stem = 112 * 112 * 7 * 7 * 3 * 64
    # x 2 FLOPs, x 3 products; the stem needs no gradient to the image.
    assert resnet.flops_per_row(cfg, {}) == 6 * macs - 2 * stem
    assert resnet.flops_per_row(cfg, {}) == 24_299_077_632


def test_flops_per_row_lm():
    cfg = config("lm365m.json")
    assert transformer_lm.flops_per_row(cfg, {"seq_len": 8192}) == 8192 * (
        1_999_466_496 + 6 * 24 * 8192 * 1024)
    assert transformer_lm.attention_shape(
        cfg, {"rows_per_chip": 2, "seq_len": 8192}) == (2, 16, 8192, 64)
