"""The step's model FLOPs against a count made by hand at test widths,
and the peaks table."""
import json
import os

import numpy as np
import pytest

import flops
import reference
from conftest import BENCH, small_config, small_traffic


def test_allowed_pairs_match_the_reference_mask():
    for text_len, at, n in ((32, 16, 8), (10, 0, 4), (10, 10, 3)):
        _, _, allowed = reference.merge_geometry(text_len, at, n)
        assert flops.allowed_pairs(text_len, at, n) == int(allowed.sum())


def test_step_flops_hand_count():
    cfg, tr = small_config(), small_traffic()
    # vision d 48, 2 layers, 2 heads x 24, mlp 96, 8 tokens; llm d 64,
    # 2 layers, 4 heads / 2 kv of 16, mlp 128, vocab 512; batch 4,
    # 32 text tokens with the image after 16 of them
    n, T, B = 8, 40, 4
    enc_layer = 2 * n * (48 * 48 * 4 + 48 * 96 * 2) + 2 * 2 * 48 * n * n
    vision = 2 * enc_layer
    projector = 2 * (2 * n * 48 * 64)
    lin = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    # text rows: positions 0..15 and 24..39 see p + 1 keys; image rows 8
    pairs = sum(p + 1 for p in range(16)) + sum(p + 1 for p in range(24, 40)) \
        + n * n
    attn = 2 * 2 * 64 * pairs
    head = 2 * 64 * 512 * 32
    fwd = 2 * (2 * lin * T + attn) + head
    bwd = 2 * (2 * lin * T + 2 * attn) + head
    want = B * (vision + projector + fwd + bwd)
    got = flops.step_flops(cfg, tr)
    assert got["total"] == want
    assert got["vision"] == B * vision


def test_align_1600_is_about_fifty_tflop():
    with open(os.path.join(BENCH, "configs", "vlm-qwen3-1.7b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "align-1600.json")) as f:
        tr = json.load(f)
    # four rows of it, whatever the cell's batch
    total = flops.step_flops(cfg, dict(tr, batch=4))["total"]
    assert 40e12 < total < 60e12


def test_peaks_are_keyed_by_device_kind():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == pytest.approx(197e12)
    assert v5e["hbm_bytes_per_s"] == pytest.approx(819e9)
    assert "cpu" not in peaks["devices"]
    assert "TPU v5e" in peaks["source"]
    np.testing.assert_array_less(0, list(v5e.values()))
