"""Shared pieces of the benchmark's CPU tests: the import paths (the
benchmark's own modules, and the program under test for the harness
tests) and a cell small enough for a test run."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_config(**over):
    """A vlm-qwen3-1.7b-shaped configuration at test widths."""
    with open(os.path.join(BENCH, "configs", "vlm-qwen3-1.7b.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=512, torch_dtype="bfloat16")
    cfg["vision"] = dict(cfg["vision"], hidden_size=48, num_hidden_layers=2,
                         num_attention_heads=2, head_dim=24,
                         intermediate_size=96, num_tokens=8)
    cfg["projector"] = dict(cfg["projector"], in_features=48,
                            out_features=64)
    cfg.update(over)
    return cfg


def small_traffic(**over):
    with open(os.path.join(BENCH, "traffic", "align-1600.json"),
              encoding="utf-8") as f:
        t = json.load(f)
    t.update(batch=4, text_len=32, image_at=16)
    t.update(over)
    return t


@pytest.fixture
def config():
    return small_config()


@pytest.fixture
def traffic():
    return small_traffic()
