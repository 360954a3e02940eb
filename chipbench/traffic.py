"""The one traffic generator: batches of a multimodal training job from a
traffic file's parameters and a seed.

A traffic file (``chipbench/traffic/<name>.json``) states the job:
``batch`` rows per step, ``text_len`` text tokens per row, ``image_at``
(the text position the image is placed before), the optimizer, and how
the program runs it (``mode``: ``replay`` on one chip, ``spmd`` for the
pipeline, with its plan search). Every row holds one image of the
configuration's ``vision.num_tokens`` patch embeddings.

The arithmetic is that of ``MultimodalDataset`` in the program's
``data/synthetic.py`` (uniform text tokens and labels over the
vocabulary, N(0, 1) float32 patch embeddings standing in for the
patch frontend's output, drawn in that order from one numpy
generator), kept here so that a change to the program cannot move the
yardstick. Every seed gives the same sizes; only the values differ.
"""
from __future__ import annotations

from typing import Any, Dict, Iterator

import numpy as np


def rows_per_step(traffic: Dict[str, Any]) -> int:
    return int(traffic["batch"])


def merged_tokens_per_step(traffic: Dict[str, Any],
                           config: Dict[str, Any]) -> int:
    """LLM-input tokens per step: text plus image tokens, every row."""
    return rows_per_step(traffic) * (int(traffic["text_len"])
                                     + int(config["vision"]["num_tokens"]))


def batches(traffic: Dict[str, Any], config: Dict[str, Any],
            seed: int) -> Iterator[Dict[str, np.ndarray]]:
    """Endless host batches (numpy) for ``seed``; the same seed gives
    the same stream."""
    rng = np.random.default_rng(seed)
    B, T = rows_per_step(traffic), int(traffic["text_len"])
    vocab = int(config["vocab_size"])
    n, d = (int(config["vision"]["num_tokens"]),
            int(config["vision"]["hidden_size"]))
    while True:
        yield {
            "text_tokens": rng.integers(0, vocab, (B, T)).astype(np.int32),
            "labels": rng.integers(0, vocab, (B, T)).astype(np.int32),
            "vision_embeds": rng.normal(0, 1, (B, n, d)).astype(np.float32),
        }


def first_batches(traffic, config, seed: int, k: int):
    it = batches(traffic, config, seed)
    return [next(it) for _ in range(k)]
