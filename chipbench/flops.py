"""Model FLOPs of one training step, from the configuration, the traffic
and the multimodal mask: what the algorithm needs, frozen-aware.

- vision tower: forward only (frozen, and nothing upstream trains);
- projector: forward, plus its weight gradient;
- LLM: forward, plus the input-gradient backward (the LLM is frozen, so
  no weight gradients; the projector's gradient flows through it);
- attention: only over the (query, key) pairs the mask allows; its
  backward needs dQ, dK and dV, twice the forward's two products;
- the LM head only at text positions, where the loss is taken.

Recomputation (rematerialised layers) is not counted, and neither are
norms, softmax or the optimizer, which are not matrix products.
A multiply-add is two FLOPs.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def allowed_pairs(text_len: int, image_at: int, n_img: int) -> int:
    """(query, key) pairs of one merged row: text rows causal over every
    earlier position, image rows over their own image only."""
    T = text_len + n_img
    pos = np.arange(T, dtype=np.int64)
    is_img = (pos >= image_at) & (pos < image_at + n_img)
    return int(np.sum(pos[~is_img] + 1) + n_img * n_img)


def step_flops(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """Per-step FLOPs by part, and their ``total``."""
    B = int(traffic["batch"])
    text_len, image_at = int(traffic["text_len"]), int(traffic["image_at"])
    v = config["vision"]
    n_img, dv = int(v["num_tokens"]), int(v["hidden_size"])
    vq = int(v["num_attention_heads"]) * int(v["head_dim"])
    d, ff = int(config["hidden_size"]), int(config["intermediate_size"])
    hd = int(config["head_dim"])
    q = int(config["num_attention_heads"]) * hd
    kv = int(config["num_key_value_heads"]) * hd
    L, V = int(config["num_hidden_layers"]), int(config["vocab_size"])
    T = text_len + n_img

    enc_lin = int(v["num_hidden_layers"]) * (
        dv * vq * 3 + vq * dv + 2 * dv * int(v["intermediate_size"]))
    enc = 2 * enc_lin * n_img \
        + int(v["num_hidden_layers"]) * 2 * 2 * vq * n_img * n_img
    proj = 2 * 2 * dv * d * n_img
    llm_lin = L * (d * q + 2 * d * kv + q * d + 3 * d * ff)
    attn_fwd = L * 2 * 2 * q * allowed_pairs(text_len, image_at, n_img)
    head = 2 * d * V * text_len
    llm_fwd = 2 * llm_lin * T + attn_fwd + head
    llm_bwd = 2 * llm_lin * T + 2 * attn_fwd + head
    parts = {"vision": float(B * enc), "projector": float(B * proj),
             "llm_forward": float(B * llm_fwd),
             "llm_backward": float(B * llm_bwd)}
    parts["total"] = float(sum(parts.values()))
    return parts
