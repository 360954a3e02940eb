"""Concrete MLLM model code: encoder backbones + composition helpers.

``encoder_init``/``encoder_forward`` implement a bidirectional
transformer encoder backbone over stubbed frame/patch embeddings —
the EVA-CLIP / Whisper-encoder stand-ins of the paper's Table 1.
``build_paper_mllm`` assembles the paper's VLM / ALM / VALM evaluation
models (vision+audio encoders in S/M/L + a Llama-style LLM) through the
Cornstarch MultimodalModule.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.configs.paper_mllm import (audio_encoder_config, llm_config,
                                      vision_encoder_config)
from repro.models import layers as L


# ---------------------------------------------------------------------------
# Generic bidirectional encoder backbone (frontend stubbed)
# ---------------------------------------------------------------------------

def _enc_layer_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 2)
    return {
        "ln1": L.norm_init(cfg, cfg.d_model, dtype),
        "attn": L.attn_init(ks[0], cfg, dtype),
        "ln2": L.norm_init(cfg, cfg.d_model, dtype),
        "mlp": L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype, gated=False),
    }


def encoder_init(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.dtype)
    k1, k2 = jax.random.split(key)
    return {
        "layers": L.stacked_init(
            lambda k: _enc_layer_init(k, cfg, dtype), k1, cfg.num_layers),
        "final_ln": L.norm_init(cfg, cfg.d_model, dtype),
    }


def encoder_forward(params, cfg: ModelConfig, embeds):
    """embeds: [B, T_m, d_m] precomputed frontend output."""
    B, Tm, _ = embeds.shape
    pos = jnp.broadcast_to(jnp.arange(Tm, dtype=jnp.int32)[None], (B, Tm))
    full = jnp.ones((B, 1, Tm, Tm), bool)
    x = embeds

    def body(x, lp):
        def blk(x):
            h = L.apply_norm(cfg, lp["ln1"], x)
            with jax.named_scope("attention"):
                a, _ = L.run_attention(lp["attn"], cfg, h, q_pos=pos,
                                       mask=full, rope=False)
            x = x + a
            h = L.apply_norm(cfg, lp["ln2"], x)
            with jax.named_scope("mlp"):
                return x + L.run_mlp(lp["mlp"], h, "gelu")
        if cfg.remat:
            blk = jax.checkpoint(blk)
        return blk(x), None

    x, _ = lax.scan(body, x, params["layers"])
    return L.apply_norm(cfg, params["final_ln"], x)


# ---------------------------------------------------------------------------
# Paper evaluation MLLMs (Table 1 zoo)
# ---------------------------------------------------------------------------

VISION_TOKENS = 576     # ~(1280x720 -> 24x24 patches), paper setup
AUDIO_TOKENS = 750      # 30 s clip at Whisper 25 fps after conv stride

def build_paper_mllm(kind: str = "valm", llm_size: str = "M",
                     vision_size: str = "S", audio_size: str = "S",
                     reduced: bool = False, text_len: int = 1024):
    """kind: vlm | alm | valm. Frozen encoders + frozen LLM + trainable
    projectors — the paper's §6 configuration."""
    from repro.core.modality import ModalityModule, MultimodalModule
    encoders: Dict[str, ModalityModule] = {}
    n_vis = 16 if reduced else VISION_TOKENS
    n_aud = 16 if reduced else AUDIO_TOKENS
    if kind in ("vlm", "valm"):
        encoders["vision"] = ModalityModule(
            "vision", vision_encoder_config(vision_size, reduced),
            modality_id=1, projector="linear", num_tokens=n_vis)
    if kind in ("alm", "valm"):
        encoders["audio"] = ModalityModule(
            "audio", audio_encoder_config(audio_size, reduced),
            modality_id=2, projector="linear", num_tokens=n_aud)
    mllm = MultimodalModule(
        encoders=encoders, llm_cfg=llm_config(llm_size, reduced),
        frozen_llm=True)
    for name in encoders:
        mllm.freeze(name, module=True, projector=False)
    return mllm
