"""Dense decoder-only transformer family.

Covers (via ModelConfig flags): starcoder2-7b (GQA+RoPE, layernorm, gelu),
qwen3-1.7b (qk_norm), gemma2-9b (local/global alternation, softcaps,
post-block norms, tied embeddings, embed scale), qwen2.5-14b (QKV bias),
and the qwen2-vl-7b language backbone (M-RoPE via cfg.mm). The MoE family
(repro.models.moe) reuses this skeleton via the ``ffn`` hook.

Interface (shared by all model families in repro.models):
    init(key, cfg)                          -> params
    forward(params, cfg, batch)             -> (logits [B,T,V], aux dict)
    hidden(params, cfg, batch)              -> (final hidden [B,T,d], aux)
    init_cache(cfg, batch, max_len, dtype)  -> cache
    decode_step(params, cfg, cache, batch)  -> (logits [B,1,V], cache)

batch keys: tokens [B,T] int32; positions [B,T] int32; optional
bits [B,T] uint32 (BAM; None => causal); optional inputs_embeds
[B,T,d] + embed_mask [B,T] bool (multimodal merge: where True, take
inputs_embeds instead of the token embedding — Cornstarch's
``cb_before_llm`` modality-token merge); optional pos3 [3,B,T] (M-RoPE).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.core import bam
from repro.models import layers as L

FFN = Callable  # (layer_params, h [B,T,d]) -> (out [B,T,d], aux scalar)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, dtype, ffn_init=None):
    ks = jax.random.split(key, 6)
    gated = cfg.act == "silu" or cfg.name.startswith("gemma2")
    p = {
        "ln1": L.norm_init(cfg, cfg.d_model, dtype),
        "attn": L.attn_init(ks[0], cfg, dtype),
        "ln2": L.norm_init(cfg, cfg.d_model, dtype),
    }
    if ffn_init is None:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype, gated)
    else:
        p["mlp"] = ffn_init(ks[1])
    if cfg.post_block_norm:
        p["post_ln1"] = L.norm_init(cfg, cfg.d_model, dtype)
        p["post_ln2"] = L.norm_init(cfg, cfg.d_model, dtype)
    return p


def init(key, cfg: ModelConfig, ffn_init=None):
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    params = {
        "embed": L.embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype),
        "layers": L.stacked_init(
            lambda k: _layer_init(k, cfg, dtype, ffn_init), k_layers,
            cfg.num_layers),
        "final_ln": L.norm_init(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(k_out, cfg.d_model, cfg.vocab_size,
                                         dtype)
    return params


# ---------------------------------------------------------------------------
# Layer body
# ---------------------------------------------------------------------------

def _layer_window(cfg: ModelConfig, layer_idx):
    """gemma2 alternation: every cfg.local_global_pattern-th layer is
    global, others use cfg.sliding_window."""
    if cfg.local_global_pattern:
        is_global = (layer_idx % cfg.local_global_pattern) == (
            cfg.local_global_pattern - 1)
        return jnp.where(is_global, 0, cfg.sliding_window).astype(jnp.int32)
    return jnp.full((), cfg.sliding_window, jnp.int32)


def _mask_for(cfg: ModelConfig, batch, window, kv_bits=None, kv_pos=None,
              q_slice=None):
    """Lazily build the attention mask (XLA fuses it into the softmax).
    window is a traced scalar (0 = full). q_slice=(start, size) builds
    just that block of query rows (the q-chunked path)."""
    q_pos = batch["positions"]
    kv_pos_full = q_pos if kv_pos is None else kv_pos
    bits = batch.get("bits")
    q_bits = bits
    if q_slice is not None:
        start, size = q_slice
        q_pos = lax.dynamic_slice_in_dim(q_pos, start, size, axis=1)
        if bits is not None:
            q_bits = lax.dynamic_slice_in_dim(bits, start, size, axis=1)
    win_ok = jnp.where(
        window > 0,
        (q_pos[:, :, None] - kv_pos_full[:, None, :]) < window, True)
    if bits is not None:
        kvb = bits if kv_bits is None else kv_bits
        m = bam.allowed_mask(q_bits, kvb, q_pos, kv_pos_full)
        q_text = bam.own_modality(
            q_bits[:, :, None].astype(jnp.uint32)) == bam.TEXT
        m = m & (win_ok | ~q_text)  # window constrains text queries only
        return m[:, None]
    m = kv_pos_full[:, None, :] <= q_pos[:, :, None]
    return (m & win_ok)[:, None]


def _default_ffn(lp, h, cfg):
    return L.run_mlp(lp["mlp"], h, cfg.act), jnp.float32(0.0)


def _block(cfg: ModelConfig, p, x, batch, layer_idx, ffn: Optional[FFN]):
    window = _layer_window(cfg, layer_idx)

    def mask_fn(start, size):
        return _mask_for(cfg, batch, window, q_slice=(start, size))

    # fused Pallas BAM / context-parallel dispatch needs a *static*
    # window; the gemma2 local/global alternation traces it per layer,
    # so that stays XLA (a cp_mesh is ignored there: each device then
    # computes full attention — correct, just not context-parallel).
    kernel_bits = None
    if not cfg.local_global_pattern and (
            cfg.cp_mesh is not None
            or L.resolve_attn_impl(cfg, batch.get("bits")) != "xla"):
        kernel_bits = batch.get("bits")

    h = L.apply_norm(cfg, p["ln1"], x)
    with jax.named_scope("attention"):
        attn_out, kv = L.run_attention(
            p["attn"], cfg, h, q_pos=batch["positions"], mask_fn=mask_fn,
            pos3=batch.get("pos3"), bits=kernel_bits,
            window=cfg.sliding_window if kernel_bits is not None else 0)
    if cfg.post_block_norm:
        attn_out = L.apply_norm(cfg, p["post_ln1"], attn_out)
    x = x + attn_out
    h = L.apply_norm(cfg, p["ln2"], x)
    with jax.named_scope("mlp"):
        if ffn is None:
            mlp_out, aux = _default_ffn(p, h, cfg)
        else:
            mlp_out, aux = ffn(p, h, layer_idx)
    if cfg.post_block_norm:
        mlp_out = L.apply_norm(cfg, p["post_ln2"], mlp_out)
    x = x + mlp_out
    if cfg.seq_shard_activations:
        from repro.launch import sharding as shd
        x = shd.constrain_residual(x)
    # kv: the layer's projected+roped K/V — discarded in training
    # (hidden's scan), captured by the serving prefill so prompt K/V
    # can be written straight into the paged decode cache
    return x, aux, kv


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, batch):
    x = params["embed"][batch["tokens"]]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if batch.get("inputs_embeds") is not None:
        x = jnp.where(batch["embed_mask"][..., None],
                      batch["inputs_embeds"].astype(x.dtype), x)
    return x


@jax.named_scope("llm")
def hidden(params, cfg: ModelConfig, batch, ffn: Optional[FFN] = None):
    x = embed_tokens(params, cfg, batch)

    def body(carry, xs):
        x, aux = carry
        lp, i = xs

        def blk(x):
            return _block(cfg, lp, x, batch, i, ffn)
        if cfg.remat:
            blk = jax.checkpoint(blk)
        x, a, _ = blk(x)
        return (x, aux + a), None

    (x, aux), _ = lax.scan(
        body, (x, jnp.float32(0.0)),
        (params["layers"], jnp.arange(cfg.num_layers)))
    return L.apply_norm(cfg, params["final_ln"], x), {"aux_loss": aux}


@jax.named_scope("lm_head")
def unembed(params, cfg: ModelConfig, h):
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = h @ w
    if cfg.final_softcap:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits


def forward(params, cfg: ModelConfig, batch, ffn: Optional[FFN] = None):
    h, aux = hidden(params, cfg, batch, ffn)
    return unembed(params, cfg, h), aux


# ---------------------------------------------------------------------------
# Decode (serve_step): one new token against a KV cache
# ---------------------------------------------------------------------------

def _cache_cfg(cfg: ModelConfig) -> ModelConfig:
    if cfg.decode_kv_replicate > cfg.num_kv_heads:
        if (cfg.num_heads % cfg.decode_kv_replicate != 0
                or cfg.decode_kv_replicate % cfg.num_kv_heads != 0):
            raise ValueError(
                f"{cfg.name}: decode_kv_replicate="
                f"{cfg.decode_kv_replicate} must divide num_heads="
                f"{cfg.num_heads} and be a multiple of num_kv_heads="
                f"{cfg.num_kv_heads}")
        return cfg.replace(num_kv_heads=cfg.decode_kv_replicate,
                           decode_kv_replicate=0)
    return cfg


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = jnp.dtype(cfg.dtype) if dtype is None else dtype
    c = L.init_kv_cache(_cache_cfg(cfg), batch, max_len, dtype)
    c["bits"] = jnp.zeros((batch, max_len), jnp.uint32)
    return c


def decode_step(params, cfg: ModelConfig, cache, batch,
                ffn: Optional[FFN] = None):
    """batch: tokens [B,1], positions [B,1] (= current index), optional
    bits [B,1]. cache: {k,v: [L,B,Tmax,Hkv,hd], bits: [B,Tmax]}."""
    B, _ = batch["tokens"].shape
    Tmax = cache["k"].shape[2]
    cur = batch["positions"][:, 0]                    # [B]
    x = embed_tokens(params, cfg, batch)
    kv_pos = jnp.broadcast_to(jnp.arange(Tmax, dtype=jnp.int32)[None],
                              (B, Tmax))

    q_bits = batch.get("bits")
    if q_bits is None:
        q_bits = jnp.full((B, 1), bam.text_token(), jnp.uint32)
    cache_bits = jnp.where(
        kv_pos < cur[:, None], cache["bits"],
        jnp.where(kv_pos == cur[:, None],
                  jnp.broadcast_to(q_bits, kv_pos.shape), jnp.uint32(0)))

    def body(x, xs):
        lp, ck, cv, i = xs
        window = _layer_window(cfg, i)
        mask = bam.allowed_mask(q_bits, cache_bits, batch["positions"], kv_pos)
        win_ok = jnp.where(
            window > 0,
            (batch["positions"][:, :, None] - kv_pos[:, None, :]) < window,
            True)
        mask = (mask & win_ok)[:, None]
        store = {}

        def kv_override(k, v):
            rep = cfg.decode_kv_replicate
            if rep > k.shape[2]:
                k = L.repeat_kv(k, rep // k.shape[2])
                v = L.repeat_kv(v, rep // v.shape[2])
            # per-row scatter: continuous batching decodes requests at
            # ragged cache offsets, so each row inserts at its own cur
            nk, nv = L.cache_update_ragged(ck, cv, k, v, cur)
            store["k"], store["v"] = nk, nv
            return nk, nv

        h = L.apply_norm(cfg, lp["ln1"], x)
        attn_out, _ = L.run_attention(
            lp["attn"], cfg, h, q_pos=batch["positions"], kv_pos=kv_pos,
            mask=mask, pos3=batch.get("pos3"), kv_override=kv_override)
        if cfg.post_block_norm:
            attn_out = L.apply_norm(cfg, lp["post_ln1"], attn_out)
        x = x + attn_out
        h = L.apply_norm(cfg, lp["ln2"], x)
        if ffn is None:
            mlp_out, _ = _default_ffn(lp, h, cfg)
        else:
            mlp_out, _ = ffn(lp, h, i)
        if cfg.post_block_norm:
            mlp_out = L.apply_norm(cfg, lp["post_ln2"], mlp_out)
        x = x + mlp_out
        return x, (store["k"], store["v"])

    layer_ids = jnp.arange(cfg.num_layers)
    x, (new_k, new_v) = lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"], layer_ids))
    h = L.apply_norm(cfg, params["final_ln"], x)
    logits = unembed(params, cfg, h)
    new_bits = cache["bits"].at[jnp.arange(B), cur].set(q_bits[:, 0])
    new_cache = {"k": new_k, "v": new_v, "bits": new_bits}
    return logits, new_cache
