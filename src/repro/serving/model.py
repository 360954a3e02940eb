"""Paged decode / prefill step builders for the dense transformer family.

Two jit-able pure functions over the page pool from
``serving.paged_cache``:

* ``paged_prefill`` — full forward over one prompt (any ``batch``
  layout, including a ContextPlan-permuted one) that captures every
  layer's projected+roped K/V via ``models.transformer._block`` and
  scatters prompt K/V + slot bitfields/positions straight into the
  page pool. Because ``cfg`` flows through ``layers.run_attention``
  unchanged, a cfg with ``cp_mesh`` set runs the prefill attention
  through the context-parallel bodies — CP prefill writing the sharded
  decode cache with no re-gather in between.
* ``make_paged_decode_step`` — one-token decode for a batch of
  requests with *ragged* per-row cache positions: each row scatters its
  new K/V into its own (page, slot) coordinate, then attends over its
  resident pages either through the dense-gather XLA reference
  (``attn="xla"``) or the single-query flash-decode kernel
  (``attn="kernel"`` on TPU, ``attn="interpret"`` on CPU).

The decode layer loop is a *python* loop (not ``lax.scan``): the Pallas
kernel needs a static per-layer sliding window, and unrolling is what
lets gemma2's local/global alternation run on the kernel path at decode
— the training side has to fall back to XLA for exactly this reason.
Decode state is tiny (one token), so the unrolled trace stays cheap.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import bam
from repro.models import layers as L
from repro.models import transformer as T

ATTN_PATHS = ("xla", "kernel", "interpret")


def check_serving_cfg(cfg: ModelConfig) -> None:
    """The paged path covers the dense-transformer decode family; fail
    loudly (and early) for the families it does not."""
    from repro.models import api
    if api.module_for(cfg) is not T:
        raise ValueError(
            f"paged serving supports the dense transformer family; "
            f"{cfg.name!r} decodes through "
            f"{api.module_for(cfg).__name__}")
    if cfg.mm is not None and cfg.mm.mrope_sections:
        raise ValueError(
            f"{cfg.name!r} uses M-RoPE (pos3) — not yet wired through "
            f"the paged decode path")


def static_layer_window(cfg: ModelConfig, layer_idx: int) -> int:
    """Python-int twin of ``transformer._layer_window`` (the kernel
    needs the window at trace time; the unrolled decode loop makes the
    layer index static)."""
    if cfg.local_global_pattern:
        is_global = (layer_idx % cfg.local_global_pattern) == (
            cfg.local_global_pattern - 1)
        return 0 if is_global else cfg.sliding_window
    return cfg.sliding_window


def grid_window(cfg: ModelConfig) -> int:
    """Sliding window the *decode grid* may prune pages with: only
    when every layer shares it. With gemma2-style alternation the grid
    must keep full-attention reach (window=0) and per-layer windows
    mask in-kernel instead."""
    return 0 if cfg.local_global_pattern else cfg.sliding_window


def _replicate_kv(cfg: ModelConfig, k, v):
    """Match the cache's (possibly ``decode_kv_replicate``-widened) KV
    head count. k/v are 4-D with heads at axis 2 — [B, T, Hkv, hd] at
    decode, [L, T, Hkv, hd] for the stacked prefill K/V."""
    rep = cfg.decode_kv_replicate
    if rep > k.shape[2]:
        k = bam.repeat_kv(k, rep // k.shape[2])
        v = bam.repeat_kv(v, rep // v.shape[2])
    return k, v


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill_forward(params, cfg: ModelConfig, batch):
    """Forward over the prompt that keeps each layer's K/V.

    Returns (logits [B,T,V], k [L,B,T,Hkv,hd], v [L,B,T,Hkv,hd]).
    The layer loop is unrolled so the per-layer K/V can be stacked —
    same math as ``transformer.hidden`` (it runs ``T._block``)."""
    x = T.embed_tokens(params, cfg, batch)
    ks, vs = [], []
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        x, _aux, (k, v) = T._block(cfg, lp, x, batch, jnp.int32(i), None)
        ks.append(k)
        vs.append(v)
    h = L.apply_norm(cfg, params["final_ln"], x)
    return T.unembed(params, cfg, h), jnp.stack(ks), jnp.stack(vs)


def paged_prefill(params, cfg: ModelConfig, cache, batch, page, slot):
    """Run the prompt forward and write its K/V + slot metadata into
    the page pool.

    batch: tokens/positions/bits [1, T] (one request — continuous
    batching admits and prefills requests one at a time); ``page``/
    ``slot`` [T] int32 physical coordinates from
    ``PageTable.coords`` — in whatever order the batch rows are laid
    out, so a ContextPlan-permuted batch writes each rank's token run
    into its own pages. Rows with bits=0 (page-alignment padding) are
    written but masked everywhere.

    Returns (logits [1,T,V], new cache). jit with static cfg; retraces
    per distinct padded prompt length.
    """
    if batch.get("bits") is None:
        raise ValueError(
            "paged_prefill needs batch['bits'] — the page pool's mask "
            "metadata is the bitfield; use bam.causal_bits for text")
    logits, k, v = prefill_forward(params, cfg, batch)
    k, v = _replicate_kv(cfg, k[:, 0], v[:, 0])     # [L, T, Hkv, hd]
    new = dict(cache)
    # pool [L, P, Hkv, page_size, hd]: the (page, slot) index pair is
    # split by the head axis, so the indexed view is [T, L, Hkv, hd]
    new["k"] = cache["k"].at[:, page, :, slot].set(
        jnp.swapaxes(k, 0, 1).astype(cache["k"].dtype))
    new["v"] = cache["v"].at[:, page, :, slot].set(
        jnp.swapaxes(v, 0, 1).astype(cache["v"].dtype))
    new["bits"] = cache["bits"].at[page, slot].set(batch["bits"][0])
    new["pos"] = cache["pos"].at[page, slot].set(batch["positions"][0])
    return logits, new


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def paged_decode_step(params, cfg: ModelConfig, cache, batch, *,
                      attn: str = "xla"):
    """One decode token for every batch row against the page pool.

    batch keys:
      tokens/positions/bits [B, 1] — positions are *semantic* (RoPE +
        masking); rows are independent requests at arbitrary ragged
        offsets;
      page/slot [B] int32 — each row's physical insert coordinate
        (empty rows point at the null page);
      page_tables [B, max_pages] int32 (attn="xla") — dense gather
        rows, null-page padded;
      steps — 5-tuple of [n_steps] int32 arrays (attn="kernel"/
        "interpret") from ``build_decode_grid(...).arrays()``.

    Returns (logits [B, 1, V], new cache). The new token's K/V and its
    bits/pos metadata are inserted *before* attention, so each query
    attends itself — matching ``transformer.decode_step``.
    """
    if attn not in ATTN_PATHS:
        raise ValueError(f"attn={attn!r}; pick from {ATTN_PATHS}")
    from repro.kernels.paged_decode import (paged_decode_attention,
                                            paged_decode_ref)
    B = batch["tokens"].shape[0]
    page = batch["page"]
    slot = batch["slot"]
    pos = batch["positions"]                            # [B, 1]
    q_bits = batch.get("bits")
    if q_bits is None:
        q_bits = jnp.full((B, 1), bam.text_token(), jnp.uint32)

    x = T.embed_tokens(params, cfg, batch)              # [B, 1, d]
    bits_pages = cache["bits"].at[page, slot].set(q_bits[:, 0])
    pos_pages = cache["pos"].at[page, slot].set(pos[:, 0])
    ks, vs = cache["k"], cache["v"]

    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        window = static_layer_window(cfg, i)
        h = L.apply_norm(cfg, lp["ln1"], x)
        q, k, v = L.attn_project_qkv(lp["attn"], cfg, h, h)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
        k, v = _replicate_kv(cfg, k, v)
        ks = ks.at[i, page, :, slot].set(k[:, 0].astype(ks.dtype))
        vs = vs.at[i, page, :, slot].set(v[:, 0].astype(vs.dtype))
        if attn == "xla":
            out = paged_decode_ref(
                q[:, 0], ks[i], vs[i], q_bits, pos, bits_pages, pos_pages,
                batch["page_tables"], softcap=cfg.attn_softcap,
                window=window)
        else:
            out = paged_decode_attention(
                q[:, 0], ks[i], vs[i], q_bits, pos, bits_pages, pos_pages,
                batch["steps"], softcap=cfg.attn_softcap, window=window,
                interpret=(attn == "interpret"))
        attn_out = out[:, None].reshape(B, 1, cfg.q_dim) @ lp["attn"]["wo"]
        if cfg.post_block_norm:
            attn_out = L.apply_norm(cfg, lp["post_ln1"], attn_out)
        x = x + attn_out
        h = L.apply_norm(cfg, lp["ln2"], x)
        mlp_out, _ = T._default_ffn(lp, h, cfg)
        if cfg.post_block_norm:
            mlp_out = L.apply_norm(cfg, lp["post_ln2"], mlp_out)
        x = x + mlp_out

    h = L.apply_norm(cfg, params["final_ln"], x)
    logits = T.unembed(params, cfg, h)
    return logits, {"k": ks, "v": vs, "bits": bits_pages, "pos": pos_pages}


def make_paged_decode_step(cfg: ModelConfig, attn: str = "xla"):
    """jit-ready closure over (params, cache, batch)."""
    check_serving_cfg(cfg)

    def step(params, cache, batch):
        return paged_decode_step(params, cfg, cache, batch, attn=attn)

    return step
