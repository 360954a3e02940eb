"""Shared neural-net layer library (pure functional, pytree params).

Every model family in ``repro.models`` builds on these primitives. All
parameters are plain dicts of jnp arrays; init functions take an explicit
PRNG key; apply functions are pure. Layer stacks use ``lax.scan`` over
stacked parameters (leading ``L`` axis) — required for compile
tractability of 28–54-layer models under a 512-device dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

Params = dict


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: float = 0.02):
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype, scale: float = 0.02):
    return (jax.random.normal(key, (vocab, d)) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * lax.rsqrt(var + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(dt)


def layernorm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def norm_init(cfg: ModelConfig, d: int, dtype):
    if cfg.norm == "rmsnorm":
        return {"w": jnp.zeros((d,), dtype)}
    return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def apply_norm(cfg: ModelConfig, p: Params, x):
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["w"])
    return layernorm(x, p["w"], p["b"])


# ---------------------------------------------------------------------------
# RoPE (+ M-RoPE for qwen2-vl)
# ---------------------------------------------------------------------------

def rope_angles(pos, head_dim: int, theta: float):
    """pos: [..., T] int -> cos/sin [..., T, head_dim//2] fp32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, pos, theta: float):
    """x: [B, T, H, hd]; pos: [B, T] (or [T]) -> rotated x (split-half form)."""
    hd = x.shape[-1]
    cos, sin = rope_angles(pos, hd, theta)   # [B, T, hd/2]
    cos = cos[..., None, :]                  # [B, T, 1, hd/2]
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, pos3, sections, theta: float):
    """Multimodal RoPE (qwen2-vl, arXiv:2409.12191).

    pos3: [3, B, T] (temporal, height, width) position ids. ``sections``
    partitions the half-dim into (t, h, w) bands; each band rotates by its
    own position stream. For text tokens all three ids are equal, reducing
    to standard RoPE.
    """
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    # build per-frequency position selection
    sec_ids = jnp.concatenate([
        jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)
    ])                                                   # [half]
    pos3f = pos3.astype(jnp.float32)                     # [3, B, T]
    pos_sel = jnp.take(pos3f, sec_ids, axis=0)           # [half, B, T]
    ang = jnp.moveaxis(pos_sel, 0, -1) * freqs           # [B, T, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Masked scaled-dot-product attention (XLA path).
# The Pallas BAM kernel (repro.kernels) implements the same semantics for
# the perf-critical path; `repro.core.bam.allowed_mask` is the single
# source of truth for mask semantics.
# ---------------------------------------------------------------------------

from repro.core.bam import repeat_kv  # noqa: E402  (shared GQA expand)


def sdpa(q, k, v, mask, *, softcap: float = 0.0, scale: Optional[float] = None):
    """q: [B,Tq,H,hd] k/v: [B,Tk,H,hd] mask: broadcastable to [B,H,Tq,Tk] bool."""
    hd = q.shape[-1]
    scale = scale if scale is not None else hd ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    neg = jnp.finfo(jnp.float32).min
    logits = jnp.where(mask, logits, neg)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    # rows with no allowed key (padding) -> zero output, not NaN
    any_ok = jnp.any(mask, axis=-1, keepdims=True)
    probs = jnp.where(any_ok, probs, 0.0).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def sdpa_q_chunked(q, k, v, mask_fn, chunk: int, *, softcap: float = 0.0):
    """Flash-style q-chunked attention for the XLA path: queries are
    processed in blocks of ``chunk``; the mask tile is built per block
    by ``mask_fn(start, size)`` so neither the [Tq,Tk] logits nor the
    [Tq,Tk] mask ever materialize (§Perf-D, the prefill memory lever).
    q/k/v: [B,T,H,hd] (k/v already GQA-expanded)."""
    B, Tq, H, hd = q.shape
    assert Tq % chunk == 0, (Tq, chunk)
    nc = Tq // chunk

    def body(_, i):
        qs = lax.dynamic_slice_in_dim(q, i * chunk, chunk, axis=1)
        mask = mask_fn(i * chunk, chunk)

        def f(qs, mask):
            return sdpa(qs, k, v, mask, softcap=softcap)
        return None, jax.checkpoint(f)(qs, mask)

    _, outs = lax.scan(body, None, jnp.arange(nc))
    # [nc, B, chunk, H, hd] -> [B, Tq, H, hd]
    return jnp.moveaxis(outs, 0, 1).reshape(B, Tq, H, hd)


def causal_mask(q_pos, kv_pos, window: int = 0):
    """q_pos: [B,Tq], kv_pos: [B,Tk] -> [B,1,Tq,Tk] bool."""
    m = kv_pos[:, None, :] <= q_pos[:, :, None]
    if window:
        m &= (q_pos[:, :, None] - kv_pos[:, None, :]) < window
    return m[:, None]


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, dtype, cross: bool = False) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, cfg.q_dim, dtype),
        "wk": dense_init(ks[1], d, cfg.kv_dim, dtype),
        "wv": dense_init(ks[2], d, cfg.kv_dim, dtype),
        "wo": dense_init(ks[3], cfg.q_dim, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dtype)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dtype)
    if cfg.use_qk_norm:
        p["qnorm"] = jnp.zeros((cfg.head_dim,), dtype)
        p["knorm"] = jnp.zeros((cfg.head_dim,), dtype)
    return p


def attn_project_qkv(p: Params, cfg: ModelConfig, x_q, x_kv):
    b, tq, _ = x_q.shape
    tk = x_kv.shape[1]
    q = x_q @ p["wq"]
    k = x_kv @ p["wk"]
    v = x_kv @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, tq, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, tk, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, tk, cfg.num_kv_heads, cfg.head_dim)
    if cfg.use_qk_norm:
        q = rmsnorm(q, p["qnorm"])
        k = rmsnorm(k, p["knorm"])
    return q, k, v


def resolve_attn_impl(cfg: ModelConfig, bits, kv_override=None) -> str:
    """The attention implementation of one call: XLA without BAM bits
    (the encoders' full masks, the gemma2 traced-window layers) and on
    the decode path (``kv_override``); else ``cfg.attn_impl`` when set
    explicitly; else, by default, the fused BAM kernel on TPU and XLA on
    other backends."""
    if bits is None or kv_override is not None:
        return "xla"
    if cfg.attn_impl != "auto":
        return cfg.attn_impl
    return "bam_kernel" if jax.default_backend() == "tpu" else "xla"


def run_attention(p: Params, cfg: ModelConfig, x_q, *, x_kv=None, q_pos=None,
                  kv_pos=None, mask=None, mask_fn=None, rope: bool = True,
                  pos3=None, window: int = 0, kv_override=None, bits=None,
                  kv_bits=None):
    """Full attention block. ``mask``: [B,1|H,Tq,Tk] bool or None (causal).
    ``mask_fn(start, size)`` enables the q-chunked path
    (cfg.attn_q_chunk) without materializing the full mask.

    kv_override: (k, v) already-projected cache tensors (decode path).
    bits/kv_bits: BAM bitfields [B,T*]; when given and
    ``resolve_attn_impl`` picks a kernel impl (by default on TPU),
    attention dispatches to the fused Pallas path
    (repro.kernels.ops.bam_attention — mask in-registers, LSE
    residuals, fused backward) with ``window`` as the static sliding
    window and tiles from ``ops.flash_blocks``. The decode path
    (kv_override) stays on XLA.

    Context parallelism: when ``cfg.cp_mesh`` is set and bits are
    given, attention dispatches to ``core.context_parallel
    .cp_attention`` instead — the token axis shards over
    ``cfg.cp_axis``, per-step math follows ``resolve_attn_impl``, and
    the combining-aware custom_vjp keeps the whole thing differentiable.
    Inputs must already be permuted to the ContextPlan layout.
    """
    impl = resolve_attn_impl(cfg, bits, kv_override)
    x_kv = x_q if x_kv is None else x_kv
    b, tq, _ = x_q.shape
    q, k, v = attn_project_qkv(p, cfg, x_q, x_kv)
    if rope:
        # NB: k is projected from x_kv; in every rope=True call site
        # x_kv is x_q (self-attention), so the fresh K rotates by the
        # *query* positions. kv_pos describes already-cached tokens and
        # is only a masking input (they were roped when inserted).
        if pos3 is not None and cfg.mm is not None and cfg.mm.mrope_sections:
            q = apply_mrope(q, pos3, cfg.mm.mrope_sections, cfg.rope_theta)
            k = apply_mrope(k, pos3, cfg.mm.mrope_sections, cfg.rope_theta)
        else:
            q = apply_rope(q, q_pos, cfg.rope_theta)
            k = apply_rope(k, q_pos, cfg.rope_theta)
    if kv_override is not None:
        k, v = kv_override(k, v)
    elif cfg.cp_mesh is not None and bits is not None:
        # context-parallel dispatch: global arrays in plan layout, the
        # token axis shard_map'd over cfg.cp_axis; differentiable on
        # every impl (combining-aware custom_vjp in the CP bodies).
        from repro.core.context_parallel import cp_attention
        with jax.named_scope("sdpa"):
            out = cp_attention(
                cfg.cp_mesh, cfg.cp_axis, q, k, v, bits,
                bits if kv_bits is None else kv_bits, q_pos,
                q_pos if kv_pos is None else kv_pos, method=cfg.cp_method,
                softcap=cfg.attn_softcap, window=window, impl=impl)
        return out.reshape(b, tq, cfg.q_dim) @ p["wo"], (k, v)
    elif impl != "xla":
        # fused Pallas BAM path: GQA folded into the kernel's index
        # maps, bitfield mask evaluated in-registers, custom_vjp with
        # (out, lse) residuals — the training hot path.
        from repro.kernels.ops import bam_attention, flash_blocks
        block_q, block_k = flash_blocks(tq, k.shape[1])
        with jax.named_scope("sdpa"):
            out = bam_attention(
                q, k, v, bits, bits if kv_bits is None else kv_bits,
                q_pos, q_pos if kv_pos is None else kv_pos,
                softcap=cfg.attn_softcap, window=window, impl=impl,
                block_q=block_q, block_k=block_k)
        return out.reshape(b, tq, cfg.q_dim) @ p["wo"], (k, v)
    with jax.named_scope("sdpa"):
        # n_rep from the actual tensor: decode caches may carry
        # replicated KV heads (cfg.decode_kv_replicate)
        n_rep = cfg.num_heads // k.shape[2]
        kf, vf = repeat_kv(k, n_rep), repeat_kv(v, n_rep)
        chunk = cfg.attn_q_chunk
        if mask_fn is not None and chunk and tq % chunk == 0 \
                and tq > chunk:
            out = sdpa_q_chunked(q, kf, vf, mask_fn, chunk,
                                 softcap=cfg.attn_softcap)
        else:
            if mask is None and mask_fn is not None:
                mask = mask_fn(0, tq)
            if mask is None:
                assert q_pos is not None
                mask = causal_mask(q_pos,
                                   kv_pos if kv_pos is not None else q_pos,
                                   window)
            out = sdpa(q, kf, vf, mask, softcap=cfg.attn_softcap)
    out = out.reshape(b, tq, cfg.q_dim) @ p["wo"]
    return out, (k, v)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, d_ff: int, dtype, gated: bool) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d, d_ff, dtype),
         "w_down": dense_init(ks[1], d_ff, d, dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], d, d_ff, dtype)
    return p


def run_mlp(p: Params, x, act: str):
    up = x @ p["w_up"]
    if "w_gate" in p:
        g = x @ p["w_gate"]
        h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * up
    else:
        h = jax.nn.silu(up) if act == "silu" else jax.nn.gelu(up)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# KV cache (stacked over layers for scan)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                  num_layers: Optional[int] = None):
    L = num_layers if num_layers is not None else cfg.num_layers
    shape = (L, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_update(cache_k, cache_v, k_new, v_new, index):
    """Insert [B, Tnew, Hkv, hd] at position ``index`` (single layer)."""
    k = lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                 (0, index, 0, 0))
    v = lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                 (0, index, 0, 0))
    return k, v


def cache_update_ragged(cache_k, cache_v, k_new, v_new, index):
    """Per-row insert for continuous batching: ``index`` is [B] int32
    (each request sits at its own ragged cache offset), ``k_new``/
    ``v_new`` are one-token [B, 1, Hkv, hd]."""
    rows = jnp.arange(cache_k.shape[0])
    k = cache_k.at[rows, index].set(k_new[:, 0].astype(cache_k.dtype))
    v = cache_v.at[rows, index].set(v_new[:, 0].astype(cache_v.dtype))
    return k, v


# ---------------------------------------------------------------------------
# Stacked-layer init helper
# ---------------------------------------------------------------------------

def stacked_init(per_layer_init, key, num_layers: int):
    """vmap a per-layer init over stacked keys -> params with leading L dim."""
    keys = jax.random.split(key, num_layers)
    return jax.vmap(per_layer_init)(keys)


def scan_layers(body, params_stacked, carry, cfg: ModelConfig, *,
                length: Optional[int] = None, extra=None):
    """Run ``carry = body(carry, layer_params, layer_idx, extra)`` over the
    stacked layer params with lax.scan (+ optional remat)."""
    L = length if length is not None else cfg.num_layers
    idx = jnp.arange(L)

    def step(c, xs):
        lp, i = xs
        fn = body
        if cfg.remat:
            fn = jax.checkpoint(body, static_argnums=(), policy=None)
        return fn(c, lp, i, extra), None

    carry, _ = lax.scan(step, carry, (params_stacked, idx))
    return carry
