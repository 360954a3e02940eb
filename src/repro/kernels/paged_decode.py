"""Single-query flash-decode over a paged BAM KV cache — Pallas TPU.

Decode attention is one query token per request against that request's
resident cache pages. The kernel runs a flattened grid

    grid = (Hkv, n_steps),  dimension_semantics = (parallel, arbitrary)

where the step axis is the host-precomputed active-page list from
``repro.serving.paged_cache.build_decode_grid``: per batch row, a
k-major sweep over only the pages its query bitfield can reach. The
five scalar-prefetch operands (``req``, ``page``, ``first``, ``last``,
``active``) drive every BlockSpec index map, so a fully-masked page —
an image's tokens while decoding a text-only document, another
modality's stream, a pruned sliding-window span — costs neither a grid
step nor a K/V page DMA. ``first``/``last`` frame each request's steps
for online-softmax scratch init/flush, the same contract as
``bam.BlockMask`` (and checked by the same kernellint coverage rules).

GQA: a step scores the n_rep query heads that share a KV head as one
(n_rep, hd) tile against the page's (page_size, hd) tile of the
head-major pool [P, Hkv, page_size, hd] — no head-expanded K/V ever
materializes. The mask is evaluated in-registers from the bitfields via
``_mask_tile`` (one [1, page_size] tile of it lives in VREGs per step).
Softcap and sliding window are static params; ``window`` constrains
text queries only, mirroring ``bam.allowed_mask``.

The step arrays are *traced* operands (lengths grow every decode step)
but their length is a static shape — callers bucket ``n_steps``
(``decode_grid_bucket``) to keep the jit cache warm; pad steps carry
``active=0`` and touch nothing.

``paged_decode_ref`` is the XLA fallback: gather each request's pages
dense via its page-table row (null-page padded) and run the reference
masked softmax. It is the serving engine's ``attn="xla"`` path and the
oracle the kernel is tested against.

Decode-only: no VJP. A grid step holds one request's query heads for
one KV head; packing several requests per tile to fill the MXU's rows
is a known follow-up.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bam
from repro.kernels.bam_attention import NEG_INF
from repro.kernels.ref import bam_attention_ref


def _mask_tile(qb, kb, qp, kp, window: int):
    """Query-side bitfields/positions [n, 1] and key-side [1, page_size]
    (int32) -> [n, page_size] bool. Mirrors repro.core.bam.allowed_mask;
    bits never use bit 31, so int32 is exact.

    The training kernels rewrite the bitfields into per-token words in
    XLA once per call (``bam_attention._mask_words``) and reuse each
    word across a row or column of tiles. Decode has no such reuse: the
    pool holds raw bitfields and positions per slot, written by prefill
    and every decode step, and each page tile meets one query row once.
    Rewriting there would cost about as many operations as this mask."""
    nonpad = (qb != 0) & (kb != 0)
    same_doc = bam.instance_id(qb) == bam.instance_id(kb)
    bit_ok = ((bam.attends_set(qb) >> bam.own_modality(kb)) & 1) != 0
    q_text = bam.own_modality(qb) == bam.TEXT
    causal = kp <= qp
    if window:
        causal &= (qp - kp) < window
    within = bam.own_modality(kb) == bam.own_modality(qb)
    # select written as logic: Mosaic cannot lower a where over bools
    rule = (q_text & causal) | (~q_text & within)
    return nonpad & same_doc & bit_ok & rule


# ---------------------------------------------------------------------------
# Kernel body
# ---------------------------------------------------------------------------

def _paged_decode_kernel(req_ref, page_ref, first_ref, last_ref, active_ref,
                         qb_ref, qp_ref, kb_ref, kp_ref,
                         q_ref, k_ref, v_ref,
                         o_ref, m_scr, l_scr, acc_scr, *,
                         softcap: float, window: int, scale: float,
                         block_skip: bool):
    t = pl.program_id(1)

    @pl.when(first_ref[t] == 1)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    allowed = _mask_tile(qb_ref[0], kb_ref[0], qp_ref[0], kp_ref[0],
                         window)                     # [1, page_size]
    is_active = active_ref[t] == 1

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # [n_rep, hd]
        k = k_ref[0, 0].astype(jnp.float32)                  # [ps, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(allowed, s, NEG_INF)
        m_prev = m_scr[...]                                  # [n_rep, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(allowed, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if block_skip:
        # a page that survived grid compaction can still be fully
        # masked for THIS layer's sliding window — skip its MXU work
        pl.when(is_active & jnp.any(allowed))(compute)
    else:
        pl.when(is_active)(compute)

    @pl.when(last_ref[t] == 1)
    def _finish():
        l = l_scr[...]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0, out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Index maps — named defs so kernellint's arity rule can resolve them:
# grid rank 2 (g, t) + 5 scalar-prefetch refs = 7 arguments each.
# ---------------------------------------------------------------------------

def _im_qmeta(g, t, req, page, first, last, active):
    return (req[t], 0, 0)


def _im_page_meta(g, t, req, page, first, last, active):
    return (page[t], 0, 0)


def _im_qgroup(g, t, req, page, first, last, active):
    return (req[t], g, 0, 0)


def _im_ktile(g, t, req, page, first, last, active):
    return (page[t], g, 0, 0)


# ---------------------------------------------------------------------------
# pallas_call wrapper
# ---------------------------------------------------------------------------

def paged_decode_attention(q, k_pages, v_pages, q_bits, q_pos,
                           kv_bits, kv_pos, steps, *,
                           softcap: float = 0.0, window: int = 0,
                           block_skip: bool = True,
                           interpret: bool = False):
    """Paged single-query BAM flash decode.

    q: [B, H, hd] (one token per request row);
    k_pages/v_pages: [P, Hkv, page_size, hd] (H % Hkv == 0) — head-major
    pages, so one (page_size, hd) tile per (page, kv head);
    q_bits: [B, 1] uint32; q_pos: [B, 1] int32;
    kv_bits: [P, page_size] uint32; kv_pos: [P, page_size] int32;
    steps: (req, page, first, last, active) int32 [n_steps] arrays from
    ``build_decode_grid(...).arrays()`` — traced operands; their length
    is the static grid extent.

    Returns [B, H, hd]. Rows whose steps are all inactive (empty batch
    slots, fully-masked queries) come back exactly zero.

    The grid is (Hkv, n_steps): each step scores the n_rep query heads
    that share one KV head against one page, so a page is fetched once
    per KV head, not once per query head.
    """
    B, H, hd = q.shape
    P, Hkv, page_size, hd_k = k_pages.shape
    if hd != hd_k:
        raise ValueError(f"q head_dim {hd} != kv head_dim {hd_k}")
    if H % Hkv:
        raise ValueError(f"GQA needs H % Hkv == 0, got H={H} Hkv={Hkv}")
    n_rep = H // Hkv
    if kv_bits.shape != (P, page_size) or kv_pos.shape != (P, page_size):
        raise ValueError(
            f"kv page metadata {kv_bits.shape}/{kv_pos.shape} does not "
            f"match the page pool ({P}, {page_size})")
    if q_bits.shape != (B, 1) or q_pos.shape != (B, 1):
        raise ValueError(
            f"q_bits/q_pos must be [B, 1]=({B}, 1), got "
            f"{q_bits.shape}/{q_pos.shape}")
    req, page, first, last, active = (jnp.asarray(s, jnp.int32)
                                      for s in steps)
    n_steps = req.shape[0]
    if not all(s.shape == (n_steps,) for s in (page, first, last, active)):
        raise ValueError("decode-grid step arrays disagree on length")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(Hkv, n_steps),
        in_specs=[
            pl.BlockSpec((1, 1, 1), _im_qmeta),
            pl.BlockSpec((1, 1, 1), _im_qmeta),
            pl.BlockSpec((1, 1, page_size), _im_page_meta),
            pl.BlockSpec((1, 1, page_size), _im_page_meta),
            pl.BlockSpec((1, 1, n_rep, hd), _im_qgroup),
            pl.BlockSpec((1, 1, page_size, hd), _im_ktile),
            pl.BlockSpec((1, 1, page_size, hd), _im_ktile),
        ],
        out_specs=pl.BlockSpec((1, 1, n_rep, hd), _im_qgroup),
        scratch_shapes=[
            pltpu.VMEM((n_rep, 1), jnp.float32),
            pltpu.VMEM((n_rep, 1), jnp.float32),
            pltpu.VMEM((n_rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, softcap=softcap,
                          window=window, scale=hd ** -0.5,
                          block_skip=block_skip),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, n_rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(req, page, first, last, active,
      q_bits[:, :, None], q_pos[:, :, None], kv_bits[:, None],
      kv_pos[:, None], q.reshape(B, Hkv, n_rep, hd), k_pages, v_pages)
    return out.reshape(B, H, hd)


# ---------------------------------------------------------------------------
# XLA reference / fallback
# ---------------------------------------------------------------------------

def paged_decode_ref(q, k_pages, v_pages, q_bits, q_pos, kv_bits, kv_pos,
                     page_tables, *, softcap: float = 0.0,
                     window: int = 0):
    """Dense-gather decode oracle: materialize each request's resident
    pages via its page-table row (``[B, max_pages]`` int32, padded with
    the null page, whose bits are all zero and mask out) and run the
    reference masked softmax. Same signature family as the kernel but
    addressed by table rows instead of a step list."""
    B, H, hd = q.shape
    P, Hkv, page_size, _ = k_pages.shape
    mp = page_tables.shape[1]
    pt = jnp.asarray(page_tables, jnp.int32)

    def gather(pages):                      # -> [B, mp * page_size, Hkv, hd]
        return jnp.swapaxes(pages[pt], 2, 3).reshape(B, mp * page_size,
                                                     Hkv, hd)

    k, v = gather(k_pages), gather(v_pages)
    bits = kv_bits[pt].reshape(B, mp * page_size)
    pos = kv_pos[pt].reshape(B, mp * page_size)
    out = bam_attention_ref(q[:, None], k, v, q_bits, bits, q_pos, pos,
                            softcap=softcap, window=window)
    return out[:, 0]
