"""jit'd public wrapper for BAM attention.

Dispatch:
  impl="xla"           — fused-XLA reference math (CPU backends and
                         GSPMD dry-runs)
  impl="bam_kernel"    — Pallas TPU kernels (real hardware; what the
                         models pick on TPU, models.layers
                         .resolve_attn_impl)
  impl="bam_interpret" — Pallas kernel bodies interpreted on CPU
                         (correctness validation; what tests sweep)

Handles GQA, padding to block multiples (pad tokens get bits=0 ⇒ never
attend/attended; pad positions get -1 so debug dumps and workload stats
never alias pad tokens onto real position 0), and the custom_vjp.

Backward: for the kernel impls the forward saves (out, lse) as flash
residuals and the backward runs the fused Pallas dQ / dK/dV kernels
(``bam_flash_attention_bwd``) — no O(Tq·Tk) intermediate is ever
traced. Only impl="xla" still recomputes through the reference path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.bam_attention import (NEG_INF, bam_flash_attention,
                                         bam_flash_attention_bwd)
from repro.kernels.ref import bam_attention_ref


def _pad_axis(x, to: int, axis: int, value=0):
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg, constant_values=value)


def _pad_all(q, k, v, q_bits, kv_bits, q_pos, kv_pos, block_q, block_k):
    """Pad token axes to block multiples. bits pad with 0 (masked);
    positions pad with -1 (NOT 0 — padding onto a real position makes
    workload stats and debug dumps lie, even though bits=0 already
    masks the tokens)."""
    Tq, Tk = q.shape[1], k.shape[1]
    Tq_p = -(-Tq // block_q) * block_q
    Tk_p = -(-Tk // block_k) * block_k
    return (_pad_axis(q, Tq_p, 1), _pad_axis(k, Tk_p, 1),
            _pad_axis(v, Tk_p, 1),
            _pad_axis(q_bits, Tq_p, 1), _pad_axis(kv_bits, Tk_p, 1),
            _pad_axis(q_pos, Tq_p, 1, value=-1),
            _pad_axis(kv_pos, Tk_p, 1, value=-1))


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _bam_attention(q, k, v, q_bits, kv_bits, q_pos, kv_pos,
                   softcap, window, impl, block_q, block_k, block_map):
    out, _ = _fwd_impl(q, k, v, q_bits, kv_bits, q_pos, kv_pos,
                       softcap, window, impl, block_q, block_k, block_map)
    return out


def _fwd_impl(q, k, v, q_bits, kv_bits, q_pos, kv_pos,
              softcap, window, impl, block_q, block_k, block_map):
    """Returns (out [B,Tq,H,hd], lse [B,H,Tq] or None for impl=xla)."""
    if impl == "xla":
        return bam_attention_ref(q, k, v, q_bits, kv_bits, q_pos, kv_pos,
                                 softcap=softcap, window=window), None
    Tq = q.shape[1]
    padded = _pad_all(q, k, v, q_bits, kv_bits, q_pos, kv_pos,
                      block_q, block_k)
    out, lse = bam_flash_attention(
        padded[0], padded[1], padded[2], padded[3], padded[4],
        padded[5], padded[6], softcap=softcap, window=window,
        block_q=block_q, block_k=block_k, return_mode="residual",
        block_map=block_map, interpret=(impl == "bam_interpret"))
    return out[:, :Tq], lse[:, :, :Tq]


def _fwd_vjp(q, k, v, q_bits, kv_bits, q_pos, kv_pos,
             softcap, window, impl, block_q, block_k, block_map):
    out, lse = _fwd_impl(q, k, v, q_bits, kv_bits, q_pos, kv_pos,
                         softcap, window, impl, block_q, block_k, block_map)
    return out, (q, k, v, q_bits, kv_bits, q_pos, kv_pos, out, lse)


def _bwd_vjp(softcap, window, impl, block_q, block_k, block_map, res, g):
    q, k, v, q_bits, kv_bits, q_pos, kv_pos, out, lse = res

    if impl == "xla":
        # XLA fallback: recompute through the reference path and let
        # the compiler derive the VJP (materializes the [Tq,Tk] mask).
        def f(q, k, v):
            return bam_attention_ref(q, k, v, q_bits, kv_bits, q_pos,
                                     kv_pos, softcap=softcap, window=window)

        _, vjp = jax.vjp(f, q, k, v)
        dq, dk, dv = vjp(g)
        return dq, dk, dv, None, None, None, None

    # Fused kernel backward from the (out, lse) residuals.
    dq, dk, dv = bam_attention_chunk_bwd(
        q, k, v, out, g, lse, q_bits, kv_bits, q_pos, kv_pos,
        softcap=softcap, window=window, impl=impl, block_q=block_q,
        block_k=block_k, block_map=block_map)
    return dq, dk, dv, None, None, None, None


def bam_attention_chunk_bwd(q, k, v, out, g, lse, q_bits, kv_bits, q_pos,
                            kv_pos, *, softcap: float = 0.0,
                            window: int = 0, impl: str = "bam_interpret",
                            block_q: int = 128, block_k: int = 128,
                            block_map=None):
    """Fused flash backward from (out, lse) residuals — the building
    block both the single-device ``bam_attention`` VJP and the
    context-parallel chunk backwards share.

    The combining-aware property: ``(out, lse)`` need not come from
    attention over THIS ``k``/``v`` chunk alone — pass the cross-chunk
    COMBINED output and log-sum-exp (CP: derived from the merged
    ``(m, l)`` stats) and the result is this chunk's exact contribution
    to the global-softmax gradients: ``dq`` sums over chunks; ``dk``/
    ``dv`` (GQA-folded to [B, Tk, Hkv, hd]) are complete per chunk. Runs
    the fused Pallas dQ / dK-dV kernels; no O(Tq·Tk) intermediate is
    ever traced. Handles non-block-multiple lengths by bits=0 / pos=-1
    padding, like the forward."""
    assert impl in ("bam_kernel", "bam_interpret"), impl
    Tq, Tk = q.shape[1], k.shape[1]
    qp, kp_, vp, qbp, kbp, qpp, kpp = _pad_all(
        q, k, v, q_bits, kv_bits, q_pos, kv_pos, block_q, block_k)
    Tq_p = qp.shape[1]
    outp = _pad_axis(out, Tq_p, 1)
    gp = _pad_axis(g, Tq_p, 1)
    # padded q rows: lse = NEG_INF reproduces the kernel's own padding
    lsep = _pad_axis(lse, Tq_p, 2, value=NEG_INF)
    dq, dk, dv = bam_flash_attention_bwd(
        qp, kp_, vp, outp, gp, lsep, qbp, kbp, qpp, kpp,
        softcap=softcap, window=window, block_q=block_q, block_k=block_k,
        block_map=block_map, interpret=(impl == "bam_interpret"))
    return dq[:, :Tq], dk[:, :Tk], dv[:, :Tk]


_bam_attention.defvjp(_fwd_vjp, _bwd_vjp)


def _default_pos(B, T):
    return jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))


def bam_attention(q, k, v, q_bits, kv_bits, q_pos=None, kv_pos=None, *,
                  softcap: float = 0.0, window: int = 0,
                  impl: str = "xla", block_q: int = 128,
                  block_k: int = 128, block_map=None):
    """Public BAM attention. q: [B,Tq,H,hd]; k/v: [B,Tk,Hkv,hd];
    bits uint32 [B,T*]; pos default = iota.

    block_map: optional host-precomputed ``repro.core.bam.BlockMask``
    (grid compaction — active tiles only). Static: a new map retraces.
    """
    B, Tq = q.shape[:2]
    Tk = k.shape[1]
    if q_pos is None:
        q_pos = _default_pos(B, Tq)
    if kv_pos is None:
        kv_pos = _default_pos(B, Tk)
    return _bam_attention(q, k, v, q_bits, kv_bits, q_pos, kv_pos,
                          float(softcap), int(window), impl,
                          int(block_q), int(block_k), block_map)


def auto_block(T: int, cap: int = 128) -> int:
    """Tile size for short sequences: next multiple of 16, capped."""
    return min(cap, -(-T // 16) * 16)


def flash_blocks(Tq: int, Tk: int) -> tuple:
    """(block_q, block_k) of the training path's kernels, from the
    lengths alone: for each, the largest multiple of 128 up to 1024 that
    splits the length into at least two tiles and pads it by under an
    eighth; short sequences take ``auto_block``. On a v5e this picks
    1024 at 4096 tokens and 896 at 1600 (padded to 1792), the fastest
    square tiles of a sweep at both lengths: per-step overhead and the
    per-element softmax work outweigh the masked elements a large tile
    computes."""
    def pick(T):
        for b in range(1024, 127, -128):
            n = -(-T // b)
            if n >= 2 and (n * b - T) * 8 < T:
                return b
        return auto_block(T)
    return pick(Tq), pick(Tk)


def bam_attention_stats(q, k, v, q_bits, kv_bits, q_pos=None, kv_pos=None, *,
                        softcap: float = 0.0, window: int = 0,
                        impl: str = "bam_interpret", block_q: int = 128,
                        block_k: int = 128, block_map=None):
    """Unnormalized flash-attention partials for cross-chunk combination
    (context parallelism): returns (acc [B,H,Tq,hd] f32 = sum p·V,
    m [B,H,Tq], l [B,H,Tq]) with the bitfield mask evaluated in-kernel —
    no [B,H,Tq,Tk] logits in HBM. This op is a forward building block
    with no VJP of its own: the combine happens OUTSIDE (the CP bodies),
    so gradients are defined there — ``core.context_parallel``'s
    combining-aware custom_vjps derive (out, lse) from the merged
    (m, l) and drive ``bam_attention_chunk_bwd`` per chunk. Don't
    ``jax.grad`` through this op directly; grad through
    ``cp_attention`` (or ``bam_attention`` single-device) instead.
    """
    assert impl in ("bam_kernel", "bam_interpret"), impl
    B, Tq = q.shape[:2]
    Tk = k.shape[1]
    if q_pos is None:
        q_pos = _default_pos(B, Tq)
    if kv_pos is None:
        kv_pos = _default_pos(B, Tk)
    padded = _pad_all(q, k, v, q_bits, kv_bits, q_pos, kv_pos,
                      block_q, block_k)
    acc, m, l = bam_flash_attention(
        padded[0], padded[1], padded[2], padded[3], padded[4],
        padded[5], padded[6], softcap=softcap, window=window,
        block_q=block_q, block_k=block_k, return_mode="stats",
        block_map=block_map, interpret=(impl == "bam_interpret"))
    return acc[:, :, :Tq], m[:, :, :Tq], l[:, :, :Tq]
