"""BAM flash attention — Pallas TPU kernels (Cornstarch C3, TPU-native).

The paper represents multimodal attention masks as 1-D per-token integer
bitfields (BAM) and materializes [T,T] masks only transiently inside the
attention op (their FlexAttention path). The TPU-native analogue built
here goes further: the mask is evaluated **in-registers inside the
kernel** from the two bitfield vectors — the [T,T] mask never exists in
HBM *or* VMEM, only a [bq,bk] tile of it lives in VREGs per grid step.

Layout / tiling (dense grid):
  grid = (B, H, Tq/bq, Tk/bk), dimension_semantics = (parallel, parallel,
  parallel, arbitrary). Online-softmax running stats (m, l) and the
  output accumulator live in VMEM scratch and persist across the
  arbitrary (k-block) grid dimension; the output tile is written at the
  last k step. bq = bk = 128 matches the MXU systolic tile.

  Mosaic requires the last two dimensions of every block to be
  multiples of (8, 128) or equal to the array's. The wrappers keep the
  public token-major [B, T, H, hd] signature and hand the kernels:
    * head-major tensors [B, H, T, hd] — (bq, hd) tiles;
    * q-side bitfields/positions as columns [B, Tq, 1] — (bq, 1) tiles;
    * k-side bitfields/positions as rows [B, 1, Tk] — (1, bk) tiles;
    * per-row statistics (lse, m, l, delta) as columns [B, H, Tq, 1],
      and 2-D (bq, 1) running-stat scratch.

Block sparsity, two levels (beyond-paper):
  * in-kernel skip (``block_skip``): the kernel reduces the [bq,bk]
    bitfield intersection before touching the MXU; a fully-masked tile
    skips the QK^T matmul via ``pl.when`` — but still pays its grid step
    and K/V copies.
  * grid compaction (``block_map``): a host-side
    ``repro.core.bam.build_block_map`` precomputes the active
    (q-block, k-block) tile list from the block-level bitfield
    reduction; the kernel then runs a flattened grid (B, H, n_steps)
    driven by scalar-prefetch index maps
    (``pltpu.PrefetchScalarGridSpec``), so fully-masked tiles cost
    neither a grid step nor a K/V DMA.

GQA: the K/V BlockSpec index_map folds the q-head -> kv-head mapping
(h // n_rep), so no jnp.repeat of K/V ever materializes.

Forward modes (``return_mode``):
  * ``"out"``       — normalized attention output only;
  * ``"residual"``  — (out, lse[B,H,Tq]); the per-row log-sum-exp is the
    flash-attention residual the fused backward consumes, so backward
    never re-materializes the O(Tq*Tk) logits;
  * ``"stats"``     — unnormalized partials (acc[B,H,Tq,hd] f32,
    m[B,H,Tq], l[B,H,Tq]) for cross-chunk online-softmax combination —
    what the context-parallel ring/allgather bodies consume.

Backward: ``bam_flash_attention_bwd`` is a pair of fused kernels — dQ
over a (B, H, nq, nk) grid and dK/dV over the transposed (B, H, nk, nq)
grid — that recompute the logits tile-by-tile from (q, k, lse), apply
the bitfield mask in-registers, and accumulate gradients in VMEM
scratch. Both honor ``block_skip`` and ``block_map`` exactly like the
forward. The old recompute-through-XLA path survives only as the
``impl="xla"`` fallback in ops.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bam

NEG_INF = -1e30


def _mask_tile(qb, kb, qp, kp, window: int):
    """Query-side bitfields/positions [bq, 1] and key-side [1, bk] (int32
    or uint32) -> [bq, bk] bool. Mirrors repro.core.bam.allowed_mask
    (tested against it); bits never use bit 31, so int32 is exact."""
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


def _tile_mask(qb_ref, kb_ref, qp_ref, kp_ref, window: int):
    """The [bq, bk] mask tile from the (1, bq, 1) query-column blocks
    and the (1, 1, bk) key-row blocks."""
    return _mask_tile(qb_ref[0], kb_ref[0], qp_ref[0], kp_ref[0], window)


def _col(x):
    """[..., T] -> [..., T, 1]: per-token values as a column."""
    return x[..., None]


def _row(x):
    """[..., T] -> [..., 1, T]: per-token values as a row."""
    return x[..., None, :]


def _head_major(x):
    """[B, T, H, hd] <-> [B, H, T, hd]."""
    return jnp.swapaxes(x, 1, 2)


# ---------------------------------------------------------------------------
# Forward kernel bodies (shared by the dense and compacted grids)
# ---------------------------------------------------------------------------

def _fwd_accumulate(allowed, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                    softcap: float, scale: float):
    q = q_ref[0, 0].astype(jnp.float32)                 # [bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)                 # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(allowed, s, NEG_INF)
    m_prev = m_scr[...]                                 # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(allowed, p, 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + \
        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _fwd_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _fwd_finish(mode, out_refs, m_scr, l_scr, acc_scr):
    m = m_scr[...]                                      # [bq, 1]
    l = l_scr[...]
    if mode == "stats":
        acc_ref, m_ref, l_ref = out_refs
        acc_ref[0, 0] = acc_scr[...].astype(acc_ref.dtype)
        m_ref[0, 0] = m
        l_ref[0, 0] = l
        return
    out = acc_scr[...] / jnp.maximum(l, 1e-30)
    out = jnp.where(l > 0, out, 0.0)
    if mode == "residual":
        o_ref, lse_ref = out_refs
        lse_ref[0, 0] = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                                  NEG_INF)
    else:
        (o_ref,) = out_refs
    o_ref[0, 0] = out.astype(o_ref.dtype)


def _bam_fwd_kernel(qb_ref, kb_ref, qp_ref, kp_ref,     # bitfield meta
                    q_ref, k_ref, v_ref,                # tensors
                    *refs,                              # outputs + scratch
                    softcap: float, window: int, nk: int, scale: float,
                    block_skip: bool, mode: str):
    out_refs, (m_scr, l_scr, acc_scr) = refs[:-3], refs[-3:]
    ki = pl.program_id(3)

    pl.when(ki == 0)(lambda: _fwd_init(m_scr, l_scr, acc_scr))
    allowed = _tile_mask(qb_ref, kb_ref, qp_ref, kp_ref, window)

    def compute():
        _fwd_accumulate(allowed, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                        softcap, scale)

    if block_skip:
        # block sparsity: a fully-masked tile never touches the MXU
        pl.when(jnp.any(allowed))(compute)
    else:
        compute()

    pl.when(ki == nk - 1)(
        lambda: _fwd_finish(mode, out_refs, m_scr, l_scr, acc_scr))


def _bam_fwd_kernel_sparse(qblk_ref, kblk_ref, first_ref, last_ref,
                           active_ref,                  # scalar prefetch
                           qb_ref, kb_ref, qp_ref, kp_ref,
                           q_ref, k_ref, v_ref,
                           *refs,
                           softcap: float, window: int, scale: float,
                           block_skip: bool, mode: str):
    """Grid-compacted forward: grid (B, H, n_steps); the active-tile list
    (host-precomputed) drives the index maps, init and flush."""
    out_refs, (m_scr, l_scr, acc_scr) = refs[:-3], refs[-3:]
    t = pl.program_id(2)

    pl.when(first_ref[t] == 1)(lambda: _fwd_init(m_scr, l_scr, acc_scr))
    allowed = _tile_mask(qb_ref, kb_ref, qp_ref, kp_ref, window)
    is_active = active_ref[t] == 1

    def compute():
        _fwd_accumulate(allowed, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                        softcap, scale)

    if block_skip:
        pl.when(is_active & jnp.any(allowed))(compute)
    else:
        pl.when(is_active)(compute)

    pl.when(last_ref[t] == 1)(
        lambda: _fwd_finish(mode, out_refs, m_scr, l_scr, acc_scr))


# ---------------------------------------------------------------------------
# Backward kernel bodies
# ---------------------------------------------------------------------------

def _recompute_p_ds(allowed, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    softcap: float, scale: float):
    """Recompute the probability tile from (q, k, lse) and form
    dS = P * (dP - delta), with the softcap chain rule folded in.
    Returns (p [bq,bk], ds [bq,bk], q, k, do) all f32."""
    q = q_ref[0, 0].astype(jnp.float32)                 # [bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)                 # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                 # [bq, 1]
    delta = delta_ref[0, 0]                             # [bq, 1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    p = jnp.where(allowed, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if softcap:
        ds = ds * (1.0 - (s / softcap) ** 2)
    return p, ds, q, k, do


def _dq_accumulate(allowed, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_scr, softcap: float, scale: float):
    _, ds, _, k, _ = _recompute_p_ds(
        allowed, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
        softcap, scale)
    dq_scr[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


def _bam_bwd_dq_kernel(qb_ref, kb_ref, qp_ref, kp_ref,
                       q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dq_scr, *, softcap: float, window: int,
                       nk: int, scale: float, block_skip: bool):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    allowed = _tile_mask(qb_ref, kb_ref, qp_ref, kp_ref, window)

    def compute():
        _dq_accumulate(allowed, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dq_scr, softcap, scale)

    if block_skip:
        pl.when(jnp.any(allowed))(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bam_bwd_dq_kernel_sparse(qblk_ref, kblk_ref, first_ref, last_ref,
                              active_ref,
                              qb_ref, kb_ref, qp_ref, kp_ref,
                              q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, dq_ref, dq_scr, *,
                              softcap: float, window: int, scale: float,
                              block_skip: bool):
    t = pl.program_id(2)

    @pl.when(first_ref[t] == 1)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    allowed = _tile_mask(qb_ref, kb_ref, qp_ref, kp_ref, window)
    is_active = active_ref[t] == 1

    def compute():
        _dq_accumulate(allowed, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dq_scr, softcap, scale)

    if block_skip:
        pl.when(is_active & jnp.any(allowed))(compute)
    else:
        pl.when(is_active)(compute)

    @pl.when(last_ref[t] == 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_accumulate(allowed, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_scr, dv_scr, softcap: float, scale: float):
    p, ds, q, _, do = _recompute_p_ds(
        allowed, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
        softcap, scale)
    dv_scr[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dk_scr[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


def _bam_bwd_dkv_kernel(qb_ref, kb_ref, qp_ref, kp_ref,
                        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_scr, dv_scr, *,
                        softcap: float, window: int, nq: int, scale: float,
                        block_skip: bool):
    """Transposed grid (B, H, nk, nq): the arbitrary dimension iterates
    q blocks; dK/dV accumulate per k block."""
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    allowed = _tile_mask(qb_ref, kb_ref, qp_ref, kp_ref, window)

    def compute():
        _dkv_accumulate(allowed, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dk_scr, dv_scr, softcap, scale)

    if block_skip:
        pl.when(jnp.any(allowed))(compute)
    else:
        compute()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bam_bwd_dkv_kernel_sparse(qblk_ref, kblk_ref, first_ref, last_ref,
                               active_ref,
                               qb_ref, kb_ref, qp_ref, kp_ref,
                               q_ref, k_ref, v_ref, do_ref, lse_ref,
                               delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                               softcap: float, window: int, scale: float,
                               block_skip: bool):
    t = pl.program_id(2)

    @pl.when(first_ref[t] == 1)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    allowed = _tile_mask(qb_ref, kb_ref, qp_ref, kp_ref, window)
    is_active = active_ref[t] == 1

    def compute():
        _dkv_accumulate(allowed, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dk_scr, dv_scr, softcap, scale)

    if block_skip:
        pl.when(is_active & jnp.any(allowed))(compute)
    else:
        pl.when(is_active)(compute)

    @pl.when(last_ref[t] == 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _check_block_map(block_map, block_q, block_k, nq, nk, window):
    assert block_map.block_q == block_q and block_map.block_k == block_k, \
        ("block_map was built for different tile sizes",
         (block_map.block_q, block_map.block_k), (block_q, block_k))
    assert block_map.nq == nq and block_map.nk == nk, \
        ("block_map grid does not match the padded sequence",
         (block_map.nq, block_map.nk), (nq, nk))
    assert block_map.window == window, \
        ("block_map was built for a different sliding window — tiles "
         "valid under this window may have been pruned",
         block_map.window, window)


def _prefetch_arrays(block_map, major):
    return tuple(jnp.asarray(a) for a in block_map.arrays(major))


def _dense_index_maps(n_rep: int, q_major: bool):
    """Index maps for the dense 4-D grids: (b, h, iq, ik) for the q-major
    forward/dQ grids, (b, h, ik, iq) for the k-major dK/dV grid."""

    def order(i, j):
        return (i, j) if q_major else (j, i)

    def qm(b, h, i, j):
        return (b, order(i, j)[0], 0)

    def km(b, h, i, j):
        return (b, 0, order(i, j)[1])

    def qtile(b, h, i, j):
        return (b, h, order(i, j)[0], 0)

    def ktile(b, h, i, j):
        return (b, h // n_rep, order(i, j)[1], 0)

    def ktile_full(b, h, i, j):
        return (b, h, order(i, j)[1], 0)

    return qm, km, qtile, ktile, ktile_full


def _sparse_index_maps(n_rep: int):
    """Index maps for the compacted (B, H, n_steps) grids. All receive
    (b, h, t, *scalar_prefetch_refs); the step arrays address the
    blocks. Shared by forward and backward so the prefetch layout can
    only change in one place."""

    def qm(b, h, t, qblk, kblk, first, last, active):
        return (b, qblk[t], 0)

    def km(b, h, t, qblk, kblk, first, last, active):
        return (b, 0, kblk[t])

    def qtile(b, h, t, qblk, kblk, first, last, active):
        return (b, h, qblk[t], 0)

    def ktile(b, h, t, qblk, kblk, first, last, active):
        return (b, h // n_rep, kblk[t], 0)

    def ktile_full(b, h, t, qblk, kblk, first, last, active):
        return (b, h, kblk[t], 0)

    return qm, km, qtile, ktile, ktile_full


def _in_specs(maps, block_q, block_k, hd, bwd: bool):
    """BlockSpecs of the operands (q-meta x2, k-meta x2, q, k, v and, for
    the backward, dO, lse, delta) in call order. Per-row statistics are
    (bq, 1) columns of [B, H, Tq, 1], addressed like the q tiles."""
    qm, km, qtile, ktile, _ = maps
    specs = [pl.BlockSpec((1, block_q, 1), qm),
             pl.BlockSpec((1, 1, block_k), km),
             pl.BlockSpec((1, block_q, 1), qm),
             pl.BlockSpec((1, 1, block_k), km),
             pl.BlockSpec((1, 1, block_q, hd), qtile),
             pl.BlockSpec((1, 1, block_k, hd), ktile),
             pl.BlockSpec((1, 1, block_k, hd), ktile)]
    if bwd:
        specs += [pl.BlockSpec((1, 1, block_q, hd), qtile),
                  pl.BlockSpec((1, 1, block_q, 1), qtile),
                  pl.BlockSpec((1, 1, block_q, 1), qtile)]
    return specs


def _meta_operands(q_bits, kv_bits, q_pos, kv_pos):
    return (_col(q_bits), _row(kv_bits), _col(q_pos), _row(kv_pos))


def bam_flash_attention(q, k, v, q_bits, kv_bits, q_pos, kv_pos, *,
                        softcap: float = 0.0, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        block_skip: bool = True,
                        interpret: bool = False,
                        return_mode: str = "out",
                        block_map=None):
    """Pallas BAM attention forward. Shapes as in ref.py; Tq % block_q
    == 0 and Tk % block_k == 0 (ops.py pads with bits=0, pos=-1).

    return_mode: "out" -> out [B,Tq,H,hd] | "residual" -> (out,
    lse [B,H,Tq]) | "stats" -> (acc [B,H,Tq,hd] f32, m, l [B,H,Tq]).
    block_map: optional ``repro.core.bam.BlockMask`` — compacted grid.
    """
    assert return_mode in ("out", "residual", "stats"), return_mode
    B, Tq, H, hd = q.shape
    _, Tk, Hkv, _ = k.shape
    assert H % Hkv == 0
    n_rep = H // Hkv
    assert Tq % block_q == 0 and Tk % block_k == 0, (Tq, Tk)
    nq, nk = Tq // block_q, Tk // block_k

    row = jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32)
    out_shapes = {
        "out": (jax.ShapeDtypeStruct((B, H, Tq, hd), q.dtype),),
        "residual": (jax.ShapeDtypeStruct((B, H, Tq, hd), q.dtype), row),
        "stats": (jax.ShapeDtypeStruct((B, H, Tq, hd), jnp.float32),
                  row, row),
    }[return_mode]
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, hd), jnp.float32),
    ]
    common = dict(softcap=softcap, window=window, scale=hd ** -0.5,
                  block_skip=block_skip, mode=return_mode)
    operands = (*_meta_operands(q_bits, kv_bits, q_pos, kv_pos),
                _head_major(q), _head_major(k), _head_major(v))

    if block_map is None:
        maps = _dense_index_maps(n_rep, q_major=True)
        kernel = functools.partial(_bam_fwd_kernel, nk=nk, **common)
        prefetch = ()
        grid = (B, H, nq, nk)
        semantics = ("parallel", "parallel", "parallel", "arbitrary")
    else:
        _check_block_map(block_map, block_q, block_k, nq, nk, window)
        maps = _sparse_index_maps(n_rep)
        kernel = functools.partial(_bam_fwd_kernel_sparse, **common)
        prefetch = _prefetch_arrays(block_map, "q")
        grid = (B, H, block_map.n_steps)
        semantics = ("parallel", "parallel", "arbitrary")
    qtile = maps[2]
    out_specs = [pl.BlockSpec((1, 1, block_q, hd), qtile)] + \
        [pl.BlockSpec((1, 1, block_q, 1), qtile)] * (len(out_shapes) - 1)
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=_in_specs(maps, block_q, block_k, hd, bwd=False),
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=list(out_shapes),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )(*prefetch, *operands)

    if return_mode == "stats":
        acc, m, l = outs
        return acc, m[..., 0], l[..., 0]
    out = _head_major(outs[0])
    if return_mode == "residual":
        return out, outs[1][..., 0]
    return out


def bam_flash_attention_bwd(q, k, v, out, do, lse, q_bits, kv_bits, q_pos,
                            kv_pos, *, softcap: float = 0.0, window: int = 0,
                            block_q: int = 128, block_k: int = 128,
                            block_skip: bool = True,
                            interpret: bool = False,
                            block_map=None):
    """Fused BAM flash-attention backward: dQ, dK, dV from the saved
    (out, lse) residuals — the O(Tq*Tk) logits are recomputed tile by
    tile in VMEM, never materialized. dK/dV are returned GQA-reduced to
    [B, Tk, Hkv, hd]."""
    B, Tq, H, hd = q.shape
    _, Tk, Hkv, _ = k.shape
    n_rep = H // Hkv
    assert Tq % block_q == 0 and Tk % block_k == 0, (Tq, Tk)
    nq, nk = Tq // block_q, Tk // block_k
    scale = hd ** -0.5

    # delta_i = sum_d dO_i·O_i — the rowwise correction term (O(T·hd))
    delta = jnp.einsum("bqhd,bqhd->bhq", out.astype(jnp.float32),
                       do.astype(jnp.float32))

    common = dict(softcap=softcap, window=window, scale=scale,
                  block_skip=block_skip)
    operands = (*_meta_operands(q_bits, kv_bits, q_pos, kv_pos),
                _head_major(q), _head_major(k), _head_major(v),
                _head_major(do), _col(lse.astype(jnp.float32)), _col(delta))
    dk_shape = jax.ShapeDtypeStruct((B, H, Tk, hd), jnp.float32)

    if block_map is None:
        q_maps = _dense_index_maps(n_rep, q_major=True)
        k_maps = _dense_index_maps(n_rep, q_major=False)
        dq_kernel = functools.partial(_bam_bwd_dq_kernel, nk=nk, **common)
        dkv_kernel = functools.partial(_bam_bwd_dkv_kernel, nq=nq, **common)
        q_prefetch = k_prefetch = ()
        q_grid, k_grid = (B, H, nq, nk), (B, H, nk, nq)
        semantics = ("parallel", "parallel", "parallel", "arbitrary")
    else:
        _check_block_map(block_map, block_q, block_k, nq, nk, window)
        q_maps = k_maps = _sparse_index_maps(n_rep)
        dq_kernel = functools.partial(_bam_bwd_dq_kernel_sparse, **common)
        dkv_kernel = functools.partial(_bam_bwd_dkv_kernel_sparse, **common)
        q_prefetch = _prefetch_arrays(block_map, "q")
        k_prefetch = _prefetch_arrays(block_map, "k")
        q_grid = (B, H, block_map.n_steps)
        k_grid = (B, H, len(block_map.k_steps))
        semantics = ("parallel", "parallel", "arbitrary")
    params = pltpu.CompilerParams(dimension_semantics=semantics)

    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(q_prefetch),
            grid=q_grid,
            in_specs=_in_specs(q_maps, block_q, block_k, hd, bwd=True),
            out_specs=pl.BlockSpec((1, 1, block_q, hd), q_maps[2]),
            scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, hd), q.dtype),
        compiler_params=params,
        interpret=interpret,
    )(*q_prefetch, *operands)

    ktile_full = k_maps[4]
    dk_h, dv_h = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(k_prefetch),
            grid=k_grid,
            in_specs=_in_specs(k_maps, block_q, block_k, hd, bwd=True),
            out_specs=[pl.BlockSpec((1, 1, block_k, hd), ktile_full),
                       pl.BlockSpec((1, 1, block_k, hd), ktile_full)],
            scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                            pltpu.VMEM((block_k, hd), jnp.float32)],
        ),
        out_shape=[dk_shape, dk_shape],
        compiler_params=params,
        interpret=interpret,
    )(*k_prefetch, *operands)

    # GQA: fold q-head grads back onto shared KV heads
    dk_h = dk_h.reshape(B, Hkv, n_rep, Tk, hd).sum(axis=2)
    dv_h = dv_h.reshape(B, Hkv, n_rep, Tk, hd).sum(axis=2)
    return (_head_major(dq), _head_major(dk_h).astype(k.dtype),
            _head_major(dv_h).astype(v.dtype))
