"""BAM flash attention — Pallas TPU kernels (Cornstarch C3, TPU-native).

The paper represents multimodal attention masks as 1-D per-token integer
bitfields (BAM) and materializes [T,T] masks only transiently inside the
attention op (their FlexAttention path). The TPU-native analogue built
here goes further: the mask is evaluated **in-registers inside the
kernel** from per-token vectors — the [T,T] mask never exists in HBM
*or* VMEM, only a [bq,bk] tile of it lives in VREGs per grid step.

Mask words: the wrappers rewrite each token's bitfield and position
into three or four int32 words (``_mask_words``) from which a tile's
mask takes seven elementwise operations; the rewrite is exact for every
bitfield (tested against ``repro.core.bam.allowed_mask``).

Layout / tiling (dense grid):
  grid = (B, H, Tq/bq, Tk/bk), dimension_semantics = (parallel, parallel,
  parallel, arbitrary). Online-softmax running stats (m, l) and the
  output accumulator live in VMEM scratch and persist across the
  arbitrary (k-block) grid dimension; the output tile is written at the
  last k step. The wrappers take any (8, 128)-aligned tiles;
  ``ops.flash_blocks`` picks them from the shape for the training path.

  Mosaic requires the last two dimensions of every block to be
  multiples of (8, 128) or equal to the array's. The wrappers keep the
  public token-major [B, T, H, hd] signature and hand the kernels:
    * head-major tensors [B, H, T, hd] — (bq, hd) tiles;
    * in the forward and dQ kernels, query-side words as columns
      [B, Tq, 1] — (bq, 1) tiles — and key-side words as rows [B, 1, Tk]
      — (1, bk) tiles; per-row statistics (lse, delta) as columns
      [B, H, Tq, 1], and 2-D (bq, 1) running-stat scratch;
    * in the dK/dV kernel, which works on transposed [bk, bq] tiles
      (Sᵀ = K Qᵀ, so that no matmul takes a transposed operand),
      key-side words as columns, query-side words and statistics as
      rows [B, 1, Tq] / [B, H, 1, Tq].

Precision: q, k, v, dO — and the probabilities P and dS where they are
matmul operands — enter the MXU in the inputs' dtype (bf16 in
training) with f32 accumulation; the scale, the mask, the exponentials,
the online-softmax statistics (m, l, lse, delta) and the VMEM
accumulators are f32. Float32 inputs keep float32 operands throughout.

Block sparsity, two levels (beyond-paper):
  * tile classes (dense grid, ``block_skip``): the wrapper classifies
    every tile on the device from the bitfields
    (``repro.core.bam.tile_classes``: empty, partial or full, exact for
    every bitfield) and hands the classes in by scalar prefetch. An
    empty tile skips the MXU, a full one skips the per-element mask,
    and only a partial tile evaluates the mask in-registers. An empty
    tile's inner operands point at the nearest tile of its row that
    computes, so it costs its grid step but no copy.
  * grid compaction (``block_map``): a host-side
    ``repro.core.bam.build_block_map`` precomputes the active
    (q-block, k-block) tile list from the block-level bitfield
    reduction; the kernel then runs a flattened grid (B, H, n_steps)
    driven by scalar-prefetch index maps
    (``pltpu.PrefetchScalarGridSpec``), so fully-masked tiles cost
    neither a grid step nor a K/V DMA.

GQA: the K/V BlockSpec index_map folds the q-head -> kv-head mapping
(h // n_rep), so no jnp.repeat of K/V ever materializes; the dense
dK/dV grid runs over KV heads and accumulates the n_rep query heads of
each in VMEM.

Forward modes (``return_mode``):
  * ``"out"``       — normalized attention output only;
  * ``"residual"``  — (out, lse[B,H,Tq]); the per-row log-sum-exp is the
    flash-attention residual the fused backward consumes, so backward
    never re-materializes the O(Tq*Tk) logits;
  * ``"stats"``     — unnormalized partials (acc[B,H,Tq,hd] f32,
    m[B,H,Tq], l[B,H,Tq]) for cross-chunk online-softmax combination —
    what the context-parallel ring/allgather bodies consume.

Backward: ``bam_flash_attention_bwd`` is a pair of fused kernels — dQ
over a (B, H, nq, nk) grid and dK/dV over the transposed
(B, Hkv, nk, n_rep * nq) grid — that recompute the logits tile-by-tile
from (q, k, lse), apply the mask in-registers, and accumulate gradients
in VMEM scratch. Both honor ``block_skip`` and ``block_map`` exactly
like the forward.

Device traces name the kernels ``bam_fwd``, ``bam_bwd_dq`` and
``bam_bwd_dkv``. The old recompute-through-XLA path survives only as the
``impl="xla"`` fallback in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bam

NEG_INF = -1e30
_INT_MAX, _INT_MIN = 2 ** 31 - 1, -2 ** 31


def _mask_words(q_bits, kv_bits, q_pos, kv_pos, window: int):
    """Rewrite ``bam.allowed_mask`` as per-token int32 words [B, T]:

        allowed(i, j) = d_i == d_j  and  e_i & o_j != 0
                        and  p_j <= hi_i  [and  lo_i < p_j]

    with d the instance id; o_j = 1 << m_j for a key that is not
    padding and whose modality an attends set can name, else 0; e_i the
    attends set A_i of a text query, A_i & (1 << m_i) of a modality
    query (which attends its own modality only), 0 for padding;
    hi_i = p_i for a text query (causal), INT_MAX for a modality query;
    and, with a sliding window, lo_i = p_i - window for a text query,
    INT_MIN for a modality query. Exact for every bitfield and every
    position above INT_MIN. Returns (query words, key words)."""
    qb = q_bits.astype(jnp.uint32)
    kb = kv_bits.astype(jnp.uint32)

    def onehot(m):
        nameable = m < bam.ATTEND_BITS
        return jnp.where(nameable, jnp.uint32(1) << jnp.where(nameable, m, 0),
                         0)

    q_text = bam.own_modality(qb) == bam.TEXT
    a = bam.attends_set(qb)
    e = jnp.where(q_text, a, a & onehot(bam.own_modality(qb)))
    q_words = [bam.instance_id(qb), jnp.where(qb != 0, e, 0),
               jnp.where(q_text, q_pos, _INT_MAX)]
    if window:
        q_words.append(jnp.where(q_text, q_pos - window, _INT_MIN))
    k_words = [bam.instance_id(kb),
               jnp.where(kb != 0, onehot(bam.own_modality(kb)), 0), kv_pos]
    return ([w.astype(jnp.int32) for w in q_words],
            [w.astype(jnp.int32) for w in k_words])


def _words_mask(q_refs, k_refs):
    """The mask tile from the words' blocks: query words as (bq, 1)
    columns and key words as (1, bk) rows give [bq, bk]; query rows and
    key columns give its transpose [bk, bq]."""
    qd, qe, qhi, *qlo = (r[0] for r in q_refs)
    kd, ko, kp = (r[0] for r in k_refs)
    ok = (qd == kd) & ((qe & ko) != 0) & (kp <= qhi)
    for lo in qlo:
        ok &= lo < kp
    return ok


def _col(x):
    """[..., T] -> [..., T, 1]: per-token values as a column."""
    return x[..., None]


def _row(x):
    """[..., T] -> [..., 1, T]: per-token values as a row."""
    return x[..., None, :]


def _head_major(x):
    """[B, T, H, hd] <-> [B, H, T, hd]."""
    return jnp.swapaxes(x, 1, 2)


def _dot(a, b, contract):
    """MXU matmul of operands in their own dtype, accumulated in f32."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b


# ---------------------------------------------------------------------------
# Grids: what the kernel bodies and index maps need to know of the grid
# ---------------------------------------------------------------------------

def _tile_word(tiles_ref, b, outer, inner, n_outer: int, n_inner: int):
    """Tile (outer, inner) of batch row ``b`` in a dense grid's flattened
    [B, n_outer, n_inner] scalar prefetch (SMEM)."""
    return tiles_ref[(b * n_outer + outer) * n_inner + inner]


def _pack_tiles(cls, block_skip: bool):
    """A dense grid's scalar prefetch from the tile classes
    cls [B, n_outer, n_inner] (inner: the grid's arbitrary axis): each
    tile's word is ``fetch << 2 | class``. ``fetch`` is the inner block
    whose operands the tile's step loads: its own, or for a skipped
    empty tile the nearest tile of its row that computes, so that the
    pipeline issues no copy for it. Flattened for SMEM."""
    n = cls.shape[-1]
    idx = jnp.arange(n, dtype=jnp.int32)
    fetch = jnp.broadcast_to(idx, cls.shape)
    if block_skip:
        active = cls != bam.TILE_EMPTY
        before = lax.cummax(jnp.where(active, idx, -1), axis=2)
        after = lax.cummin(jnp.where(active, idx, n), axis=2, reverse=True)
        fetch = jnp.where(before >= 0, before, jnp.where(after < n, after, 0))
    return ((fetch << 2) | cls).reshape(-1)


def _dispatch(cls, compute, mask, block_skip: bool):
    """Run ``compute(allowed)`` on a dense-grid tile by its class: a full
    tile passes no mask, a partial one the in-register mask; an empty
    one is skipped (masked like a partial one without ``block_skip``)."""
    pl.when(cls == bam.TILE_FULL)(lambda: compute(None))
    partial = (cls == bam.TILE_PARTIAL) if block_skip else \
        (cls != bam.TILE_FULL)
    pl.when(partial)(lambda: compute(mask()))


class _Dense:
    """The full grid. q-major (forward, dQ): (B, H, nq, nk). k-major
    (dK/dV): (B, Hkv, nk, n_rep * nq), whose step j walks q block
    j % nq of query head g * n_rep + j // nq — the GQA fold. One
    scalar-prefetch array of packed tile words (``_pack_tiles``)."""

    n_prefetch = 1

    def __init__(self, B, heads, nq, nk, n_rep, q_major, block_skip):
        self.nq, self.nk, self.n_rep = nq, nk, n_rep
        self.q_major, self.block_skip = q_major, block_skip
        inner = nk if q_major else n_rep * nq
        self.shape = (B, heads, nq if q_major else nk, inner)
        self.semantics = ("parallel", "parallel", "parallel", "arbitrary")
        self.folds_gqa = not q_major

    def prefetch(self, q_bits, kv_bits, q_pos, kv_pos, block_q, block_k,
                 window):
        cls = bam.tile_classes(q_bits, kv_bits, q_pos, kv_pos, block_q,
                               block_k, window)
        if not self.q_major:
            cls = jnp.swapaxes(cls, 1, 2)
        return (_pack_tiles(cls, self.block_skip),)

    def _word(self, tiles, b, i, j):
        if self.q_major:
            return _tile_word(tiles, b, i, j, self.nq, self.nk)
        return _tile_word(tiles, b, i, j % self.nq, self.nk, self.nq)

    def blocks(self, b, h, i, j, tiles):
        """Grid indices -> (b, query head, q block, kv head, k block)."""
        fetch = self._word(tiles, b, i, j) >> 2
        if self.q_major:
            return b, h, i, h // self.n_rep, fetch
        return b, h * self.n_rep + j // self.nq, fetch, h, i

    def first(self, refs):
        return pl.program_id(3) == 0

    def last(self, refs):
        return pl.program_id(3) == self.shape[3] - 1

    def run(self, refs, compute, mask):
        word = self._word(refs[0], pl.program_id(0), pl.program_id(2),
                          pl.program_id(3))
        _dispatch(word & 3, compute, mask, self.block_skip)


class _Compacted:
    """The grid of a host-built block map: (B, H, n_steps) over its
    active tiles in q-major or k-major order, five scalar-prefetch step
    arrays (q block, k block, first, last, active)."""

    n_prefetch = 5

    def __init__(self, block_map, major, B, H, n_rep, block_skip):
        self.arrays = tuple(jnp.asarray(a) for a in block_map.arrays(major))
        self.n_rep, self.block_skip = n_rep, block_skip
        self.shape = (B, H, len(self.arrays[0]))
        self.semantics = ("parallel", "parallel", "arbitrary")
        self.folds_gqa = False

    def prefetch(self, *_):
        return self.arrays

    def blocks(self, b, h, t, qblk, kblk, first, last, active):
        return b, h, qblk[t], h // self.n_rep, kblk[t]

    def first(self, refs):
        return refs[2][pl.program_id(2)] == 1

    def last(self, refs):
        return refs[3][pl.program_id(2)] == 1

    def run(self, refs, compute, mask):
        allowed = mask()
        go = refs[4][pl.program_id(2)] == 1
        if self.block_skip:
            go &= jnp.any(allowed)
        pl.when(go)(lambda: compute(allowed))


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


def _grid(block_map, major, B, H, Hkv, nq, nk, block_q, block_k, window,
          block_skip):
    n_rep = H // Hkv
    if block_map is None:
        heads = H if major == "q" else Hkv
        return _Dense(B, heads, nq, nk, n_rep, major == "q", block_skip)
    _check_block_map(block_map, block_q, block_k, nq, nk, window)
    return _Compacted(block_map, major, B, H, n_rep, block_skip)


def _specs(grid, block_q, block_k, hd, n_qwords, kind):
    """BlockSpecs of the operands in call order — query words, key
    words, q, k, v and, for the backward, dO, lse, delta — plus the
    index maps of the q tile, the dK/dV output tile and the statistics
    column. ``kind``: "fwd" / "dq" (query words as columns, key words as
    rows) or "dkv" (the transpose, statistics as rows)."""
    blocks = grid.blocks

    def qcol(*a):
        return (a[0], blocks(*a)[2], 0)

    def qrow(*a):
        return (a[0], 0, blocks(*a)[2])

    def kcol(*a):
        return (a[0], blocks(*a)[4], 0)

    def krow(*a):
        return (a[0], 0, blocks(*a)[4])

    def qtile(*a):
        b, hq, qb, _, _ = blocks(*a)
        return (b, hq, qb, 0)

    def ktile(*a):
        b, _, _, hkv, kb = blocks(*a)
        return (b, hkv, kb, 0)

    def dk_tile(*a):
        b, hq, _, hkv, kb = blocks(*a)
        return (b, hkv if grid.folds_gqa else hq, kb, 0)

    def stat_row(*a):
        b, hq, qb, _, _ = blocks(*a)
        return (b, hq, 0, qb)

    if kind == "dkv":
        qw = pl.BlockSpec((1, 1, block_q), qrow)
        kw = pl.BlockSpec((1, block_k, 1), kcol)
        stat = pl.BlockSpec((1, 1, 1, block_q), stat_row)
    else:
        qw = pl.BlockSpec((1, block_q, 1), qcol)
        kw = pl.BlockSpec((1, 1, block_k), krow)
        stat = pl.BlockSpec((1, 1, block_q, 1), qtile)
    q_spec = pl.BlockSpec((1, 1, block_q, hd), qtile)
    k_spec = pl.BlockSpec((1, 1, block_k, hd), ktile)
    specs = [qw] * n_qwords + [kw] * 3 + [q_spec, k_spec, k_spec]
    if kind != "fwd":
        specs += [q_spec, stat, stat]
    return specs, qtile, dk_tile


def _call(kernel, grid, prefetch, in_specs, out_specs, out_shape, scratch,
          block_q: int, block_k: int, interpret, name: str):
    # tiles past 256 x 256 hold several f32 [bq, bk] temporaries and get
    # a larger scoped-VMEM budget
    vmem = 64 * 2 ** 20 if block_q * block_k > 256 * 256 else None
    return pl.pallas_call(
        functools.partial(kernel, grid=grid),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=grid.shape,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=grid.semantics, vmem_limit_bytes=vmem),
        interpret=interpret,
        name=name,
    )


def _unpack(refs, grid, n_qwords: int, n_tensors: int):
    """Kernel refs -> (prefetch, query words, key words, tensors, rest)."""
    p = grid.n_prefetch
    i = p + n_qwords
    return (refs[:p], refs[p:i], refs[i:i + 3], refs[i + 3:i + 3 + n_tensors],
            refs[i + 3 + n_tensors:])


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

def _fwd_accumulate(allowed, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                    softcap: float, scale: float):
    """One tile of the online softmax; ``allowed`` is the [bq, bk] mask
    or None for a tile whose every pair is allowed. A row that has met
    no allowed key keeps m = NEG_INF and takes p = 1 on masked entries;
    the first allowed key rescales them by alpha = 0, and
    ``_fwd_finish`` zeroes a row that meets none."""
    v = v_ref[0, 0]                                     # [bk, hd]
    s = _dot(q_ref[0, 0], k_ref[0, 0], _NT) * scale     # [bq, bk] f32
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    if allowed is not None:
        s = jnp.where(allowed, s, NEG_INF)
    m_prev = m_scr[...]                                 # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + _dot(p.astype(v.dtype), v, _NN)
    m_scr[...] = m_new


def _fwd_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _fwd_finish(mode, out_refs, m_scr, l_scr, acc_scr):
    m = m_scr[...]                                      # [bq, 1]
    live = m > NEG_INF / 2                              # met an allowed key
    l = jnp.where(live, l_scr[...], 0.0)
    if mode == "stats":
        acc_ref, m_ref, l_ref = out_refs
        acc_ref[0, 0] = jnp.where(live, acc_scr[...], 0.0).astype(
            acc_ref.dtype)
        m_ref[0, 0] = m
        l_ref[0, 0] = l
        return
    out = jnp.where(live, acc_scr[...] / jnp.maximum(l, 1e-30), 0.0)
    if mode == "residual":
        o_ref, lse_ref = out_refs
        lse_ref[0, 0] = jnp.where(live, m + jnp.log(jnp.maximum(l, 1e-30)),
                                  NEG_INF)
    else:
        (o_ref,) = out_refs
    o_ref[0, 0] = out.astype(o_ref.dtype)


def _fwd_kernel(*refs, grid, n_qwords: int, softcap: float, scale: float,
                mode: str):
    pre, qw, kw, (q_ref, k_ref, v_ref), rest = _unpack(refs, grid,
                                                       n_qwords, 3)
    out_refs, (m_scr, l_scr, acc_scr) = rest[:-3], rest[-3:]
    pl.when(grid.first(pre))(lambda: _fwd_init(m_scr, l_scr, acc_scr))
    grid.run(pre,
             lambda allowed: _fwd_accumulate(allowed, q_ref, k_ref, v_ref,
                                             m_scr, l_scr, acc_scr,
                                             softcap, scale),
             lambda: _words_mask(qw, kw))
    pl.when(grid.last(pre))(
        lambda: _fwd_finish(mode, out_refs, m_scr, l_scr, acc_scr))


def _p_ds(allowed, a, b, da, db, lse, delta, softcap: float, scale: float):
    """Recompute one tile's probabilities P = exp(a·bᵀ·scale − lse) and
    form dS = P ∘ (da·dbᵀ − delta), with the softcap chain rule folded
    in; lse and delta broadcast against the tile (columns in the dQ
    orientation, rows in the transposed dK/dV one). f32 results;
    ``allowed`` None means a tile whose every pair is allowed."""
    s = _dot(a, b, _NT) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    p = jnp.exp(s - lse)
    if allowed is not None:
        p = jnp.where(allowed, p, 0.0)
    ds = p * (_dot(da, db, _NT) - delta)
    if softcap:
        ds = ds * (1.0 - (s / softcap) ** 2)
    return p, ds


def _dq_kernel(*refs, grid, n_qwords: int, softcap: float, scale: float):
    pre, qw, kw, tensors, (dq_ref, dq_scr) = _unpack(refs, grid, n_qwords, 6)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = tensors

    @pl.when(grid.first(pre))
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def compute(allowed):
        k = k_ref[0, 0]
        _, ds = _p_ds(allowed, q_ref[0, 0], k, do_ref[0, 0], v_ref[0, 0],
                      lse_ref[0, 0], delta_ref[0, 0], softcap, scale)
        dq_scr[...] += _dot(ds.astype(k.dtype), k, _NN) * scale

    grid.run(pre, compute, lambda: _words_mask(qw, kw))

    @pl.when(grid.last(pre))
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, grid, n_qwords: int, softcap: float, scale: float):
    """dK/dV on transposed [bk, bq] tiles: Sᵀ = K Qᵀ, dPᵀ = V dOᵀ,
    dV += Pᵀ dO, dK += dSᵀ Q — no matmul takes a transposed operand."""
    pre, qw, kw, tensors, rest = _unpack(refs, grid, n_qwords, 6)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = tensors
    dk_ref, dv_ref, dk_scr, dv_scr = rest

    @pl.when(grid.first(pre))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def compute(allowed):
        q, do = q_ref[0, 0], do_ref[0, 0]
        p, ds = _p_ds(allowed, k_ref[0, 0], q, v_ref[0, 0], do,
                      lse_ref[0, 0], delta_ref[0, 0], softcap, scale)
        dv_scr[...] += _dot(p.astype(do.dtype), do, _NN)
        dk_scr[...] += _dot(ds.astype(q.dtype), q, _NN) * scale

    grid.run(pre, compute, lambda: _words_mask(qw, kw))

    @pl.when(grid.last(pre))
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Public wrappers
# ---------------------------------------------------------------------------

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
    assert Tq % block_q == 0 and Tk % block_k == 0, (Tq, Tk)
    grid = _grid(block_map, "q", B, H, Hkv, Tq // block_q, Tk // block_k,
                 block_q, block_k, window, block_skip)
    prefetch = grid.prefetch(q_bits, kv_bits, q_pos, kv_pos, block_q,
                             block_k, window)
    q_words, k_words = _mask_words(q_bits, kv_bits, q_pos, kv_pos, window)
    in_specs, qtile, _ = _specs(grid, block_q, block_k, hd, len(q_words),
                                "fwd")

    row = jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32)
    out_shapes = {
        "out": (jax.ShapeDtypeStruct((B, H, Tq, hd), q.dtype),),
        "residual": (jax.ShapeDtypeStruct((B, H, Tq, hd), q.dtype), row),
        "stats": (jax.ShapeDtypeStruct((B, H, Tq, hd), jnp.float32),
                  row, row),
    }[return_mode]
    out_specs = [pl.BlockSpec((1, 1, block_q, hd), qtile)] + \
        [pl.BlockSpec((1, 1, block_q, 1), qtile)] * (len(out_shapes) - 1)
    scratch = [pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, hd), jnp.float32)]
    kernel = functools.partial(_fwd_kernel, n_qwords=len(q_words),
                               softcap=softcap, scale=hd ** -0.5,
                               mode=return_mode)
    outs = _call(kernel, grid, prefetch, in_specs, out_specs,
                 list(out_shapes), scratch, block_q, block_k, interpret,
                 "bam_fwd")(
        *prefetch, *map(_col, q_words), *map(_row, k_words),
        _head_major(q), _head_major(k), _head_major(v))

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
    assert Tq % block_q == 0 and Tk % block_k == 0, (Tq, Tk)
    nq, nk = Tq // block_q, Tk // block_k

    # delta_i = sum_d dO_i·O_i — the rowwise correction term (O(T·hd))
    delta = jnp.einsum("bqhd,bqhd->bhq", out.astype(jnp.float32),
                       do.astype(jnp.float32))
    lse = lse.astype(jnp.float32)
    q_words, k_words = _mask_words(q_bits, kv_bits, q_pos, kv_pos, window)
    tensors = (_head_major(q), _head_major(k), _head_major(v),
               _head_major(do))
    common = dict(n_qwords=len(q_words), softcap=softcap, scale=hd ** -0.5)
    tiles = (q_bits, kv_bits, q_pos, kv_pos, block_q, block_k, window)

    grid = _grid(block_map, "q", B, H, Hkv, nq, nk, block_q, block_k,
                 window, block_skip)
    prefetch = grid.prefetch(*tiles)
    in_specs, qtile, _ = _specs(grid, block_q, block_k, hd, len(q_words),
                                "dq")
    dq = _call(functools.partial(_dq_kernel, **common), grid, prefetch,
               in_specs, pl.BlockSpec((1, 1, block_q, hd), qtile),
               jax.ShapeDtypeStruct((B, H, Tq, hd), q.dtype),
               [pltpu.VMEM((block_q, hd), jnp.float32)],
               block_q, block_k, interpret, "bam_bwd_dq")(
        *prefetch, *map(_col, q_words), *map(_row, k_words), *tensors,
        _col(lse), _col(delta))

    grid = _grid(block_map, "k", B, H, Hkv, nq, nk, block_q, block_k,
                 window, block_skip)
    prefetch = grid.prefetch(*tiles)
    in_specs, _, dk_tile = _specs(grid, block_q, block_k, hd, len(q_words),
                                  "dkv")
    dk_shape = jax.ShapeDtypeStruct((B, Hkv, Tk, hd), k.dtype) \
        if grid.folds_gqa else jax.ShapeDtypeStruct((B, H, Tk, hd),
                                                    jnp.float32)
    dk_h, dv_h = _call(functools.partial(_dkv_kernel, **common), grid,
                       prefetch, in_specs,
                       [pl.BlockSpec((1, 1, block_k, hd), dk_tile)] * 2,
                       [dk_shape, dk_shape],
                       [pltpu.VMEM((block_k, hd), jnp.float32)] * 2,
                       block_q, block_k, interpret, "bam_bwd_dkv")(
        *prefetch, *map(_row, q_words), *map(_col, k_words), *tensors,
        _row(lse), _row(delta))

    if not grid.folds_gqa:
        # GQA: fold the compacted grid's q-head grads onto the KV heads
        n_rep = H // Hkv
        dk_h = dk_h.reshape(B, Hkv, n_rep, Tk, hd).sum(axis=2)
        dv_h = dv_h.reshape(B, Hkv, n_rep, Tk, hd).sum(axis=2)
    return (_head_major(dq), _head_major(dk_h).astype(k.dtype),
            _head_major(dv_h).astype(v.dtype))
