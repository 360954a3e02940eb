"""Bitfield Attention Mask (BAM) — Cornstarch §4.3.1, TPU/JAX adaptation.

A full multimodal attention mask is O(T^2); BAM represents it as a 1-D
vector of per-token integer bitfields, expanded blockwise only inside the
attention computation (the Pallas kernel evaluates it in-registers; the
XLA path lets the compiler fuse it into the softmax).

Bit layout (uint32 — container JAX runs x64-disabled; the paper uses
int64 with ~60 modality bits. Semantics are identical, widening to two
lanes of uint32 or uint64 is mechanical):

    [15:0]   attends-set  A_i : bit m set => token i may attend modality m
    [22:16]  own modality m_i : 0 = text, 1..15 = encoder streams
    [30:23]  instance id  d_i : packed-document id (multimodal packing)
    value 0                  : padding token (never attends / attended)

Mask semantics (single source of truth; mirrored by kernels/ref.py and
validated against each other in tests):

    allowed(i, j) =
        bits_q[i] != 0 and bits_k[j] != 0          (non-padding)
        and d_i == d_j                             (same packed document)
        and (A_i >> m_j) & 1                       (modality-attend bit)
        and ( m_i == 0  ->  pos_j <= pos_i         (text queries: causal)
              m_i != 0  ->  m_j == m_i )           (modality: bidirectional
                                                    within own stream)

Sliding-window (gemma2 local layers) further requires
``pos_i - pos_j < window`` for text queries.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

TEXT = 0
ATTEND_BITS = 16
MOD_SHIFT = 16
MOD_BITS = 7
INST_SHIFT = 23
INST_BITS = 8

_ATTEND_MASK = (1 << ATTEND_BITS) - 1
_MOD_MASK = (1 << MOD_BITS) - 1
_INST_MASK = (1 << INST_BITS) - 1


def encode(attends: int, modality: int, instance: int = 0) -> int:
    assert 0 <= attends <= _ATTEND_MASK
    assert 0 <= modality <= _MOD_MASK
    assert 0 <= instance <= _INST_MASK
    return attends | (modality << MOD_SHIFT) | (instance << INST_SHIFT)


def text_token(attend_modalities: Sequence[int] = (), instance: int = 0) -> int:
    """A text token attends text + the given encoder modality streams."""
    a = 1 << TEXT
    for m in attend_modalities:
        a |= 1 << m
    return encode(a, TEXT, instance)


def modality_token(modality: int, instance: int = 0) -> int:
    """Encoder-output tokens attend (bidirectionally) their own stream."""
    assert modality != TEXT
    return encode(1 << modality, modality, instance)


# -- field extraction (works on jnp or np arrays) ---------------------------

def attends_set(bits):
    return bits & _ATTEND_MASK


def own_modality(bits):
    return (bits >> MOD_SHIFT) & _MOD_MASK


def instance_id(bits):
    return (bits >> INST_SHIFT) & _INST_MASK


# ---------------------------------------------------------------------------
# Mask expansion (oracle; O(Tq*Tk) — only for tests/XLA-fused paths)
# ---------------------------------------------------------------------------

def allowed_mask(q_bits, kv_bits, q_pos, kv_pos, window: int = 0):
    """Expand BAM to a boolean mask.

    q_bits: [..., Tq] uint32; kv_bits: [..., Tk]; q_pos/kv_pos: int32
    positions (global sequence positions — CP ranks hold permuted blocks,
    so positions are explicit, not iota).
    Returns bool [..., Tq, Tk].
    """
    qb = q_bits[..., :, None].astype(jnp.uint32)
    kb = kv_bits[..., None, :].astype(jnp.uint32)
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]

    nonpad = (qb != 0) & (kb != 0)
    same_doc = instance_id(qb) == instance_id(kb)
    bit_ok = ((attends_set(qb) >> own_modality(kb)) & 1) != 0
    q_is_text = own_modality(qb) == TEXT
    causal = kp <= qp
    if window:
        causal &= (qp - kp) < window
    within = own_modality(kb) == own_modality(qb)
    rule = jnp.where(q_is_text, causal, within)
    return nonpad & same_doc & bit_ok & rule


TILE_EMPTY, TILE_PARTIAL, TILE_FULL = 0, 1, 2


def tile_classes(q_bits, kv_bits, q_pos, kv_pos, block_q: int, block_k: int,
                 window: int = 0):
    """Classify each [block_q, block_k] tile of the mask on the device:
    TILE_EMPTY (no pair allowed), TILE_PARTIAL or TILE_FULL (every pair
    allowed). [B, Tq] / [B, Tk] inputs whose lengths are block multiples
    -> int32 [B, Tq // block_q, Tk // block_k]. Reduced from
    ``allowed_mask`` itself, so exact for every bitfield; XLA fuses the
    boolean mask into the two reductions."""
    m = allowed_mask(q_bits, kv_bits, q_pos, kv_pos, window)
    B, Tq, Tk = m.shape
    m = m.reshape(B, Tq // block_q, block_q, Tk // block_k, block_k)
    return (jnp.any(m, axis=(2, 4)).astype(jnp.int32)
            + jnp.all(m, axis=(2, 4)).astype(jnp.int32))


def causal_bits(batch: int, seq: int, dtype=jnp.uint32):
    """Degenerate BAM for a pure-text causal LM (paper §4.3.1: causal is
    the 1-D special case)."""
    return jnp.full((batch, seq), text_token(), dtype)


def repeat_kv(k, n_rep: int):
    """GQA head expansion [B, T, Hkv, hd] -> [B, T, Hkv*n_rep, hd] —
    the dense-path pairing of the kernel's index-map head fold (shared
    by models.layers and the CP XLA bodies)."""
    if n_rep == 1:
        return k
    b, t, h, d = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, t, h, n_rep, d))
    return k.reshape(b, t, h * n_rep, d)


# ---------------------------------------------------------------------------
# Per-token workload (row-sums of the mask) — O(T * M) via per-modality
# cumulative counts, no O(T^2) materialization. Used by the token
# distribution planners (§4.3.2).
# ---------------------------------------------------------------------------

def token_workload(bits: np.ndarray, pos: np.ndarray,
                   window: int = 0) -> np.ndarray:
    """bits/pos: [T] (numpy, host-side planning). Returns float64 [T]:
    W_i = number of keys token i attends = row-sum of allowed_mask."""
    bits = np.asarray(bits, np.uint32)
    pos = np.asarray(pos, np.int64)
    T = bits.shape[0]
    order = np.argsort(pos, kind="stable")
    inv = np.empty_like(order)
    inv[order] = np.arange(T)

    mod = (bits >> MOD_SHIFT) & _MOD_MASK
    inst = (bits >> INST_SHIFT) & _INST_MASK
    att = bits & _ATTEND_MASK
    nonpad = bits != 0

    W = np.zeros(T, np.float64)
    for d in np.unique(inst[nonpad]):
        sel = nonpad & (inst == d)
        idx = np.where(sel)[0]
        idx = idx[np.argsort(pos[idx], kind="stable")]
        m = mod[idx]
        a = att[idx]
        p = pos[idx]
        n = idx.shape[0]
        mods_here = np.unique(m)
        total = {mm: int((m == mm).sum()) for mm in mods_here}
        w = np.zeros(n, np.float64)
        text_rows = m == TEXT
        for mm in mods_here:
            bit_ok = ((a >> int(mm)) & 1) != 0
            # text queries: count of modality-mm keys with
            # pos_i - window < pos_j <= pos_i (exact per modality — a
            # single min(total, window) clamp would over-subtract for
            # text rows that also attend modality keys)
            pos_mm = p[m == mm]          # ascending (p is sorted)
            hi = np.searchsorted(pos_mm, p, side="right")
            if window:
                lo = np.searchsorted(pos_mm, p - window, side="right")
            else:
                lo = 0
            w += np.where(text_rows & bit_ok, hi - lo, 0.0)
            # modality queries: bidirectional within own stream only
            # (window constrains text queries only, matching allowed_mask)
            if mm != TEXT:
                w += np.where((m == mm) & bit_ok, float(total[mm]), 0.0)
        W[idx] = w
    return W


def block_workload(bits: np.ndarray, pos: np.ndarray, block: int,
                   window: int = 0) -> np.ndarray:
    """Sum token workloads over contiguous blocks of ``block`` tokens
    (paper: assignment is done at block granularity for accelerator
    efficiency)."""
    W = token_workload(bits, pos, window)
    T = W.shape[0]
    nb = (T + block - 1) // block
    padded = np.zeros(nb * block, np.float64)
    padded[:T] = W
    return padded.reshape(nb, block).sum(axis=1)


# ---------------------------------------------------------------------------
# Host-side kernel grid compaction: from the block-level reduction of the
# bitfield mask, a flattened list of active (q-block, k-block) tiles that
# drives the Pallas kernel through a scalar-prefetch index map. Fully
# masked tiles are dropped from the grid itself — they cost neither a
# grid step nor a K/V DMA (the in-kernel `pl.when` skip only saves the
# MXU work, not the copies).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockMask:
    """Compacted kernel grid for one (bits, pos) mask instance.

    Two flattened orderings of the active tiles, both as tuples of
    python ints so the object is hashable (it rides through
    ``jax.custom_vjp`` as a static argument):

    * q-major (forward + dQ backward): tiles sorted by q-block, each
      q-block's active k-blocks consecutive. ``first``/``last`` flag the
      accumulator init/flush steps; a q-block with NO active tile still
      gets one step with ``active == 0`` so its output rows are written
      (as zeros) exactly once.
    * k-major (dK/dV backward): same construction transposed.
    """
    block_q: int
    block_k: int
    nq: int
    nk: int
    window: int
    q_steps: Tuple[Tuple[int, int, int, int, int], ...]  # (iq, ik, first, last, active)
    k_steps: Tuple[Tuple[int, int, int, int, int], ...]

    @property
    def n_steps(self) -> int:
        return len(self.q_steps)

    @property
    def n_dense_steps(self) -> int:
        return self.nq * self.nk

    @property
    def skip_fraction(self) -> float:
        active = sum(s[4] for s in self.q_steps)
        return 1.0 - active / max(self.n_dense_steps, 1)

    def arrays(self, major: str = "q"):
        """(q_block, k_block, first, last, active) int32 arrays for the
        scalar-prefetch operands."""
        steps = self.q_steps if major == "q" else self.k_steps
        cols = np.asarray(steps, np.int32).reshape(len(steps), 5)
        return tuple(np.ascontiguousarray(cols[:, j]) for j in range(5))


def _flatten_active(active: np.ndarray) -> Tuple[Tuple[int, ...], ...]:
    """active: [n_major, n_minor] bool -> q-major flattened step tuples."""
    steps = []
    for i in range(active.shape[0]):
        js = np.flatnonzero(active[i])
        if js.size == 0:
            steps.append((i, 0, 1, 1, 0))
            continue
        for t, j in enumerate(js):
            steps.append((i, int(j), int(t == 0), int(t == js.size - 1), 1))
    return tuple(steps)


def build_block_map(q_bits, kv_bits, q_pos, kv_pos, block_q: int,
                    block_k: int, window: int = 0) -> BlockMask:
    """Block-level reduction of the bitfield mask (host side, numpy).

    Accepts [T] or [B, T] arrays; a tile is active if ANY batch row has
    any allowed (q, k) pair inside it, so one map is valid for the whole
    batch. Sequences are padded to block multiples with bits=0 (never
    attends — identical to the kernel wrapper's padding)."""
    q_bits = np.atleast_2d(np.asarray(q_bits, np.uint32))
    kv_bits = np.atleast_2d(np.asarray(kv_bits, np.uint32))
    q_pos = np.atleast_2d(np.asarray(q_pos, np.int64))
    kv_pos = np.atleast_2d(np.asarray(kv_pos, np.int64))
    Tq, Tk = q_bits.shape[1], kv_bits.shape[1]
    nq = -(-Tq // block_q)
    nk = -(-Tk // block_k)

    def _pad(x, to, value=0):
        pad = to - x.shape[1]
        if pad:
            x = np.pad(x, ((0, 0), (0, pad)), constant_values=value)
        return x

    qb = _pad(q_bits, nq * block_q)
    kb = _pad(kv_bits, nk * block_k)
    qp = _pad(q_pos, nq * block_q, -1)
    kp = _pad(kv_pos, nk * block_k, -1)
    # reduce strip-by-strip: peak host memory O(B·block_q·Tk), never the
    # full O(Tq·Tk) mask — at the long-context scale this feature
    # targets, materializing the dense mask would be the very blow-up
    # the compacted grid exists to avoid
    active = np.zeros((nq, nk), bool)
    for iq in range(nq):
        s = slice(iq * block_q, (iq + 1) * block_q)
        strip = np.asarray(allowed_mask(qb[:, s], kb, qp[:, s], kp, window))
        active[iq] = strip.reshape(-1, block_q, nk, block_k).any(
            axis=(0, 1, 3))
    return BlockMask(block_q=block_q, block_k=block_k, nq=nq, nk=nk,
                     window=window,
                     q_steps=_flatten_active(active),
                     k_steps=tuple((i, j, f, l, a) for (j, i, f, l, a)
                                   in _flatten_active(active.T)))


# ---------------------------------------------------------------------------
# BAM construction for the synthetic multimodal batches (EP / EE / MP —
# paper Fig. 11 mask types)
# ---------------------------------------------------------------------------

def build_sample_bits(segments: Sequence[Tuple[str, int, int]],
                      seq_len: int) -> Tuple[np.ndarray, np.ndarray]:
    """segments: list of (kind, modality_id, length); kind in
    {"text", "mod"}; instance id increments on a "doc" boundary marker
    ("newdoc", 0, 0). Returns (bits [T] uint32, pos [T] int32), padded
    with zeros to seq_len."""
    bits, pos = [], []
    inst = 0
    p = 0
    seen_mods: set[int] = set()
    for kind, m, n in segments:
        if kind == "newdoc":
            inst += 1
            p = 0
            seen_mods = set()
            continue
        if kind == "mod":
            seen_mods.add(m)
            for _ in range(n):
                bits.append(modality_token(m, inst))
                pos.append(p)
                p += 1
        else:
            for _ in range(n):
                bits.append(text_token(sorted(seen_mods), inst))
                pos.append(p)
                p += 1
    assert len(bits) <= seq_len, (len(bits), seq_len)
    out_b = np.zeros(seq_len, np.uint32)
    out_p = np.zeros(seq_len, np.int32)
    out_b[: len(bits)] = bits
    out_p[: len(pos)] = pos
    return out_b, out_p
