"""Multimodality-aware context parallelism (Cornstarch §4.3 + §5.3).

Each CP rank holds the token *blocks* assigned by a distribution plan
(core/distribution.py) — note positions/bitfields travel with the
tokens, since after LPT assignment a rank's tokens are NOT contiguous.

Implementations:

* ``allgather`` (paper §5.3, Llama-3 style, the default): every rank
  all-gathers K/V (+ kv bits/positions) and computes attention rows for
  its local queries only. Load balance therefore depends ONLY on the
  per-rank row workloads — exactly what the LPT plan equalizes.
* ``ring``: P2P ring (ppermute) with online-softmax combination —
  the baseline the paper compares against (and the fallback for which
  random distribution is provided).

Both run under ``shard_map`` over a named mesh axis. A collective-free
reference (``cp_reference``) computes identical math for single-device
tests; multi-device equivalence is tested in a subprocess with
``--xla_force_host_platform_device_count``.

Per-step attention math (``impl=``): the default ``"xla"`` body
materializes the [B,H,Tq,Tk] logits in HBM per step; ``"bam_kernel"`` /
``"bam_interpret"`` route through the Pallas stats kernel
(``repro.kernels.ops.bam_attention_stats``) which returns the same
unnormalized (acc, m, l) partials with the bitfield mask evaluated
in-registers — the per-step logits never leave VMEM. The XLA body is
kept as the CPU fallback and ``cp_reference`` stays the oracle.

Both bodies are DIFFERENTIABLE: each carries a combining-aware
``custom_vjp`` that saves the per-rank (out, lse) flash residuals
derived from the cross-chunk combined (m, l), so the backward runs the
same fused per-chunk flash backward the single-device kernel path uses
(``repro.kernels.ops.bam_attention_chunk_bwd``) — no O(Tq·Tk)
intermediate is ever traced on the kernel impls. Backward collectives:
allgather's backward reduce-scatters dK/dV back to their owner ranks
(``psum_scatter``); ring's backward runs the REVERSE ring, with the
accumulating dK/dV chunk traveling alongside its K/V chunk so both are
home after G steps. Training enters through
``repro.models.layers.run_attention`` (``ModelConfig.cp_mesh``) and
``repro.training.steps.make_cp_train_step``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import bam
from repro.core.distribution import Plan

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Plan application (host side): permute tokens so each rank's assigned
# blocks are contiguous in the sharded layout.
# ---------------------------------------------------------------------------

def plan_permutation(plan: Plan, seq_len: int) -> np.ndarray:
    """perm[i] = source token index of the i-th token in CP layout.

    The result is always a TRUE permutation of ``arange(seq_len)`` —
    every token appears exactly once. Plans balance block *workloads*,
    so per-rank token counts may differ; counts are rebalanced to
    differ by at most one (ranks ``0..seq_len % num_ranks - 1`` get the
    extra token), moving the trailing tokens of over-full ranks to
    under-full ranks deterministically. When ``seq_len % num_ranks !=
    0`` equal counts are impossible — shard_map consumers must pad the
    sequence to a rank multiple first. Raises ``ValueError`` if the
    plan's blocks do not cover ``seq_len`` tokens."""
    slices = [s[s < seq_len] for s in plan.rank_token_slices()]
    total = sum(len(s) for s in slices)
    if total != seq_len:
        raise ValueError(
            f"plan covers {total} tokens "
            f"({len(plan.assignment)} blocks x {plan.block_size}) "
            f"but seq_len={seq_len}")
    counts = [len(s) for s in slices]
    if len(set(counts)) != 1:
        # rebalance counts while keeping workload order: move trailing
        # tokens from over-full to under-full ranks (deterministic).
        # Excess and deficit match exactly because targets sum to
        # seq_len, so no token is ever dropped.
        base, rem = divmod(seq_len, plan.num_ranks)
        targets = [base + (1 if g < rem else 0)
                   for g in range(plan.num_ranks)]
        extra: list = []
        for g, s in enumerate(slices):
            if len(s) > targets[g]:
                extra.extend(s[targets[g]:])
                slices[g] = s[:targets[g]]
        for g, s in enumerate(slices):
            need = targets[g] - len(s)
            if need > 0:
                slices[g] = np.concatenate(
                    [s, np.asarray(extra[:need], dtype=np.int64)])
                extra = extra[need:]
        assert not extra, "rebalance left unassigned tokens"
    return np.concatenate(slices).astype(np.int64)


def apply_plan(tree, perm: np.ndarray, axis: int = 1):
    """Gather ``axis`` (the token axis) of every array by perm."""
    return jax.tree.map(lambda a: jnp.take(a, perm, axis=axis), tree)


def invert_perm(perm: np.ndarray) -> np.ndarray:
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    return inv


# ---------------------------------------------------------------------------
# Local attention with explicit (m, l) stats for online combination
# ---------------------------------------------------------------------------

def _masked_attn_stats(q, k, v, mask, scale, softcap: float = 0.0):
    """Returns (acc [B,H,Tq,hd] = sum exp(l-m)·V, m [B,H,Tq], l [B,H,Tq])
    — unnormalized flash-attention partials for cross-chunk combine.
    Dense XLA body: materializes [B,H,Tq,Tk] logits (CPU fallback; the
    kernel path in ``_attn_stats`` avoids exactly this). GQA K/V are
    head-expanded (the kernel folds the mapping into its index maps
    instead)."""
    k = bam.repeat_kv(k, q.shape[2] // k.shape[2])
    v = bam.repeat_kv(v, q.shape[2] // v.shape[2])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                         # [B,H,Tq]
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v)
    return acc.astype(jnp.float32), m, l


def _attn_stats(q, k, v, q_bits, kv_bits, q_pos, kv_pos,
                softcap: float, window: int, impl: str,
                block_q: Optional[int] = None,
                block_k: Optional[int] = None):
    """Stats-path dispatch: ``impl="xla"`` builds the dense mask and
    logits; kernel impls evaluate the bitfield in-registers and never
    materialize an O(Tq·Tk) intermediate. Both derive the hd**-0.5
    scale themselves (the kernel hardcodes it) so the paths can't
    silently diverge."""
    if impl == "xla":
        mask = bam.allowed_mask(q_bits, kv_bits, q_pos, kv_pos,
                                window)[:, None]
        return _masked_attn_stats(q, k, v, mask, q.shape[-1] ** -0.5,
                                  softcap)
    from repro.kernels.ops import auto_block, bam_attention_stats
    return bam_attention_stats(
        q, k, v, q_bits, kv_bits, q_pos, kv_pos, softcap=softcap,
        window=window, impl=impl,
        block_q=block_q or auto_block(q.shape[1]),
        block_k=block_k or auto_block(k.shape[1]))


def _combine_stats(acc1, m1, l1, acc2, m2, l2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return acc1 * a1[..., None] + acc2 * a2[..., None], m, l1 * a1 + l2 * a2


def _finish(acc, m, l, dtype):
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.where((l > 0)[..., None], out, 0.0)
    return jnp.einsum("bhqd->bqhd", out).astype(dtype)


def _lse_from_stats(m, l):
    """Combined (m, l) -> per-row log-sum-exp [B,H,Tq] — the flash
    residual every per-chunk backward renormalizes against. Rows with
    no allowed key (l == 0) get NEG_INF, matching the kernel's own
    padding convention."""
    return jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)


# ---------------------------------------------------------------------------
# Per-chunk flash backward from the COMBINED residuals
# ---------------------------------------------------------------------------

def _dense_chunk_bwd(q, k, v, out, g, lse, q_bits, kv_bits, q_pos, kv_pos,
                     softcap: float, window: int):
    """XLA fallback chunk backward: same math as the fused kernels
    (dS = P·(dP − Δ) from the combined lse), dense [B,H,Tq,Tk]
    intermediates. Returns (dq_contrib, dk, dv) with dk/dv GQA-folded
    to the K/V head count."""
    n_rep = q.shape[2] // k.shape[2]
    scale = q.shape[-1] ** -0.5
    mask = bam.allowed_mask(q_bits, kv_bits, q_pos, kv_pos, window)[:, None]
    kf = bam.repeat_kv(k, n_rep).astype(jnp.float32)
    vf = bam.repeat_kv(v, n_rep).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    # fully-masked rows carry lse = NEG_INF; clamp so the (discarded)
    # masked lanes of exp() cannot overflow to inf
    lse_safe = jnp.where(lse <= NEG_INF / 2, 0.0, lse)
    p = jnp.where(mask, jnp.exp(s - lse_safe[..., None]), 0.0)
    delta = jnp.einsum("bqhd,bqhd->bhq", out.astype(jnp.float32), gf)
    dp = jnp.einsum("bqhd,bkhd->bhqk", gf, vf)
    ds = p * (dp - delta[..., None])
    if softcap:
        ds = ds * (1.0 - (s / softcap) ** 2)
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, kf) * scale
    dk_h = jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
    dv_h = jnp.einsum("bhqk,bqhd->bkhd", p, gf)
    if n_rep > 1:
        B, Tk, H, hd = dk_h.shape
        dk_h = dk_h.reshape(B, Tk, H // n_rep, n_rep, hd).sum(axis=3)
        dv_h = dv_h.reshape(B, Tk, H // n_rep, n_rep, hd).sum(axis=3)
    return dq.astype(q.dtype), dk_h.astype(k.dtype), dv_h.astype(v.dtype)


def _chunk_bwd(q, k, v, out, g, lse, q_bits, kv_bits, q_pos, kv_pos,
               softcap: float, window: int, impl: str,
               block_q: Optional[int] = None,
               block_k: Optional[int] = None):
    """One K/V chunk's flash backward against the combined (out, lse)
    residuals: (dq_contrib, dk, dv). dq contributions sum over chunks;
    dk/dv are complete for the chunk. Kernel impls run the fused Pallas
    dQ / dK-dV kernels per chunk — no O(Tq·Tk) recompute."""
    if impl == "xla":
        return _dense_chunk_bwd(q, k, v, out, g, lse, q_bits, kv_bits,
                                q_pos, kv_pos, softcap, window)
    from repro.kernels.ops import auto_block, bam_attention_chunk_bwd
    return bam_attention_chunk_bwd(
        q, k, v, out, g, lse, q_bits, kv_bits, q_pos, kv_pos,
        softcap=softcap, window=window, impl=impl,
        block_q=block_q or auto_block(q.shape[1]),
        block_k=block_k or auto_block(k.shape[1]))


# ---------------------------------------------------------------------------
# CP attention bodies (run inside shard_map) — differentiable via
# combining-aware custom_vjps: residuals are the per-rank (out, lse)
# derived from the cross-chunk combined (m, l).
# ---------------------------------------------------------------------------

def _gather_kv(axis_name, k, v, kv_bits, kv_pos):
    return (lax.all_gather(k, axis_name, axis=1, tiled=True),
            lax.all_gather(v, axis_name, axis=1, tiled=True),
            lax.all_gather(kv_bits, axis_name, axis=1, tiled=True),
            lax.all_gather(kv_pos, axis_name, axis=1, tiled=True))


_NONDIFF = (0, 1, 2, 3, 4, 5)   # axis_name, softcap, window, impl, bq, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=_NONDIFF)
def _allgather_diff(axis_name, softcap, window, impl, block_q, block_k,
                    q, k, v, q_bits, kv_bits, q_pos, kv_pos):
    out, _ = _allgather_fwd(axis_name, softcap, window, impl, block_q,
                            block_k, q, k, v, q_bits, kv_bits, q_pos,
                            kv_pos)
    return out


def _allgather_fwd(axis_name, softcap, window, impl, block_q, block_k,
                   q, k, v, q_bits, kv_bits, q_pos, kv_pos):
    k_all, v_all, kb_all, kp_all = _gather_kv(axis_name, k, v, kv_bits,
                                              kv_pos)
    acc, m, l = _attn_stats(q, k_all, v_all, q_bits, kb_all, q_pos, kp_all,
                            softcap, window, impl, block_q, block_k)
    out = _finish(acc, m, l, q.dtype)
    # residuals are O(Tq_local·H·hd): local tensors + (out, lse); the
    # gathered K/V are re-gathered in backward instead of saved
    return out, (q, k, v, q_bits, kv_bits, q_pos, kv_pos, out,
                 _lse_from_stats(m, l))


def _allgather_bwd(axis_name, softcap, window, impl, block_q, block_k,
                   res, g):
    q, k, v, q_bits, kv_bits, q_pos, kv_pos, out, lse = res
    k_all, v_all, kb_all, kp_all = _gather_kv(axis_name, k, v, kv_bits,
                                              kv_pos)
    dq, dk_all, dv_all = _chunk_bwd(
        q, k_all, v_all, out, g, lse, q_bits, kb_all, q_pos, kp_all,
        softcap, window, impl, block_q, block_k)
    # every rank produced grads for ALL keys; reduce-scatter them back
    # to the owner rank's token slice
    dk = lax.psum_scatter(dk_all, axis_name, scatter_dimension=1,
                          tiled=True)
    dv = lax.psum_scatter(dv_all, axis_name, scatter_dimension=1,
                          tiled=True)
    return dq, dk, dv, None, None, None, None


_allgather_diff.defvjp(_allgather_fwd, _allgather_bwd)


def _allgather_body(q, k, v, q_bits, kv_bits, q_pos, kv_pos, *,
                    axis_name: str, softcap: float, window: int,
                    impl: str = "xla", block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """Per-rank: local queries [B,Tq/G]; gather all K/V."""
    return _allgather_diff(axis_name, softcap, window, impl, block_q,
                           block_k, q, k, v, q_bits, kv_bits, q_pos,
                           kv_pos)


def _ring_shift(axis_name, G, arrays, reverse: bool = False):
    perm = [((j + 1) % G, j) if reverse else (j, (j + 1) % G)
            for j in range(G)]
    return tuple(lax.ppermute(a, axis_name, perm) for a in arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=_NONDIFF)
def _ring_diff(axis_name, softcap, window, impl, block_q, block_k,
               q, k, v, q_bits, kv_bits, q_pos, kv_pos):
    out, _ = _ring_fwd(axis_name, softcap, window, impl, block_q, block_k,
                       q, k, v, q_bits, kv_bits, q_pos, kv_pos)
    return out


def _ring_fwd(axis_name, softcap, window, impl, block_q, block_k,
              q, k, v, q_bits, kv_bits, q_pos, kv_pos):
    G = lax.psum(1, axis_name)
    B, Tq, H, hd = q.shape

    def step(i, carry):
        acc, m, l, kc, vc, kb, kp = carry
        a2, m2, l2 = _attn_stats(q, kc, vc, q_bits, kb, q_pos, kp,
                                 softcap, window, impl, block_q, block_k)
        acc, m, l = _combine_stats(acc, m, l, a2, m2, l2)
        kc, vc, kb, kp = _ring_shift(axis_name, G, (kc, vc, kb, kp))
        return acc, m, l, kc, vc, kb, kp

    acc0 = jnp.zeros((B, H, Tq, hd), jnp.float32)
    m0 = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    acc, m, l, *_ = lax.fori_loop(
        0, G, step, (acc0, m0, l0, k, v, kv_bits, kv_pos))
    out = _finish(acc, m, l, q.dtype)
    # after G shifts every chunk is home again: residuals stay local
    return out, (q, k, v, q_bits, kv_bits, q_pos, kv_pos, out,
                 _lse_from_stats(m, l))


def _ring_bwd(axis_name, softcap, window, impl, block_q, block_k, res, g):
    """Reverse ring: the K/V chunk travels the opposite direction with
    its accumulating dK/dV alongside; after G steps chunk and grads are
    back on the owner rank."""
    q, k, v, q_bits, kv_bits, q_pos, kv_pos, out, lse = res
    G = lax.psum(1, axis_name)

    def step(i, carry):
        dq, kc, vc, kb, kp, dkc, dvc = carry
        dq2, dk2, dv2 = _chunk_bwd(q, kc, vc, out, g, lse, q_bits, kb,
                                   q_pos, kp, softcap, window, impl,
                                   block_q, block_k)
        dq = dq + dq2.astype(jnp.float32)
        dkc = dkc + dk2.astype(jnp.float32)
        dvc = dvc + dv2.astype(jnp.float32)
        kc, vc, kb, kp, dkc, dvc = _ring_shift(
            axis_name, G, (kc, vc, kb, kp, dkc, dvc), reverse=True)
        return dq, kc, vc, kb, kp, dkc, dvc

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq, _, _, _, _, dk, dv = lax.fori_loop(
        0, G, step, (dq0, k, v, kv_bits, kv_pos, dk0, dv0))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None, None, None)


_ring_diff.defvjp(_ring_fwd, _ring_bwd)


def _ring_body(q, k, v, q_bits, kv_bits, q_pos, kv_pos, *,
               axis_name: str, softcap: float, window: int,
               impl: str = "xla", block_q: Optional[int] = None,
               block_k: Optional[int] = None):
    """P2P ring: pass K/V chunks around, combine online-softmax stats."""
    return _ring_diff(axis_name, softcap, window, impl, block_q, block_k,
                      q, k, v, q_bits, kv_bits, q_pos, kv_pos)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

_CP_BODIES = {"allgather": _allgather_body, "ring": _ring_body}


def cp_attention(mesh, axis_name: str, q, k, v, q_bits, kv_bits, q_pos,
                 kv_pos, *, method: str = "allgather", softcap: float = 0.0,
                 window: int = 0, impl: str = "xla",
                 block_q: Optional[int] = None,
                 block_k: Optional[int] = None):
    """Inputs are GLOBAL arrays already permuted to plan layout
    ([B, T, H, hd] etc.); shard_map splits the token axis over
    ``axis_name``. Output is the global [B, T, H, hd] in plan layout.

    impl: per-step attention math — "xla" (dense logits, CPU fallback)
    or "bam_kernel" / "bam_interpret" (Pallas stats kernel, no
    O(Tq·Tk) intermediate per rank). Fully differentiable on every
    impl: the bodies carry combining-aware custom_vjps whose backward
    runs the fused per-chunk flash kernels from the combined (out, lse)
    residuals (reduce-scatter for allgather, reverse ring for ring) —
    grads match ``jax.grad`` of ``cp_reference``. block_q/block_k
    override the kernel tile sizes (default: auto from local lengths).
    """
    if method not in _CP_BODIES:
        raise ValueError(f"unknown CP method {method!r}; valid methods: "
                         f"{sorted(_CP_BODIES)}")
    fn = functools.partial(_CP_BODIES[method], axis_name=axis_name,
                           softcap=softcap, window=window, impl=impl,
                           block_q=block_q, block_k=block_k)
    tok = P(None, axis_name)
    tok3 = P(None, axis_name, None, None)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(tok3, tok3, tok3, tok, tok, tok, tok),
        out_specs=tok3, check_vma=False,
    )(q, k, v, q_bits, kv_bits, q_pos, kv_pos)


def cp_reference(q, k, v, q_bits, kv_bits, q_pos, kv_pos, *,
                 softcap: float = 0.0, window: int = 0):
    """Collective-free oracle: identical math on the full arrays (and,
    being plain jnp, the gradient oracle for the CP backward)."""
    mask = bam.allowed_mask(q_bits, kv_bits, q_pos, kv_pos, window)[:, None]
    scale = q.shape[-1] ** -0.5
    acc, m, l = _masked_attn_stats(q, k, v, mask, scale, softcap)
    return _finish(acc, m, l, q.dtype)


def simulate_rank_workloads(plan: Plan, bits: np.ndarray, pos: np.ndarray,
                            window: int = 0) -> np.ndarray:
    """Per-rank attention FLOPs proxy (row workload sums) used by the
    Table-4 style benchmark: the max over ranks bounds the attention
    step time under all-gather CP. Vectorized: blockwise reshape-sum
    then one scatter-add over the plan's block -> rank map (no
    O(ranks × blocks) Python loop)."""
    W = bam.token_workload(bits, pos, window)
    bs = plan.block_size
    nb = len(plan.assignment)
    padded = np.zeros(nb * bs, np.float64)
    n = min(len(W), nb * bs)
    padded[:n] = W[:n]
    block_sums = padded.reshape(nb, bs).sum(axis=1)
    loads = np.zeros(plan.num_ranks)
    np.add.at(loads, plan.assignment, block_sums)
    return loads
