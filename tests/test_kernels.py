"""Pallas BAM flash-attention kernel vs pure-jnp oracle: shape / dtype /
mask-mode sweeps in interpret mode (kernel body executed on CPU), plus
the fused-backward and grid-compaction contracts."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bam
from repro.data.synthetic import random_multimodal_bits
from repro.kernels.ops import bam_attention, bam_attention_stats
from repro.kernels.ref import bam_attention_ref


def make_inputs(seed, B, T, H, Hkv, hd, dtype, segs=None):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, T, H, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, hd), dtype)
    segs = segs or [("text", 0, T // 4), ("mod", 1, T // 4),
                    ("text", 0, T // 4), ("mod", 2, T // 8),
                    ("text", 0, T - 7 * (T // 8))]
    bits_np, pos_np = bam.build_sample_bits(segs, T)
    bits = jnp.broadcast_to(jnp.asarray(bits_np)[None], (B, T))
    pos = jnp.broadcast_to(jnp.asarray(pos_np)[None], (B, T))
    return q, k, v, bits, pos


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("shape", [(1, 32, 2, 2, 16), (2, 48, 4, 2, 32),
                                   (1, 64, 8, 2, 64)])
def test_kernel_matches_oracle_shapes(seed, shape):
    B, T, H, Hkv, hd = shape
    q, k, v, bits, pos = make_inputs(seed, B, T, H, Hkv, hd, jnp.float32)
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos)
    out = bam_attention(q, k, v, bits, bits, pos, pos,
                        impl="bam_interpret", block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    q, k, v, bits, pos = make_inputs(0, 1, 32, 4, 4, 32, dtype)
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos)
    out = bam_attention(q, k, v, bits, bits, pos, pos,
                        impl="bam_interpret", block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("bq,bk", [(8, 8), (8, 32), (32, 8), (16, 48)])
def test_kernel_block_shapes(bq, bk):
    q, k, v, bits, pos = make_inputs(1, 1, 96, 2, 1, 16, jnp.float32)
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos)
    out = bam_attention(q, k, v, bits, bits, pos, pos,
                        impl="bam_interpret", block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_unpadded_lengths():
    """T not a multiple of the block size (ops.py pads with bits=0)."""
    q, k, v, bits, pos = make_inputs(2, 2, 41, 2, 2, 16, jnp.float32)
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos)
    out = bam_attention(q, k, v, bits, bits, pos, pos,
                        impl="bam_interpret", block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_softcap_window():
    q, k, v, bits, pos = make_inputs(3, 1, 32, 2, 2, 16, jnp.float32)
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos, softcap=30.0,
                            window=7)
    out = bam_attention(q, k, v, bits, bits, pos, pos, softcap=30.0,
                        window=7, impl="bam_interpret", block_q=16,
                        block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_packed_documents():
    segs = [("text", 0, 8), ("mod", 1, 8), ("text", 0, 8),
            ("newdoc", 0, 0), ("text", 0, 8), ("mod", 2, 8),
            ("text", 0, 8)]
    q, k, v, bits, pos = make_inputs(4, 1, 48, 2, 2, 16, jnp.float32,
                                     segs=segs)
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos)
    out = bam_attention(q, k, v, bits, bits, pos, pos,
                        impl="bam_interpret", block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_gqa_no_repeat():
    """GQA handled by BlockSpec index_map (no materialized repeat)."""
    q, k, v, bits, pos = make_inputs(5, 1, 32, 8, 2, 16, jnp.float32)
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos)
    out = bam_attention(q, k, v, bits, bits, pos, pos,
                        impl="bam_interpret", block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_gradients_match():
    q, k, v, bits, pos = make_inputs(6, 1, 32, 2, 2, 16, jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(bam_attention(q, k, v, bits, bits, pos, pos,
                                     impl="bam_interpret", block_q=16,
                                     block_k=16) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(bam_attention_ref(q, k, v, bits, bits, pos,
                                         pos) ** 2)

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_block_skip_equivalence():
    """Block sparsity must be a pure optimization (no numeric change)."""
    from repro.kernels.bam_attention import bam_flash_attention
    q, k, v, bits, pos = make_inputs(7, 1, 64, 2, 2, 16, jnp.float32)
    a = bam_flash_attention(q, k, v, bits, bits, pos, pos, block_q=16,
                            block_k=16, block_skip=True, interpret=True)
    b = bam_flash_attention(q, k, v, bits, bits, pos, pos, block_q=16,
                            block_k=16, block_skip=False, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_xla_impl_matches_ref():
    q, k, v, bits, pos = make_inputs(8, 2, 40, 4, 2, 16, jnp.float32)
    out = bam_attention(q, k, v, bits, bits, pos, pos, impl="xla")
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0)


# ---------------------------------------------------------------------------
# Fused backward (custom_vjp saves (out, lse); backward is two Pallas
# kernels — never recomputes through the XLA reference path)
# ---------------------------------------------------------------------------

def _mode_inputs(mode, seed, B=1, T=64, H=4, Hkv=2, hd=16):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, T, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, hd))
    bits_np, pos_np = random_multimodal_bits(T, mode, seed=seed)
    bits = jnp.broadcast_to(jnp.asarray(bits_np)[None], (B, T))
    pos = jnp.broadcast_to(jnp.asarray(pos_np)[None], (B, T))
    return q, k, v, bits, pos, bits_np, pos_np


def _grads(q, k, v, bits, pos, **kw):
    def loss(q, k, v):
        return jnp.sum(bam_attention(q, k, v, bits, bits, pos, pos,
                                     **kw) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("mode", ["ep", "ee", "mp"])
@pytest.mark.parametrize("gqa", [(2, 2), (4, 2), (8, 2)])
def test_fused_backward_matches_xla(mode, gqa):
    H, Hkv = gqa
    q, k, v, bits, pos, *_ = _mode_inputs(mode, seed=0, H=H, Hkv=Hkv)
    gk = _grads(q, k, v, bits, pos, impl="bam_interpret",
                block_q=16, block_k=16)
    gx = _grads(q, k, v, bits, pos, impl="xla")
    for a, b in zip(gk, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("softcap,window", [(30.0, 0), (0.0, 7), (20.0, 9)])
def test_fused_backward_softcap_window(softcap, window):
    q, k, v, bits, pos, *_ = _mode_inputs("ee", seed=1)
    kw = dict(softcap=softcap, window=window)
    gk = _grads(q, k, v, bits, pos, impl="bam_interpret",
                block_q=16, block_k=16, **kw)
    gx = _grads(q, k, v, bits, pos, impl="xla", **kw)
    for a, b in zip(gk, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_fused_backward_padding_zero_grads():
    """bits=0 tokens must receive exactly-zero dQ/dK/dV."""
    B, T, H, hd = 1, 48, 2, 16
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, T, H, hd))
               for i in range(3))
    bits_np, pos_np = bam.build_sample_bits(
        [("text", 0, 16), ("mod", 1, 8), ("text", 0, 8)], T)  # 16 padded
    bits = jnp.asarray(bits_np)[None]
    pos = jnp.asarray(pos_np)[None]
    dq, dk, dv = _grads(q, k, v, bits, pos, impl="bam_interpret",
                        block_q=16, block_k=16)
    assert not np.asarray(dq)[:, 32:].any()
    assert not np.asarray(dk)[:, 32:].any()
    assert not np.asarray(dv)[:, 32:].any()
    # and the non-pad grads match the oracle
    gx = _grads(q, k, v, bits, pos, impl="xla")
    for a, b in zip((dq, dk, dv), gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_fused_backward_no_quadratic_intermediate():
    """The traced backward must not allocate any O(Tq·Tk) f32 array —
    only [block_q, block_k] tiles inside the kernels. (The jaxpr walk
    lives in repro.analysis.jaxprlint, promoted from this file.)"""
    from repro.analysis.jaxprlint import quadratic_f32
    T = 64
    q, k, v, bits, pos, *_ = _mode_inputs("ee", seed=0, T=T)

    def loss(q, k, v):
        return jnp.sum(bam_attention(q, k, v, bits, bits, pos, pos,
                                     impl="bam_interpret", block_q=16,
                                     block_k=16) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert not quadratic_f32(jaxpr, T), quadratic_f32(jaxpr, T)
    # sanity: the XLA fallback DOES trace a [T,T] intermediate, so the
    # assertion above is actually discriminating
    def loss_xla(q, k, v):
        return jnp.sum(bam_attention(q, k, v, bits, bits, pos, pos,
                                     impl="xla") ** 2)
    jaxpr_x = jax.make_jaxpr(jax.grad(loss_xla, argnums=(0, 1, 2)))(q, k, v)
    assert quadratic_f32(jaxpr_x, T)


# ---------------------------------------------------------------------------
# Grid compaction (host-side block map -> scalar-prefetch sparse grid)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["ee", "mp"])
def test_block_map_forward_equivalence(mode):
    q, k, v, bits, pos, bits_np, pos_np = _mode_inputs(mode, seed=2)
    bm = bam.build_block_map(bits_np, bits_np, pos_np, pos_np, 16, 16)
    assert 0.0 < bm.skip_fraction < 1.0      # compaction actually bites
    dense = bam_attention(q, k, v, bits, bits, pos, pos,
                          impl="bam_interpret", block_q=16, block_k=16)
    compact = bam_attention(q, k, v, bits, bits, pos, pos,
                            impl="bam_interpret", block_q=16, block_k=16,
                            block_map=bm)
    np.testing.assert_allclose(np.asarray(compact), np.asarray(dense),
                               atol=1e-6)


def test_block_map_backward_equivalence():
    q, k, v, bits, pos, bits_np, pos_np = _mode_inputs("mp", seed=4)
    bm = bam.build_block_map(bits_np, bits_np, pos_np, pos_np, 16, 16)
    gc = _grads(q, k, v, bits, pos, impl="bam_interpret",
                block_q=16, block_k=16, block_map=bm)
    gx = _grads(q, k, v, bits, pos, impl="xla")
    for a, b in zip(gc, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_block_map_window_mismatch_rejected():
    """A map built for one sliding window prunes tiles that another
    window needs — using it with a different window must fail loudly,
    not silently return wrong attention."""
    q, k, v, bits, pos, bits_np, pos_np = _mode_inputs("ee", seed=6)
    bm = bam.build_block_map(bits_np, bits_np, pos_np, pos_np, 16, 16,
                             window=8)
    with pytest.raises(AssertionError, match="different sliding window"):
        bam_attention(q, k, v, bits, bits, pos, pos,
                      impl="bam_interpret", block_q=16, block_k=16,
                      block_map=bm)
    # matching window is fine
    out = bam_attention(q, k, v, bits, bits, pos, pos, window=8,
                        impl="bam_interpret", block_q=16, block_k=16,
                        block_map=bm)
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos, window=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_block_map_padded_rows():
    """Sequences with fully-padded tail blocks: the dummy steps still
    write (zero) outputs for the empty q blocks."""
    B, T, H, hd = 1, 64, 2, 16
    key = jax.random.PRNGKey(5)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, T, H, hd))
               for i in range(3))
    bits_np, pos_np = bam.build_sample_bits([("text", 0, 24)], T)
    bm = bam.build_block_map(bits_np, bits_np, pos_np, pos_np, 16, 16)
    bits = jnp.asarray(bits_np)[None]
    pos = jnp.asarray(pos_np)[None]
    out = bam_attention(q, k, v, bits, bits, pos, pos,
                        impl="bam_interpret", block_q=16, block_k=16,
                        block_map=bm)
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(out)[:, 24:].any()


# ---------------------------------------------------------------------------
# Stats mode (context-parallel partials) + position-padding contract
# ---------------------------------------------------------------------------

def test_stats_mode_matches_forward():
    q, k, v, bits, pos = make_inputs(9, 2, 48, 4, 2, 16, jnp.float32)
    acc, m, l = bam_attention_stats(q, k, v, bits, bits, pos, pos,
                                    impl="bam_interpret", block_q=16,
                                    block_k=16)
    assert acc.shape == (2, 4, 48, 16) and m.shape == (2, 4, 48)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.where((l > 0)[..., None], out, 0.0)
    out = jnp.einsum("bhqd->bqhd", out)
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pad_positions_use_minus_one():
    """ops._pad_axis pads positions with -1 (not 0 — aliasing pad tokens
    onto real position 0 makes workload stats / debug dumps lie), and
    the kernel output is unchanged by the sentinel because bits=0
    already masks the pad tokens."""
    from repro.kernels.ops import _pad_axis
    pos = jnp.arange(5, dtype=jnp.int32)[None]
    padded = _pad_axis(pos, 8, 1, value=-1)
    np.testing.assert_array_equal(np.asarray(padded)[0, 5:], [-1, -1, -1])
    # window > 0 is where pos aliasing would have changed the math
    q, k, v, bits, pos = make_inputs(10, 1, 41, 2, 2, 16, jnp.float32)
    ref = bam_attention_ref(q, k, v, bits, bits, pos, pos, window=5)
    out = bam_attention(q, k, v, bits, bits, pos, pos, window=5,
                        impl="bam_interpret", block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# The training path's tiles (ops.flash_blocks) at training head widths
# ---------------------------------------------------------------------------

def _training_row(dtype, T=480, H=4, Hkv=2, hd=128):
    """One text-image-text row as the benchmark cells merge them, at a
    length that is not a multiple of 128, GQA n_rep H / Hkv, hd 128."""
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(jax.random.fold_in(key, 0), (1, T, H, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, T, Hkv, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, T, Hkv, hd), dtype)
    w = jax.random.normal(jax.random.fold_in(key, 3), (1, T, H, hd))
    bits_np, pos_np = bam.build_sample_bits(
        [("text", 0, T // 4), ("mod", 1, T // 3),
         ("text", 0, T - T // 4 - T // 3)], T)
    return q, k, v, w, jnp.asarray(bits_np)[None], jnp.asarray(pos_np)[None]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_training_tiles_match_oracle_and_xla(dtype):
    """At the tiles ``flash_blocks`` picks (256, padding 480 to 512 as
    896 pads the cells' 1600 to 1792),
    the kernel's output and dq/dk/dv match the f32 oracle and the XLA
    ``sdpa`` path. Float32 inputs keep float32 operands and the 2e-5 /
    1e-4 tolerances used above. Bfloat16 inputs enter the MXU as bf16
    with f32 accumulation, as the XLA path's do: each result lies within
    1e-2 of the oracle's norm and within twice the XLA path's own error."""
    _check_training_tiles(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_training_tiles_match_oracle_at_gqa_7(dtype):
    """The same at Qwen2-VL-7B's GQA: 7 query heads to each KV head
    (28 / 4 as published), which the dK/dV kernel folds in VMEM."""
    _check_training_tiles(dtype, H=7, Hkv=1)


def _check_training_tiles(dtype, **heads):
    from repro.kernels.ops import flash_blocks
    from repro.models import layers as L
    q, k, v, w, bits, pos = _training_row(dtype, **heads)
    T, H, Hkv = q.shape[1], q.shape[2], k.shape[2]
    bq, bk = flash_blocks(T, T)
    assert T % bq and T % bk          # the tiles pad this row

    def kernel(q, k, v):
        return bam_attention(q, k, v, bits, bits, pos, pos,
                             impl="bam_interpret", block_q=bq, block_k=bk)

    def xla(q, k, v):
        mask = bam.allowed_mask(bits, bits, pos, pos)[:, None]
        n_rep = H // Hkv
        return L.sdpa(q, L.repeat_kv(k, n_rep), L.repeat_kv(v, n_rep), mask)

    def oracle(q, k, v):
        return bam_attention_ref(q, k, v, bits, bits, pos, pos)

    def fwd_bwd(f, *args):
        out, vjp = jax.vjp(lambda *a: f(*a).astype(jnp.float32), *args)
        return (out, *vjp(w))

    got = fwd_bwd(kernel, q, k, v)
    via_xla = fwd_bwd(xla, q, k, v)
    with jax.default_matmul_precision("highest"):
        want = fwd_bwd(oracle, *(x.astype(jnp.float32) for x in (q, k, v)))
    if dtype == jnp.float32:
        for i, (a, b, c) in enumerate(zip(got, want, via_xla)):
            tol = 2e-5 if i == 0 else 1e-4
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=tol, rtol=tol)
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=tol, rtol=tol)
        return
    for a, b, c in zip(got, want, via_xla):
        err, err_xla = _rel(a, b), _rel(c, b)
        assert err < 1e-2 and err < 2 * err_xla, (err, err_xla)
        assert _rel(a, c) < 1e-2


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("window", [0, 5])
def test_mask_words_match_allowed_mask(seed, window):
    """The training kernels' per-token word rewrite of the mask equals
    ``bam.allowed_mask`` on arbitrary bitfields — random attends sets,
    modalities beyond the attends set's reach, instances, padding,
    positions — in both tile orientations."""
    from repro.kernels.bam_attention import _col, _mask_words, _row, \
        _words_mask
    rng = np.random.default_rng(seed)
    T = 96
    bits = (rng.integers(0, 1 << 16, T)
            | rng.integers(0, 20, T) << bam.MOD_SHIFT
            | rng.integers(0, 3, T) << bam.INST_SHIFT).astype(np.uint32)
    bits[rng.random(T) < 0.3] = bam.text_token((1, 2))
    bits[rng.random(T) < 0.1] = 0
    pos = rng.integers(-1, 40, T).astype(np.int32)
    want = np.asarray(bam.allowed_mask(bits[None], bits[None], pos[None],
                                       pos[None], window))[0]
    q_words, k_words = _mask_words(jnp.asarray(bits)[None],
                                   jnp.asarray(bits)[None],
                                   jnp.asarray(pos)[None],
                                   jnp.asarray(pos)[None], window)
    got = _words_mask([_col(w) for w in q_words], [_row(w) for w in k_words])
    np.testing.assert_array_equal(np.asarray(got), want)
    got_t = _words_mask([_row(w) for w in q_words],
                        [_col(w) for w in k_words])
    np.testing.assert_array_equal(np.asarray(got_t), want.T)
    assert 0 < want.sum() < want.size
