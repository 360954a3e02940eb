"""The Pallas kernels of the training and serving paths, compiled ahead
of time for a described TPU v5e at LLM-S head widths (H=16, Hkv=4,
hd=128, T=2048), and the training path's kernels at the tiles
``ops.flash_blocks`` picks for the benchmark cells' attention (H=16,
Hkv=8, hd=128; T=4096, and T=1600 padded; and the pipeline cell's
H=28, Hkv=4 at T=1600). Nothing runs: Mosaic
compiles each kernel for a chip that is described, not attached, and
refuses what the chip would refuse (block tiling, VMEM budget) — which
interpret mode never does.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
compiler's library, and the test workers import every test file.
"""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

B, T, H, HKV, HD = 1, 2048, 16, 4, 128
PAGES, PAGE_SIZE, DECODE_BATCH, DECODE_STEPS = 256, 16, 8, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compilation_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _block_map():
    from repro.core import bam
    bits, pos = bam.build_sample_bits(
        [("text", 0, 512), ("mod", 1, 576), ("text", 0, 512)], T)
    return bam.build_block_map(bits, bits, pos, pos, 128, 128)


def _cases():
    from repro.kernels.bam_attention import (bam_flash_attention,
                                             bam_flash_attention_bwd)
    from repro.kernels.paged_decode import paged_decode_attention
    bm = _block_map()
    fwd = ("q", "kv", "kv", "bits", "bits", "pos", "pos")
    bwd = ("q", "kv", "kv", "q", "q", "lse", "bits", "bits", "pos", "pos")
    return {
        "fwd_residual_dense": (lambda *a: bam_flash_attention(
            *a, return_mode="residual"), fwd),
        "fwd_residual_compacted": (lambda *a: bam_flash_attention(
            *a, return_mode="residual", block_map=bm), fwd),
        "fwd_stats": (lambda *a: bam_flash_attention(
            *a, return_mode="stats"), fwd),
        "bwd_dense": (bam_flash_attention_bwd, bwd),
        "bwd_compacted": (lambda *a: bam_flash_attention_bwd(
            *a, block_map=bm), bwd),
        "paged_decode": (paged_decode_attention,
                         ("dq", "pages", "pages", "qbits", "qpos",
                          "pbits", "ppos", "steps")),
    }


@pytest.mark.parametrize("case", ["fwd_residual_dense",
                                  "fwd_residual_compacted", "fwd_stats",
                                  "bwd_dense", "bwd_compacted",
                                  "paged_decode"])
def test_kernel_compiles_for_v5e(case, one_chip, no_compilation_cache):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = {
        "q": s((B, T, H, HD), jnp.bfloat16),
        "kv": s((B, T, HKV, HD), jnp.bfloat16),
        "bits": s((B, T), jnp.uint32), "pos": s((B, T), jnp.int32),
        "lse": s((B, H, T), jnp.float32),
        "dq": s((DECODE_BATCH, H, HD), jnp.bfloat16),
        "pages": s((PAGES, HKV, PAGE_SIZE, HD), jnp.bfloat16),
        "qbits": s((DECODE_BATCH, 1), jnp.uint32),
        "qpos": s((DECODE_BATCH, 1), jnp.int32),
        "pbits": s((PAGES, PAGE_SIZE), jnp.uint32),
        "ppos": s((PAGES, PAGE_SIZE), jnp.int32),
        "steps": tuple(s((DECODE_STEPS,), jnp.int32) for _ in range(5)),
    }
    fn, names = _cases()[case]
    compiled = jax.jit(fn).lower(*(shapes[n] for n in names)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem is not None and np.isfinite(mem.temp_size_in_bytes)


#: rows, merged length, query heads and KV heads of each cell's attention:
#: Qwen3-1.7B's 16 / 8 in the one-chip cells, Qwen2-VL-7B's 28 / 4 (GQA
#: 7:1) in the pipeline cell, one microbatch row a call
CELL_ROWS = {"doc-4096": (2, 4096, 16, 8), "align-1600": (5, 1600, 16, 8),
             "pp4-align-1600": (1, 1600, 28, 4)}


@pytest.mark.parametrize("case", ["fwd_residual", "bwd_dense", "fwd_stats"])
@pytest.mark.parametrize("cell", sorted(CELL_ROWS))
def test_training_kernel_compiles_for_v5e(case, cell, one_chip,
                                          no_compilation_cache):
    from repro.kernels.bam_attention import (bam_flash_attention,
                                             bam_flash_attention_bwd)
    from repro.kernels.ops import flash_blocks
    b, t, h, hkv = CELL_ROWS[cell]
    bq, bk = flash_blocks(t, t)
    tq, tk = -(-t // bq) * bq, -(-t // bk) * bk

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, kv = s((b, tq, h, HD), jnp.bfloat16), s((b, tk, hkv, HD),
                                               jnp.bfloat16)
    meta = (s((b, tq), jnp.uint32), s((b, tk), jnp.uint32),
            s((b, tq), jnp.int32), s((b, tk), jnp.int32))
    tiles = dict(block_q=bq, block_k=bk)
    if case == "bwd_dense":
        fn = functools.partial(bam_flash_attention_bwd, **tiles)
        args = (q, kv, kv, q, q, s((b, h, tq), jnp.float32), *meta)
    else:
        fn = functools.partial(bam_flash_attention, **tiles,
                               return_mode=case.split("_")[1])
        args = (q, kv, kv, *meta)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem is not None and np.isfinite(mem.temp_size_in_bytes)
