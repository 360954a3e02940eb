"""Per-architecture smoke tests (deliverable f) + model-math
consistency tests (decode vs forward, chunked vs quadratic scans)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, list_archs
from repro.launch import specs as S
from repro.models import api, whisper
from repro.optim import optimizer as opt
from repro.training import steps

ARCHS = list_archs()
B, T = 2, 32


def tiny_batch(cfg, seed=0, seq=T, batch=B):
    return S.concrete_batch(cfg, seq, batch, seed=seed)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward(arch):
    cfg = get_config(arch, reduced=True)
    params = api.init(jax.random.PRNGKey(0), cfg)
    seq = 64 if cfg.family == "vlm" else T   # room for the patch block
    batch = tiny_batch(cfg, seq=seq)
    logits, aux = api.forward(params, cfg, batch)
    assert logits.shape == (B, batch["tokens"].shape[1], cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_config(arch, reduced=True)
    params = api.init(jax.random.PRNGKey(0), cfg)
    ocfg = opt.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    state = opt.init(ocfg, params)
    step = jax.jit(steps.make_train_step(cfg, ocfg))
    seq = 64 if cfg.family == "vlm" else T
    batch = tiny_batch(cfg, seq=seq)
    p2, s2, metrics = step(params, state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually moved
    delta = max(float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree.leaves(p2), jax.tree.leaves(params)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_config(arch, reduced=True)
    params = api.init(jax.random.PRNGKey(0), cfg)
    cache = api.init_cache(cfg, B, T)
    if cfg.family == "audio":
        frames = jax.random.normal(
            jax.random.PRNGKey(1), (B, cfg.encdec.encoder_seq, cfg.d_model))
        cache = whisper.prefill_cross(params, cfg, cache, frames)
    batch = {"tokens": jnp.ones((B, 1), jnp.int32),
             "positions": jnp.full((B, 1), 3, jnp.int32)}
    logits, cache2 = api.decode_step(params, cfg, cache, batch)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "gemma2-9b",
                                  "starcoder2-7b", "qwen2.5-14b",
                                  "qwen2-moe-a2.7b", "zamba2-2.7b",
                                  "xlstm-125m"])
def test_decode_matches_forward(arch):
    """Prefill-free consistency: feeding tokens one-by-one through
    decode_step must match the parallel forward's logits."""
    cfg = get_config(arch, reduced=True)
    if cfg.family == "moe":
        # dense backend for exactness
        assert cfg.moe.backend == "dense"
    params = api.init(jax.random.PRNGKey(0), cfg)
    n = 8
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, n)), jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    full_logits, _ = api.forward(
        params, cfg, {"tokens": toks, "positions": pos})

    cache = api.init_cache(cfg, 1, n)
    got = []
    for i in range(n):
        batch = {"tokens": toks[:, i:i + 1],
                 "positions": jnp.full((1, 1), i, jnp.int32)}
        logits, cache = api.decode_step(params, cfg, cache, batch)
        got.append(logits[:, 0])
    got = jnp.stack(got, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full_logits),
                               atol=2e-3, rtol=2e-3)


def test_mamba_chunked_vs_step():
    """SSD chunked scan == recurrent single-step scan."""
    from repro.configs.base import SSMConfig
    from repro.models.mamba2 import ssd_chunked, ssd_step
    rng = jax.random.PRNGKey(0)
    Bs, T_, nh, hd, ds = 2, 16, 3, 8, 4
    xh = jax.random.normal(jax.random.fold_in(rng, 0), (Bs, T_, nh, hd))
    Bm = jax.random.normal(jax.random.fold_in(rng, 1), (Bs, T_, ds))
    Cm = jax.random.normal(jax.random.fold_in(rng, 2), (Bs, T_, ds))
    dt = jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(rng, 3), (Bs, T_, nh)))
    log_a = -jnp.exp(
        jax.random.normal(jax.random.fold_in(rng, 4), (Bs, T_, nh)) * 0.1
    ) * dt
    y_c, h_c = ssd_chunked(xh, Bm, Cm, dt, log_a, chunk=4)
    h = jnp.zeros((Bs, nh, hd, ds))
    ys = []
    for t in range(T_):
        y, h = ssd_step(xh[:, t:t+1], Bm[:, t:t+1], Cm[:, t:t+1],
                        dt[:, t:t+1], log_a[:, t:t+1], h)
        ys.append(y[:, 0])
    y_s = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("seed", range(3))
def test_mlstm_chunked_vs_parallel(seed):
    """Chunkwise mLSTM == stabilized quadratic oracle."""
    from repro.models.xlstm import mlstm_chunked, mlstm_parallel
    rng = jax.random.PRNGKey(seed)
    Bs, T_, nh, hd = 2, 24, 2, 8
    q = jax.random.normal(jax.random.fold_in(rng, 0), (Bs, T_, nh, hd))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (Bs, T_, nh, hd))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (Bs, T_, nh, hd))
    log_i = jax.random.normal(jax.random.fold_in(rng, 3), (Bs, T_, nh))
    log_f = jax.nn.log_sigmoid(
        jax.random.normal(jax.random.fold_in(rng, 4), (Bs, T_, nh)) + 2)
    ref = mlstm_parallel(q, k, v, log_i, log_f)
    got, _ = mlstm_chunked(q, k, v, log_i, log_f, chunk=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_moe_dense_vs_capacity_backend():
    """With ample capacity nothing is dropped -> backends agree."""
    from repro.configs.base import MoEConfig
    from repro.models import moe
    cfg = get_config("qwen2-moe-a2.7b", reduced=True)
    cfg_cap = cfg.replace(moe=MoEConfig(
        num_experts=4, top_k=2, num_shared_experts=1, d_expert=128,
        backend="capacity", capacity_factor=4.0))
    params = api.init(jax.random.PRNGKey(0), cfg)
    batch = tiny_batch(cfg)
    l1, _ = moe.forward(params, cfg, batch)
    l2, _ = moe.forward(params, cfg_cap, batch)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=1e-4, rtol=1e-4)


def test_gemma2_local_global_masks_differ():
    cfg = get_config("gemma2-9b", reduced=True)
    params = api.init(jax.random.PRNGKey(0), cfg)
    batch = tiny_batch(cfg)
    logits, _ = api.forward(params, cfg, batch)
    # all-global variant must differ (window is active on local layers)
    cfg2 = cfg.replace(sliding_window=0, local_global_pattern=0)
    logits2, _ = api.forward(params, cfg2, batch)
    assert float(jnp.abs(logits - logits2).max()) > 1e-6


def test_attn_impl_kernel_matches_xla():
    """cfg.attn_impl="bam_interpret" routes the transformer's attention
    through the fused Pallas path (forward AND backward) — logits and
    parameter grads must match the XLA path."""
    from repro.configs.base import ModelConfig
    from repro.core import bam
    from repro.models import transformer as tf
    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype="float32", remat=False,
                      seq_shard_activations=False)
    T_ = 40
    bits_np, pos_np = bam.build_sample_bits(
        [("text", 0, 10), ("mod", 1, 10), ("text", 0, 20)], T_)
    batch = {"tokens": jnp.zeros((2, T_), jnp.int32),
             "positions": jnp.broadcast_to(jnp.asarray(pos_np)[None],
                                           (2, T_)),
             "bits": jnp.broadcast_to(jnp.asarray(bits_np)[None], (2, T_))}
    params = tf.init(jax.random.PRNGKey(0), cfg)
    lx, _ = tf.forward(params, cfg, batch)
    lk, _ = tf.forward(params, cfg.replace(attn_impl="bam_interpret"), batch)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lx),
                               atol=2e-5, rtol=2e-5)

    def loss(p, c):
        lg, _ = tf.forward(p, c, batch)
        return jnp.sum(lg ** 2)

    g1 = jax.grad(loss)(params, cfg)
    g2 = jax.grad(loss)(params, cfg.replace(attn_impl="bam_interpret"))
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


def _routing_cfg(**kw):
    from repro.configs.base import ModelConfig
    return ModelConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                       num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                       dtype="float32", remat=False,
                       seq_shard_activations=False, **kw)


def _spy_kernel(monkeypatch, backend):
    """Report ``backend`` as JAX's default and record the impl of every
    call that reaches the fused kernel path (which returns zeros)."""
    from repro.kernels import ops
    calls = []

    def spy(q, k, v, *a, impl, **kw):
        calls.append(impl)
        return jnp.zeros_like(q)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(ops, "bam_attention", spy)
    return calls


@pytest.mark.parametrize("backend,attn_impl,with_bits,decode,expect", [
    ("tpu", "auto", True, False, "bam_kernel"),
    ("tpu", "auto", False, False, "xla"),
    ("tpu", "auto", True, True, "xla"),
    ("cpu", "auto", True, False, "xla"),
    ("tpu", "xla", True, False, "xla"),
    ("cpu", "bam_interpret", True, False, "bam_interpret"),
    ("cpu", "bam_kernel", True, False, "bam_kernel"),
])
def test_attention_routing(monkeypatch, backend, attn_impl, with_bits,
                           decode, expect):
    """By default the fused BAM kernel runs on TPU when the call carries
    BAM bits; XLA runs without bits, on the decode path and on other
    backends; an explicit ``attn_impl`` wins."""
    from repro.core import bam
    from repro.models import layers as L
    cfg = _routing_cfg(attn_impl=attn_impl)
    calls = _spy_kernel(monkeypatch, backend)
    T_ = 24
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T_, cfg.d_model))
    p = L.attn_init(jax.random.PRNGKey(1), cfg, jnp.float32)
    pos = jnp.arange(T_, dtype=jnp.int32)[None]
    bits = bam.causal_bits(1, T_) if with_bits else None
    kv_override = (lambda k, v: (k, v)) if decode else None
    assert L.resolve_attn_impl(cfg, bits, kv_override) == expect
    L.run_attention(p, cfg, x, q_pos=pos, bits=bits, kv_override=kv_override)
    assert calls == ([] if expect == "xla" else [expect])


@pytest.mark.parametrize("local_global,expect", [(0, ["bam_kernel"]),
                                                 (2, [])])
def test_transformer_routes_bits_to_kernel_on_tpu(monkeypatch, local_global,
                                                  expect):
    """The transformer's layers take the kernel on TPU when the batch
    carries bits, except under gemma2's traced local/global windows."""
    from repro.core import bam
    from repro.models import transformer as tf
    cfg = _routing_cfg(local_global_pattern=local_global,
                       sliding_window=8 if local_global else 0)
    params = tf.init(jax.random.PRNGKey(0), cfg)
    calls = _spy_kernel(monkeypatch, "tpu")
    T_ = 16
    batch = {"tokens": jnp.zeros((1, T_), jnp.int32),
             "positions": jnp.arange(T_, dtype=jnp.int32)[None],
             "bits": bam.causal_bits(1, T_)}
    tf.forward(params, cfg, batch)
    assert calls == expect


def test_vlm_mrope_text_equals_rope():
    """M-RoPE with equal (t,h,w) ids == standard RoPE (text tokens)."""
    from repro.models.layers import apply_mrope, apply_rope
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 2, 16))
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None], (2, 8))
    pos3 = jnp.broadcast_to(pos[None], (3, 2, 8))
    a = apply_rope(x, pos, 1e4)
    b = apply_mrope(x, pos3, (4, 2, 2), 1e4)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_param_count_analytics():
    """Analytic param model tracks actual init within 20% (used by the
    frozen-aware partitioner cost oracle)."""
    for arch in ("qwen3-1.7b", "xlstm-125m", "zamba2-2.7b"):
        cfg = get_config(arch, reduced=True)
        params = api.init(jax.random.PRNGKey(0), cfg)
        actual = sum(x.size for x in jax.tree.leaves(params))
        approx = cfg.param_count()
        assert 0.5 < approx / actual < 1.6, (arch, approx, actual)
