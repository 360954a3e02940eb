"""Multimodality-aware context parallelism tests (paper §4.3/§5.3).

Single-device paths run in-process; multi-rank equivalence runs in a
subprocess with a forced host device count."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.core import bam, context_parallel as cp
from repro.core import distribution as dist
from repro.models.layers import sdpa

from .helpers import host_mesh, subprocess_test


def make_case(seed=0, B=2, T=64, H=4, hd=16):
    key = jax.random.PRNGKey(seed)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, T, H, hd))
               for i in range(3))
    segs = [("text", 0, T // 4), ("mod", 1, T // 4), ("text", 0, T // 4),
            ("mod", 2, T // 8), ("text", 0, T - 7 * (T // 8))]
    bits_np, pos_np = bam.build_sample_bits(segs, T)
    bits = jnp.broadcast_to(jnp.asarray(bits_np)[None], (B, T))
    pos = jnp.broadcast_to(jnp.asarray(pos_np)[None], (B, T))
    return q, k, v, bits, pos, bits_np, pos_np


@pytest.mark.parametrize("method", ["allgather", "ring"])
def test_cp_single_rank_equals_sdpa(method):
    q, k, v, bits, pos, *_ = make_case()
    mask = bam.allowed_mask(bits, bits, pos, pos)[:, None]
    ref = sdpa(q, k, v, mask)
    mesh = jax.make_mesh((1,), ("cp",),
                         axis_types=(AxisType.Auto,))
    out = cp.cp_attention(mesh, "cp", q, k, v, bits, bits, pos, pos,
                          method=method)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


@pytest.mark.parametrize("method", ["allgather", "ring"])
def test_cp_kernel_stats_path_equals_reference(method):
    """CP bodies on the Pallas stats kernel (impl="bam_interpret"):
    the per-step [B,H,Tq,Tk] logits never materialize, the combined
    output must still equal the dense oracle."""
    q, k, v, bits, pos, *_ = make_case()
    ref = cp.cp_reference(q, k, v, bits, bits, pos, pos)
    mesh = jax.make_mesh((1,), ("cp",),
                         axis_types=(AxisType.Auto,))
    out = cp.cp_attention(mesh, "cp", q, k, v, bits, bits, pos, pos,
                          method=method, impl="bam_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_cp_reference_equals_sdpa():
    q, k, v, bits, pos, *_ = make_case(1)
    mask = bam.allowed_mask(bits, bits, pos, pos)[:, None]
    np.testing.assert_allclose(
        np.asarray(cp.cp_reference(q, k, v, bits, bits, pos, pos)),
        np.asarray(sdpa(q, k, v, mask)), atol=2e-6)


def test_plan_permutation_roundtrip():
    _, _, _, _, _, bits_np, pos_np = make_case(2)
    plan = dist.plan_tokens(bits_np, pos_np, 4, block_size=8, method="lpt")
    perm = cp.plan_permutation(plan, 64)
    inv = cp.invert_perm(perm)
    x = np.arange(64)
    np.testing.assert_array_equal(x[perm][inv], x)
    assert sorted(perm) == list(range(64))


@pytest.mark.parametrize("T,G,bs", [(60, 8, 8), (50, 4, 8), (33, 2, 4),
                                    (70, 3, 8)])
@pytest.mark.parametrize("planner", ["lpt", "zigzag", "ring"])
def test_plan_permutation_nondivisible_seq(T, G, bs, planner):
    """Regression: the rebalance path used to DROP up to G-1 trailing
    tokens whenever seq_len % num_ranks != 0 (target = seq_len // G and
    the leftover `extra` blocks were never re-appended). The result
    must always be a true permutation of arange(seq_len)."""
    bits_np, pos_np = bam.build_sample_bits([("text", 0, T)], T)
    plan = dist.plan_tokens(bits_np, pos_np, G, block_size=bs,
                            method=planner)
    perm = cp.plan_permutation(plan, T)
    assert sorted(perm.tolist()) == list(range(T))
    inv = cp.invert_perm(perm)
    np.testing.assert_array_equal(np.arange(T)[perm][inv], np.arange(T))
    # the layout is rank-contiguous with counts differing by at most
    # one (extras on the leading ranks), and each rank's segment keeps
    # its own assigned tokens first — rebalancing only trims tails and
    # appends other ranks' leftovers
    base, rem = divmod(T, G)
    targets = [base + (1 if g < rem else 0) for g in range(G)]
    own = [s[s < T] for s in plan.rank_token_slices()]
    off = 0
    for g in range(G):
        seg = perm[off:off + targets[g]]
        keep = min(targets[g], len(own[g]))
        np.testing.assert_array_equal(seg[:keep], own[g][:keep])
        off += targets[g]
    assert off == T


def test_plan_permutation_uncovered_seq_raises():
    """seq_len beyond the plan's block coverage must fail loudly."""
    bits_np, pos_np = bam.build_sample_bits([("text", 0, 32)], 32)
    plan = dist.plan_tokens(bits_np, pos_np, 2, block_size=8)
    with pytest.raises(ValueError, match="covers 32 tokens"):
        cp.plan_permutation(plan, 48)


def test_cp_attention_unknown_method_raises():
    q, k, v, bits, pos, *_ = make_case()
    mesh = jax.make_mesh((1,), ("cp",),
                         axis_types=(AxisType.Auto,))
    with pytest.raises(ValueError, match="allgather.*ring"):
        cp.cp_attention(mesh, "cp", q, k, v, bits, bits, pos, pos,
                        method="butterfly")


def test_simulate_rank_workloads_matches_loop():
    """The vectorized scatter-add must equal the per-block Python loop
    it replaced — including a partial trailing block."""
    from repro.data.synthetic import random_multimodal_bits
    for T, G, bs, window in [(300, 4, 32, 0), (256, 8, 16, 7)]:
        bits, pos = random_multimodal_bits(T, "ee", seed=1)
        bits, pos = bits[:T], pos[:T]
        plan = dist.plan_tokens(bits, pos, G, block_size=bs)
        W = bam.token_workload(bits, pos, window)
        loop = np.zeros(plan.num_ranks)
        for g, blocks in enumerate(plan.per_rank_blocks):
            for b in blocks:
                loop[g] += W[b * bs:(b + 1) * bs].sum()
        np.testing.assert_allclose(
            cp.simulate_rank_workloads(plan, bits, pos, window), loop)


@pytest.mark.parametrize("method", ["allgather", "ring"])
@pytest.mark.parametrize("planner", ["lpt", "zigzag", "random"])
@subprocess_test(4)
def test_cp_multirank_equivalence(method, planner):
    """4 CP ranks × every planner must reproduce full attention exactly
    (the distribution is a permutation, never an approximation)."""
    q, k, v, bits, pos, bits_np, pos_np = make_case()
    mask = bam.allowed_mask(bits, bits, pos, pos)[:, None]
    ref = sdpa(q, k, v, mask)
    plan = dist.plan_tokens(bits_np, pos_np, 4, block_size=8,
                            method=planner)
    perm = cp.plan_permutation(plan, 64)
    inv = cp.invert_perm(perm)
    with host_mesh(4, ("cp",)) as mesh:
        args = [jnp.take(a, perm, axis=1) for a in (q, k, v)]
        bp = jnp.take(bits, perm, axis=1)
        pp_ = jnp.take(pos, perm, axis=1)
        out = cp.cp_attention(mesh, "cp", *args, bp, bp, pp_, pp_,
                              method=method)
    out = jnp.take(out, inv, axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-6)


@pytest.mark.parametrize("method", ["allgather", "ring"])
@subprocess_test(2)
def test_cp_multirank_kernel_stats_path(method):
    """Multi-rank CP on the kernel stats path: ring-step / all-gather
    combination of Pallas partials reproduces full attention."""
    q, k, v, bits, pos, bits_np, pos_np = make_case(B=1, H=2)
    ref = cp.cp_reference(q, k, v, bits, bits, pos, pos)
    plan = dist.plan_tokens(bits_np, pos_np, 2, block_size=8,
                            method="lpt")
    perm = cp.plan_permutation(plan, 64)
    inv = cp.invert_perm(perm)
    with host_mesh(2, ("cp",)) as mesh:
        args = [jnp.take(a, perm, axis=1) for a in (q, k, v)]
        bp = jnp.take(bits, perm, axis=1)
        pp_ = jnp.take(pos, perm, axis=1)
        out = cp.cp_attention(mesh, "cp", *args, bp, bp, pp_, pp_,
                              method=method, impl="bam_interpret")
    out = jnp.take(out, inv, axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# Differentiable CP: jax.grad through the bodies must match jax.grad of
# the collective-free oracle (combining-aware custom_vjp; the kernel
# path runs the fused per-chunk flash backward, allgather reduce-
# scatters dK/dV, ring runs the reverse ring)
# ---------------------------------------------------------------------------

def _grads_of(fn, q, k, v):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                    argnums=(0, 1, 2))(q, k, v)


def _gqa_case(seed=0, B=1, T=64, H=4, Hkv=2, hd=16):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, T, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, hd))
    segs = [("text", 0, T // 4), ("mod", 1, T // 4), ("text", 0, T // 4),
            ("mod", 2, T // 8), ("text", 0, T - 7 * (T // 8))]
    bits_np, pos_np = bam.build_sample_bits(segs, T)
    bits = jnp.broadcast_to(jnp.asarray(bits_np)[None], (B, T))
    pos = jnp.broadcast_to(jnp.asarray(pos_np)[None], (B, T))
    return q, k, v, bits, pos


@pytest.mark.parametrize("method", ["allgather", "ring"])
@pytest.mark.parametrize("impl", ["xla", "bam_interpret"])
def test_cp_grads_match_reference(method, impl):
    q, k, v, bits, pos, *_ = make_case()
    mesh = jax.make_mesh((1,), ("cp",),
                         axis_types=(AxisType.Auto,))
    g_cp = _grads_of(
        lambda q, k, v: cp.cp_attention(mesh, "cp", q, k, v, bits, bits,
                                        pos, pos, method=method, impl=impl,
                                        block_q=16, block_k=16), q, k, v)
    g_ref = _grads_of(
        lambda q, k, v: cp.cp_reference(q, k, v, bits, bits, pos, pos),
        q, k, v)
    for a, b in zip(g_cp, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("method", ["allgather", "ring"])
@pytest.mark.parametrize("variant", ["softcap", "window", "gqa"])
def test_cp_grads_variants(method, variant):
    """softcap chain rule, sliding window, and GQA head-folding all
    survive the CP backward on the kernel path."""
    Hkv = 2 if variant == "gqa" else 4
    kw = {"softcap": {"softcap": 30.0}, "window": {"window": 9},
          "gqa": {}}[variant]
    q, k, v, bits, pos = _gqa_case(seed=1, Hkv=Hkv)
    mesh = jax.make_mesh((1,), ("cp",),
                         axis_types=(AxisType.Auto,))
    g_cp = _grads_of(
        lambda q, k, v: cp.cp_attention(mesh, "cp", q, k, v, bits, bits,
                                        pos, pos, method=method,
                                        impl="bam_interpret", block_q=16,
                                        block_k=16, **kw), q, k, v)
    g_ref = _grads_of(
        lambda q, k, v: cp.cp_reference(q, k, v, bits, bits, pos, pos,
                                        **kw), q, k, v)
    for a, b in zip(g_cp, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("method", ["allgather", "ring"])
@pytest.mark.parametrize("impl", ["xla", "bam_interpret"])
def test_cp_grads_padding_exact_zero(method, impl):
    """bits=0 tokens must receive exactly-zero dQ/dK/dV through CP."""
    B, T, H, hd = 1, 64, 2, 16
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, T, H, hd))
               for i in range(3))
    bits_np, pos_np = bam.build_sample_bits(
        [("text", 0, 24), ("mod", 1, 8), ("text", 0, 16)], T)  # 16 padded
    bits = jnp.asarray(bits_np)[None]
    pos = jnp.asarray(pos_np)[None]
    mesh = jax.make_mesh((1,), ("cp",),
                         axis_types=(AxisType.Auto,))
    dq, dk, dv = _grads_of(
        lambda q, k, v: cp.cp_attention(mesh, "cp", q, k, v, bits, bits,
                                        pos, pos, method=method, impl=impl,
                                        block_q=16, block_k=16), q, k, v)
    assert not np.asarray(dq)[:, 48:].any()
    assert not np.asarray(dk)[:, 48:].any()
    assert not np.asarray(dv)[:, 48:].any()
    g_ref = _grads_of(
        lambda q, k, v: cp.cp_reference(q, k, v, bits, bits, pos, pos),
        q, k, v)
    for a, b in zip((dq, dk, dv), g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("method", ["allgather", "ring"])
def test_cp_backward_no_quadratic_intermediate(method):
    """The traced CP backward on the kernel path must not allocate any
    O(Tq·Tk) f32 array — residuals are (out, lse) rows and the fused
    chunk backwards only ever hold [block_q, block_k] tiles. (The
    jaxpr walk lives in repro.analysis.jaxprlint, promoted from this
    file.)"""
    from repro.analysis.jaxprlint import quadratic_f32 as _quadratic_f32
    T = 64
    q, k, v, bits, pos, *_ = make_case(B=1, H=2)
    mesh = jax.make_mesh((1,), ("cp",),
                         axis_types=(AxisType.Auto,))

    def loss(impl):
        def f(q, k, v):
            return jnp.sum(cp.cp_attention(
                mesh, "cp", q, k, v, bits, bits, pos, pos, method=method,
                impl=impl, block_q=16, block_k=16) ** 2)
        return f

    jaxpr = jax.make_jaxpr(jax.grad(loss("bam_interpret"),
                                    argnums=(0, 1, 2)))(q, k, v)
    assert not _quadratic_f32(jaxpr, T), _quadratic_f32(jaxpr, T)
    # sanity: the XLA body DOES trace a [T,T] intermediate, so the
    # assertion above is actually discriminating
    jaxpr_x = jax.make_jaxpr(jax.grad(loss("xla"),
                                      argnums=(0, 1, 2)))(q, k, v)
    assert _quadratic_f32(jaxpr_x, T)


@pytest.mark.parametrize("method", ["allgather", "ring"])
@subprocess_test(2)
def test_cp_multirank_grads_kernel_path(method):
    """2 CP ranks on the kernel path: grads through the plan-permuted
    CP attention (reduce-scatter / reverse-ring backward collectives)
    must match the single-device oracle's grads."""
    q, k, v, bits, pos, bits_np, pos_np = make_case(B=1, H=2)
    plan = dist.plan_tokens(bits_np, pos_np, 2, block_size=8,
                            method="lpt")
    perm = jnp.asarray(cp.plan_permutation(plan, 64))
    bp = jnp.take(bits, perm, axis=1)
    pp_ = jnp.take(pos, perm, axis=1)
    with host_mesh(2, ("cp",)) as mesh:

        def loss_cp(q, k, v):
            qp, kp, vp = (jnp.take(a, perm, axis=1) for a in (q, k, v))
            out = cp.cp_attention(mesh, "cp", qp, kp, vp, bp, bp, pp_,
                                  pp_, method=method,
                                  impl="bam_interpret",
                                  block_q=16, block_k=16)
            return jnp.sum(out ** 2)   # permutation-invariant scalar

        def loss_ref(q, k, v):
            return jnp.sum(cp.cp_reference(q, k, v, bits, bits, pos,
                                           pos) ** 2)

        g1 = jax.grad(loss_cp, (0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_cp_train_step_contextplan_layout():
    """End-to-end: a typed ContextPlan drives a CP train step — loss
    and parameter grads match the plain (unpermuted, non-CP) step."""
    from repro.configs.base import get_config
    from repro.models import api
    from repro.optim import optimizer as opt
    from repro.parallel import plan_context
    from repro.training import steps

    cfg = get_config("qwen3-1.7b", reduced=True)
    T, B = 32, 2
    bits_np, pos_np = bam.build_sample_bits(
        [("text", 0, 8), ("mod", 1, 8), ("text", 0, 16)], T)
    ctx = plan_context(bits_np, pos_np, 2, block_size=4, method="lpt")
    layout = ctx.apply(T)
    assert sorted(layout["perm"].tolist()) == list(range(T))
    mesh = jax.make_mesh((1,), ("cp",),
                         axis_types=(AxisType.Auto,))

    params = api.init(jax.random.PRNGKey(0), cfg)
    ocfg = opt.AdamWConfig(lr=1e-2, warmup_steps=0, schedule="constant")
    state = opt.init(ocfg, params)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                              jnp.int32),
        "positions": jnp.broadcast_to(jnp.asarray(pos_np)[None], (B, T)),
        "bits": jnp.broadcast_to(jnp.asarray(bits_np)[None], (B, T)),
        "valid": jnp.broadcast_to(jnp.asarray(bits_np != 0)[None], (B, T)),
    }
    # a 2-rank plan on a 1-device mesh is exact but unbalanced — the
    # step must say so
    with pytest.warns(UserWarning, match="balanced for 2 ranks"):
        step_cp = jax.jit(steps.make_cp_train_step(cfg, layout, mesh,
                                                   ocfg))
    _, _, m_cp = step_cp(params, state, batch)
    _, _, m_ref = jax.jit(steps.make_train_step(cfg, ocfg))(
        params, state, batch)
    assert abs(float(m_cp["loss"]) - float(m_ref["loss"])) < 1e-4
    assert abs(float(m_cp["grad_norm"]) - float(m_ref["grad_norm"])) < 1e-3

    # grads themselves agree leaf-by-leaf (the step's value_and_grad,
    # re-derived here; Adam's 1/sqrt(v) would amplify float noise)
    cp_cfg = cfg.replace(cp_mesh=mesh, cp_axis="cp")
    perm = jnp.asarray(layout["perm"])
    pb = {k: jnp.take(x, perm, axis=1) for k, x in batch.items()}
    g_cp = jax.grad(lambda p: steps.make_loss_fn(cp_cfg)(p, pb)[0])(params)
    g_ref = jax.grad(lambda p: steps.make_loss_fn(cfg)(p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g_cp), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3)


def test_cp_train_step_missing_bits_raises():
    """A CP step on a bits-less batch would silently replicate dense
    attention on every device — it must refuse at trace time."""
    from repro.configs.base import get_config
    from repro.optim import optimizer as opt
    from repro.parallel import plan_context
    from repro.training import steps
    cfg = get_config("qwen3-1.7b", reduced=True)
    T, B = 32, 1
    bits_np, pos_np = bam.build_sample_bits([("text", 0, T)], T)
    layout = plan_context(bits_np, pos_np, 1, block_size=4).apply(T)
    mesh = jax.make_mesh((1,), ("cp",),
                         axis_types=(AxisType.Auto,))
    step = steps.make_cp_train_step(cfg, layout, mesh)
    params = {}
    batch = {"tokens": jnp.zeros((B, T), jnp.int32),
             "labels": jnp.zeros((B, T), jnp.int32),
             "positions": jnp.broadcast_to(jnp.asarray(pos_np)[None],
                                           (B, T))}
    with pytest.raises(ValueError, match="batch\\['bits'\\]"):
        step(params, {}, batch)


def test_cp_train_step_indivisible_mesh_raises():
    from repro.configs.base import get_config
    from repro.parallel import plan_context
    from repro.training import steps
    cfg = get_config("qwen3-1.7b", reduced=True)
    T = 30
    bits_np, pos_np = bam.build_sample_bits([("text", 0, T)], T)
    ctx = plan_context(bits_np, pos_np, 4, block_size=4, method="lpt")
    layout = ctx.apply(T)

    class FakeMesh:
        shape = {"cp": 4}

    with pytest.raises(ValueError, match="not divisible"):
        steps.make_cp_train_step(cfg, layout, FakeMesh())


def test_rank_workload_balance_lpt_vs_zigzag():
    """The §6.5 claim at planner level: LPT's max-rank workload is no
    worse than zigzag's on multimodal masks (usually strictly better)."""
    from repro.data.synthetic import random_multimodal_bits
    worse = 0
    for seed in range(6):
        bits, pos = random_multimodal_bits(2048, "ee", seed=seed)
        pl_l = dist.plan_tokens(bits, pos, 8, 32, method="lpt")
        pl_z = dist.plan_tokens(bits, pos, 8, 32, method="zigzag")
        l_max = cp.simulate_rank_workloads(pl_l, bits, pos).max()
        z_max = cp.simulate_rank_workloads(pl_z, bits, pos).max()
        if l_max > z_max + 1e-6:
            worse += 1
    assert worse == 0
