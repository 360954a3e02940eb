"""The plain reference against the program's ``make_mllm_train_step`` at
test widths in float32, and the weight generator against the program's
parameter layout."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import compare
import reference
import sut
import traffic as traffic_mod
from conftest import small_config, small_traffic


def _program_steps(cfg, tr, seed, steps_n):
    from repro.optim import optimizer as opt
    from repro.training import steps
    mllm = sut.build_mllm(cfg, tr)
    spec = reference.Spec.from_config(cfg)
    params = reference.init_params(reference.base_key(seed), spec,
                                   jnp.float32)
    o = tr["optimizer"]
    ocfg = opt.AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                           weight_decay=o["weight_decay"],
                           grad_clip=o["grad_clip"],
                           warmup_steps=o["warmup_steps"],
                           total_steps=o["total_steps"])
    fm = mllm.frozen_mask(params)
    state = opt.init(ocfg, params, fm)
    step, _ = steps.make_mllm_train_step(mllm, ocfg)
    step = jax.jit(step)
    w0 = np.asarray(params["encoders"]["vision"]["projector"]["w1"],
                    np.float64)
    losses, grad, gnorm = [], None, None
    for i, b in enumerate(traffic_mod.first_batches(tr, cfg, seed, steps_n)):
        params, state, m = step(params, state,
                                {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(m["loss"]))
        if i == 0:
            gnorm = float(m["grad_norm"])
            grad = np.asarray(
                state["m"]["encoders"]["vision"]["projector"]["w1"],
                np.float64) / (1 - ocfg.b1)
    w = np.asarray(params["encoders"]["vision"]["projector"]["w1"],
                   np.float64)
    key = "encoders/vision/projector/w1"
    return {"losses": losses, "gnorm": gnorm, "grad": {key: grad},
            "change": {key: w - w0}}


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 17])
def test_reference_follows_the_program_in_float32(seed):
    cfg = small_config(torch_dtype="float32")
    tr = small_traffic()
    spec = reference.Spec.from_config(cfg)
    with jax.default_matmul_precision("highest"):
        prog = _program_steps(cfg, tr, seed, 3)
    params = reference.init_params(reference.base_key(seed), spec,
                                   jnp.float32)
    ref = reference.train_steps(
        spec, reference.Optim.from_traffic(tr), params,
        traffic_mod.first_batches(tr, cfg, seed, 3),
        image_at=tr["image_at"])
    got = compare.numbers(prog, ref)
    assert got["loss_gap"] < 1e-5, got
    assert got["grad_gap"] < 1e-4, got
    assert got["grad_diff"] < 1e-4, got
    assert got["gnorm_gap"] < 1e-4, got
    assert got["change_gap"] < 1e-3, got
    assert got["change_diff"] < 1e-3, got
    assert ref["losses"][0] == pytest.approx(np.log(512), rel=0.05)


def test_weights_match_the_program_layout():
    cfg = small_config()
    tr = small_traffic()
    mllm = sut.build_mllm(cfg, tr)
    spec = reference.Spec.from_config(cfg)
    key = reference.base_key(5)
    want = jax.eval_shape(mllm.init, key)
    have = jax.eval_shape(
        lambda k: reference.init_params(k, spec, jnp.bfloat16), key)
    assert jax.tree.structure(have) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(have), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_weights_are_a_function_of_the_seed():
    spec = reference.Spec.from_config(small_config())
    a = reference.init_params(reference.base_key(2 ** 31 + 3), spec)
    b = reference.init_params(reference.base_key(2 ** 31 + 3), spec)
    c = reference.init_params(reference.base_key(3), spec)
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[-1], lc[-1])
    gains = a["encoders"]["vision"]["module"]["final_ln"]["w"]
    assert abs(float(jnp.mean(gains.astype(jnp.float32))) - 1) < 0.01
