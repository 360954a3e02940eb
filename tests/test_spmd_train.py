"""Real-model SPMD training (repro.models.stages + launch/train
--spmd): the MLLM partitioned into typed per-stage callables must
compute — through the sequential replay AND the distributed shard_map
runner — exactly what the single-process ``make_mllm_train_step``
trainer computes, train only what the freeze config says is trainable,
and round-trip checkpoints across spmd/replay modes.

Multi-device tests re-exec themselves in a subprocess with a forced
host device count (tests/helpers.subprocess_test); under the
multi-device CI job (global XLA_FLAGS) they run in-process."""
import argparse
import functools
import json
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.modality_parallel import execute_schedule
from repro.data.synthetic import MultimodalDataset
from repro.optim import optimizer as opt
from repro.training import steps

from .helpers import subprocess_test

TEXT = 16
M = 2
BATCH = 2

LAUNCH_ARGS = ["--mllm", "vlm", "--reduced", "--steps", "4",
               "--seq", str(TEXT), "--batch", str(BATCH),
               "--microbatches", str(M), "--plan-devices", "3",
               "--log-every", "0"]


#: the cases the stage partition is checked at, as (paper MLLM, LLM
#: shape, train the LLM): the reduced paper VLM; Qwen2-VL's attention
#: shape (q/k/v biases, 7 query heads to each KV head; the reduced LLM's
#: head is already untied, with no QK-norm); the ft1 fine-tune, whose
#: trainable LLM stages take weight grads; and the VALM, whose two
#: frozen towers run on different ranks and both feed the LLM
CASES = {"paper": ("vlm", {}, False),
         "qwen2vl": ("vlm", {"num_heads": 7, "num_kv_heads": 1,
                             "head_dim": 32, "qkv_bias": True}, False),
         "ft1": ("vlm", {}, True),
         "valm": ("valm", {}, False)}


@functools.lru_cache(maxsize=None)
def tiny_case(case="paper"):
    """A real (reduced) paper MLLM + a searched plan + its SPMD executor
    contract — the fixture every test here partitions. Cached per
    process: the plan search and stage build are deterministic."""
    from repro.models.mllm import build_paper_mllm
    from repro.parallel import ClusterSpec, WorkloadShape, parallelize
    kind, shape, train_llm = CASES[case]
    mllm = build_paper_mllm(kind, reduced=True, text_len=TEXT)
    mllm.llm_cfg = mllm.llm_cfg.replace(**shape)
    assert not mllm.llm_cfg.tie_embeddings and not mllm.llm_cfg.use_qk_norm
    if train_llm:
        mllm.freeze("llm", module=False)
    plan = parallelize(
        mllm, ClusterSpec(num_devices=3),
        WorkloadShape(text_len=TEXT, num_microbatches=M,
                      microbatch_size=1, block_size=8))
    ex = plan.apply(mllm, text_len=TEXT, mode="spmd")
    return mllm, plan, ex


def tiny_batch(mllm, seed=0):
    ds = MultimodalDataset(
        vocab_size=mllm.llm_cfg.vocab_size, text_len=TEXT,
        batch_size=BATCH,
        encoder_dims={n: e.cfg.d_model
                      for n, e in mllm.encoders.items()},
        encoder_tokens={n: e.num_tokens
                        for n, e in mllm.encoders.items()},
        modality_ids={n: e.modality_id
                      for n, e in mllm.encoders.items()},
        seed=seed)
    return next(iter(ds))


def reference_loss_grads(mllm, params, batch):
    """The single-process oracle: full-batch mean CE + autodiff grads
    from ``make_mllm_train_step``'s loss_fn."""
    _, loss_fn = steps.make_mllm_train_step(mllm, opt.AdamWConfig())
    (loss, _aux), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, batch)
    return float(loss), grads


def assert_frozen_zero(bundle, stage_grads):
    """Every leaf the frozen masks mark must be EXACTLY zero — frozen
    modules get no grads by schedule construction, not by masking."""
    masks = bundle.frozen_masks(stage_grads)
    checked = [0]

    def chk(m, g):
        if m:
            checked[0] += 1
            assert not np.asarray(g).any()
    for mk, gr in zip(masks, stage_grads):
        jax.tree.map(chk, mk, gr)
    assert checked[0] > 0          # the masks are not vacuous


# ---------------------------------------------------------------------------
# stage bundle contract (single device)
# ---------------------------------------------------------------------------

def test_stage_bundle_partition_roundtrip():
    """partition/unpartition is an exact bijection, stage specs tile
    the model, and trainable flags agree with the frozen masks."""
    mllm, _plan, ex = tiny_case()
    bundle = ex["stage_bundle"]
    assert len(bundle.specs) == len(ex["sim_graph"].stages)
    params = mllm.init(jax.random.PRNGKey(0))
    sp = bundle.partition(params)
    back = bundle.unpartition(sp)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), params, back)
    # the paper's freeze config: something trains (projectors),
    # something doesn't (encoder modules + LLM)
    assert any(bundle.trainable) and not all(bundle.trainable)
    masks = bundle.frozen_masks(sp)
    for s, mk in enumerate(masks):
        all_frozen = all(jax.tree.leaves(mk))
        assert bundle.trainable[s] == (not all_frozen)


def test_replay_matches_single_process_trainer():
    """Tentpole oracle, sequential half: the stage fns replayed
    through ``execute_schedule`` reproduce the single-process
    trainer's loss and grads (scaled by 1/M), with frozen-module
    grads exactly zero."""
    mllm, _plan, ex = tiny_case()
    bundle = ex["stage_bundle"]
    params = mllm.init(jax.random.PRNGKey(0))
    batch = tiny_batch(mllm)
    ref_loss, ref_grads = reference_loss_grads(mllm, params, batch)

    sp = bundle.partition(params)
    mbs = bundle.encode_microbatches(batch, M)
    res = execute_schedule(bundle.stage_fns, sp, mbs,
                           ex["sim_graph"], ex["schedule"],
                           microbatch_loss=bundle.microbatch_loss,
                           trainable=list(bundle.trainable))
    np.testing.assert_allclose(float(res["loss"]) / M, ref_loss,
                               rtol=2e-5)
    stage_grads = [jax.tree.map(lambda g: g / M, gs)
                   for gs in res["param_grads"]]
    assert_frozen_zero(bundle, stage_grads)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6),
        bundle.unpartition(stage_grads), ref_grads)


def test_encode_microbatches_rejects_indivisible_batch():
    mllm, _plan, ex = tiny_case()
    batch = tiny_batch(mllm)
    with pytest.raises(ValueError, match="divisible"):
        ex["stage_bundle"].encode_microbatches(batch, 3)


# ---------------------------------------------------------------------------
# distributed runner + train step (multi-device)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", sorted(CASES))
@subprocess_test(3)
def test_spmd_runner_trains_real_mllm(case):
    """Tentpole oracle, distributed half: the shard_map runner on the
    real stage partition matches the single-process trainer, and one
    ``make_spmd_train_step`` update moves ONLY the trainable params."""
    from repro.parallel.spmd import build_spmd_runner, mesh_from_plan
    mllm, plan, ex = tiny_case(case)
    bundle = ex["stage_bundle"]
    D = int(ex["schedule"]["num_devices"])
    mesh = mesh_from_plan(plan, mllm, D)
    if case == "valm":
        # each tower's frozen first stage on a rank of its own, and
        # both towers' outputs summed into the LLM's first stage
        specs, device_of = bundle.specs, ex["schedule"]["device_of"]
        towers = [s for s, sp in enumerate(specs)
                  if sp.kind == "encoder" and sp.first]
        assert sorted(specs[s].module for s in towers) == \
            ["audio", "vision"]
        assert not any(bundle.trainable[s] for s in towers)
        assert len({device_of[s] for s in towers}) == 2
        llm0 = next(s for s, sp in enumerate(specs)
                    if sp.kind == "llm" and sp.first)
        assert sorted(specs[p].module for p in
                      ex["sim_graph"].preds[llm0]) == ["audio", "vision"]
    params = mllm.init(jax.random.PRNGKey(0))
    batch = tiny_batch(mllm)
    ref_loss, ref_grads = reference_loss_grads(mllm, params, batch)

    sp = bundle.partition(params)
    mbs = bundle.encode_microbatches(batch, M)
    runner = build_spmd_runner(
        bundle.stage_fns, ex["sim_graph"], ex["schedule"], mesh=mesh,
        microbatch_loss=bundle.microbatch_loss,
        program=ex["spmd_program"], trainable=list(bundle.trainable))
    res = runner(sp, mbs)
    np.testing.assert_allclose(float(res["loss"]) / M, ref_loss,
                               rtol=2e-5)
    stage_grads = [jax.tree.map(lambda g: g / M, gs)
                   for gs in res["param_grads"]]
    assert_frozen_zero(bundle, stage_grads)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6),
        bundle.unpartition(stage_grads), ref_grads)

    # one optimizer step through the full distributed path
    ocfg = opt.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=4)
    masks = bundle.frozen_masks(sp)
    step = steps.make_spmd_train_step(
        bundle.stage_fns, ex["sim_graph"], ex["schedule"], ocfg,
        mesh=mesh, microbatch_loss=bundle.microbatch_loss,
        frozen_mask=masks, trainable=list(bundle.trainable),
        grad_scale=1.0 / M, program=ex["spmd_program"])
    state = opt.init(ocfg, sp, masks)
    new_sp, _state, metrics = step(sp, state, mbs)
    np.testing.assert_allclose(float(metrics["loss"]), ref_loss,
                               rtol=2e-5)
    moved = [0]

    def check_move(m, a, b):
        if m:        # frozen: bit-identical
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        elif np.asarray(a).size and not np.array_equal(
                np.asarray(a), np.asarray(b)):
            moved[0] += 1
    for mk, old, new in zip(masks, sp, new_sp):
        jax.tree.map(check_move, mk, old, new)
    assert moved[0] > 0            # the projectors actually trained


@subprocess_test(3)
def test_run_schedule_spmd_toy_fallback_is_explicit():
    """Satellite contract: ``stage_fn=None`` on the plan form warns
    that the TOY model (not the MLLM) will run; ``stage_fn="toy"``
    opts in silently."""
    from repro.parallel.spmd import run_schedule_spmd
    mllm, plan, ex = tiny_case()
    n_mb = int(plan.schedule.num_microbatches)
    mbs = jax.random.normal(jax.random.PRNGKey(5), (n_mb, 1, 4, 16))
    with pytest.warns(UserWarning, match="TOY stage model"):
        run_schedule_spmd(plan, mllm, mbs)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = run_schedule_spmd(plan, mllm, mbs, stage_fn="toy")
    assert np.isfinite(float(got["loss"]))


# ---------------------------------------------------------------------------
# launch-level: --spmd trains the real model, loss-matches the
# single-process path, and resumes bit-exactly (tier1-multidevice)
# ---------------------------------------------------------------------------

@subprocess_test(3)
def test_launch_spmd_matches_replay_and_resumes(tmp_path):
    """``launch/train --spmd`` end to end: per-step losses match the
    non-spmd run of the same seed/stream, and a crash + ``--resume``
    reproduces the uninterrupted run's tail losses exactly."""
    from repro.launch.train import main
    ref = main(LAUNCH_ARGS)
    full = main(LAUNCH_ARGS + ["--spmd", "--ckpt-dir",
                               str(tmp_path / "a"), "--ckpt-every", "2"])
    np.testing.assert_allclose(np.asarray(ref["losses"]),
                               np.asarray(full["losses"]),
                               rtol=2e-4, atol=1e-5)
    fp = tmp_path / "faults.json"
    fp.write_text(json.dumps([{"kind": "crash", "step": 3}]))
    from repro.resilience.faults import CrashInjected
    with pytest.raises(CrashInjected):
        main(LAUNCH_ARGS + ["--spmd", "--ckpt-dir",
                            str(tmp_path / "b"), "--ckpt-every", "2",
                            "--fault-plan", str(fp)])
    rest = main(LAUNCH_ARGS + ["--spmd", "--ckpt-dir",
                               str(tmp_path / "b"), "--resume"])
    full_losses = full["resilience"]["losses"]
    rest_losses = rest["resilience"]["losses"]
    assert rest_losses                       # it actually resumed
    for s, v in rest_losses.items():
        assert abs(full_losses[s] - v) < 1e-6, (s, full_losses[s], v)


@subprocess_test(3)
def test_launch_cross_mode_resume(tmp_path):
    """A replay-mode checkpoint resumes an ``--spmd`` run (params
    re-partitioned through the StageBundle) and the resulting spmd
    checkpoint resumes a replay run — both continue at the saved
    step, never restart."""
    from repro.launch.train import main
    ck = str(tmp_path / "x")
    short = [a if a != "4" else "2" for a in LAUNCH_ARGS]
    main(short + ["--ckpt-dir", ck, "--ckpt-every", "1"])
    up = main(LAUNCH_ARGS + ["--spmd", "--ckpt-dir", ck, "--resume"])
    assert sorted(up["resilience"]["losses"]) == [2, 3]
    back = main([a if a != "4" else "6" for a in LAUNCH_ARGS]
                + ["--ckpt-dir", ck, "--resume"])
    assert sorted(back["resilience"]["losses"]) == [4, 5]


# ---------------------------------------------------------------------------
# the lint gate guards the --spmd resolve path (single device)
# ---------------------------------------------------------------------------

def test_resolve_plan_lint_gate_blocks_corrupt_program(monkeypatch):
    """Satellite contract: a corrupted wave program (comm rounds
    stripped, so cross-device recvs are never delivered) must die in
    ``resolve_plan``'s schedlint gate before any device is touched."""
    from repro.launch.train import resolve_plan
    from repro.parallel import MLLMParallelPlan
    mllm, _plan, _ex = tiny_case()
    orig = MLLMParallelPlan.apply

    def corrupt(self, target, **kw):
        ex = orig(self, target, **kw)
        for wave in ex["spmd_program"].waves:
            wave.rounds = []
        return ex
    monkeypatch.setattr(MLLMParallelPlan, "apply", corrupt)
    ns = argparse.Namespace(
        plan=None, plan_out=None, plan_devices=3, cp_size=1,
        microbatches=M, batch=BATCH, seq=TEXT, spmd=True, lint=True)
    with pytest.raises(SystemExit, match="schedule lint"):
        resolve_plan(mllm, ns)
