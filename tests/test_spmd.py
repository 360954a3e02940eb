"""SPMD schedule executor (repro.parallel.spmd): the distributed
shard_map program must compute exactly what the sequential replay
(core.modality_parallel.execute_schedule) and plain autodiff compute —
loss, outputs, stage grads — and its measured per-device activation
peaks/trace must match the simulator's claims, for chains, fan-in
modality-parallel DAGs, and the golden 8-rank plan, composed with
context parallelism on one multi-axis mesh.

Multi-device tests re-exec themselves in a subprocess with a forced
host device count (tests/helpers.subprocess_test); under the
multi-device CI job (global XLA_FLAGS) they run in-process."""
import pathlib
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import schedule as sch
from repro.core.modality_parallel import execute_schedule
from repro.core.schedule.graph import PipelineGraph
from repro.core.schedule.memory import (MemoryModelMismatch,
                                        validate_schedule_memory)
from repro.parallel.spmd import (compile_spmd_program, default_mesh,
                                 reference_dag_loss, run_schedule_spmd,
                                 toy_stage_model)

from .helpers import host_mesh, subprocess_test

DATA = pathlib.Path(__file__).parent / "data"
GOLDEN_PLAN = DATA / "paper_mllm_8rank_plan.json"
CHUNKED = ("interleaved", "zb-v")
M = 8


def chain_case(schedule: str, coarse: int = 4, frozen_prefix: int = 0):
    """A pipeline chain sized so every schedule runs on multiple
    devices: ``coarse`` stages for the unchunked schedules (one per
    device), the 2x-refined chain folded onto ``coarse // 2`` devices
    for the chunked ones. Frozen-prefix stages model the paper's
    frozen encoders (bwd = 0, nothing trainable upstream). Trainable
    stages always carry bwd_w > 0 — the schedule decides whether W is
    split out (zb-*) or glued into B (1f1b/interleaved), and either
    way the weight grads must be real, not trivially zero."""
    stages = [sch.Stage(f"e{s}", 1.0, 0.0) if s < frozen_prefix
              else sch.Stage(f"s{s}", 1.0, 2.0, bwd_w=1.0)
              for s in range(coarse)]
    g = sch.chain_graph(stages)
    if schedule in CHUNKED:
        g = sch.refine_chain(sch.chain_graph(stages[:coarse // 2]), 2)
    kwargs = {"virtual_chunks": 2} if schedule in CHUNKED else {}
    sim = sch.get_scheduler(schedule, **kwargs).simulate(g, M)
    return g, sim


def assert_equivalent(got, ref, *, rtol=1e-5, atol=1e-6):
    """The full executor-parity contract: loss, outputs, grads
    (allclose) and the activation bookkeeping (EXACT)."""
    np.testing.assert_allclose(float(got["loss"]), float(ref["loss"]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got["outputs"]),
                               np.asarray(ref["outputs"]),
                               rtol=rtol, atol=atol)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=atol),
        got["param_grads"], ref["param_grads"])
    assert got["activation_trace"] == ref["activation_trace"]
    assert got["peak_activations_per_device"] == \
        ref["peak_activations_per_device"]
    assert got["peak_w_residuals_per_device"] == \
        ref["peak_w_residuals_per_device"]


# ---------------------------------------------------------------------------
# chain equivalence, all four schedules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", sch.SCHEDULES)
@subprocess_test(4)
def test_spmd_matches_replay_chain(schedule):
    """Every schedule's timeline, distributed under shard_map, equals
    the sequential replay bit-for-bit in bookkeeping and to float
    tolerance in math."""
    g, sim = chain_case(schedule)
    fn, params = toy_stage_model(len(g.stages), 16)
    mbs = jax.random.normal(jax.random.PRNGKey(1), (M, 1, 4, 16))
    ref = execute_schedule(fn, params, mbs, g, sim)
    got = run_schedule_spmd(fn, params, mbs, g, sim)
    assert_equivalent(got, ref)
    # the comparison is not vacuous: every trainable stage trained
    assert all(np.asarray(got["param_grads"]["w"][s]).any()
               for s in range(len(g.stages)))
    counts = got["program"].counts()
    assert counts["items"] == len(sim["items"])
    assert counts["devices"] == sim["num_devices"]


@pytest.mark.parametrize("schedule", ["1f1b", "zb-v"])
@subprocess_test(4)
def test_spmd_frozen_prefix_zero_grads(schedule):
    """Frozen head stages (the paper's encoders) get exactly-zero
    grads through the distributed backward, and the trainable tail
    still matches the replay."""
    g, sim = chain_case(schedule, frozen_prefix=1)
    fn, params = toy_stage_model(len(g.stages), 16)
    mbs = jax.random.normal(jax.random.PRNGKey(2), (M, 1, 4, 16))
    got = run_schedule_spmd(fn, params, mbs, g, sim)
    ref = execute_schedule(fn, params, mbs, g, sim)
    assert_equivalent(got, ref)
    frozen = [s for s in range(len(g.stages))
              if g.stages[s].bwd_w <= 0 and g.stages[s].bwd_b <= 0]
    assert frozen
    for s in frozen:
        assert not np.asarray(got["param_grads"]["w"][s]).any()


# ---------------------------------------------------------------------------
# fan-in DAG (modality parallelism)
# ---------------------------------------------------------------------------

def fanin_dag():
    """Two frozen encoders fan into a 2-stage trainable LLM — the
    modality-parallel shape where two devices' outputs land on one."""
    stages = [sch.Stage("enc0", 1.0, 1.0, bwd_w=0.0),
              sch.Stage("enc1", 1.2, 1.2, bwd_w=0.0),
              sch.Stage("llm", 1.0, 2.0, bwd_w=1.0),
              sch.Stage("llm", 1.0, 2.0, bwd_w=1.0)]
    return PipelineGraph(stages, [(0, 2), (1, 2), (2, 3)])


@pytest.mark.parametrize("schedule", ["1f1b", "zb-h1"])
@subprocess_test(4)
def test_spmd_fanin_dag_matches_replay_and_autodiff(schedule):
    """Non-chain DAG: the cotangent fan-in merge must reproduce both
    the generalized replay and the single-device autodiff oracle."""
    g = fanin_dag()
    sim = sch.get_scheduler(schedule).simulate(g, 6)
    fn, params = toy_stage_model(4, 8)
    mbs = jax.random.normal(jax.random.PRNGKey(2), (6, 1, 4, 8))
    ref = execute_schedule(fn, params, mbs, g, sim)
    got = run_schedule_spmd(fn, params, mbs, g, sim)
    assert_equivalent(got, ref)
    oracle_loss, oracle_grads = reference_dag_loss(fn, params, mbs, g)
    np.testing.assert_allclose(float(got["loss"]), float(oracle_loss),
                               rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        got["param_grads"], oracle_grads)
    # frozen encoders: zero grads, exactly
    assert not np.asarray(got["param_grads"]["w"][:2]).any()


# ---------------------------------------------------------------------------
# the golden 8-rank plan, and PP x CP composition on one mesh
# ---------------------------------------------------------------------------

@subprocess_test(8)
def test_spmd_golden_plan_matches_reference():
    """Plan form: the checked-in 8-rank paper plan drives the SPMD
    executor end to end (apply -> compile -> split_devices mesh ->
    shard_map), matching replay + autodiff and the plan's own
    peak-activation claim."""
    from repro.models.mllm import build_paper_mllm
    from repro.parallel import MLLMParallelPlan
    plan = MLLMParallelPlan.load(str(GOLDEN_PLAN))
    mllm = build_paper_mllm("vlm", reduced=True, text_len=plan.text_len)
    ex = plan.apply(mllm, mode="spmd")
    graph, sim = ex["sim_graph"], ex["schedule"]
    assert ex["spmd_program"] is not None
    n_mb, d = plan.schedule.num_microbatches, 16
    mbs = jax.random.normal(jax.random.PRNGKey(3), (n_mb, 1, 4, d))
    got = run_schedule_spmd(plan, mllm, mbs, stage_fn="toy")
    fn, params = toy_stage_model(len(graph.stages), d)
    ref = execute_schedule(fn, params, mbs, graph, sim)
    assert_equivalent(got, ref)
    oloss, ograds = reference_dag_loss(fn, params, mbs, graph)
    np.testing.assert_allclose(float(got["loss"]), float(oloss),
                               rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        got["param_grads"], ograds)
    assert got["peak_activations_per_device"] == \
        list(sim["peak_activations_per_device"]) == \
        list(plan.schedule.peak_activations_per_device)


@subprocess_test(8)
def test_spmd_composed_pp_cp_one_mesh():
    """One plan JSON drives PP x CP on a single ("pp", "cp") mesh: the
    SPMD pipeline program runs over the pp axis (replicating over cp)
    and the plan's ContextPlan drives a CP train step over the cp axis
    — both matching their single-device references."""
    from repro.configs.base import get_config
    from repro.core import bam
    from repro.models import api
    from repro.models.mllm import build_paper_mllm
    from repro.optim import optimizer as opt
    from repro.parallel import MLLMParallelPlan
    from repro.training import steps

    plan = MLLMParallelPlan.load(str(GOLDEN_PLAN))
    mllm = build_paper_mllm("vlm", reduced=True, text_len=plan.text_len)
    ex = plan.apply(mllm, mode="spmd")
    graph, sim = ex["sim_graph"], ex["schedule"]
    with host_mesh((2, 4), ("pp", "cp")) as mesh:
        # pipeline half: program over "pp", replicated over "cp"
        n_mb, d = plan.schedule.num_microbatches, 8
        fn, params = toy_stage_model(len(graph.stages), d)
        mbs = jax.random.normal(jax.random.PRNGKey(4), (n_mb, 1, 4, d))
        got = run_schedule_spmd(fn, params, mbs, graph, sim, mesh=mesh)
        ref = execute_schedule(fn, params, mbs, graph, sim)
        assert_equivalent(got, ref)

        # context half: the SAME plan's ContextPlan on the cp axis
        T, B = plan.text_len, 1
        layout = plan.context.apply(T)
        cfg = get_config("qwen3-1.7b", reduced=True)
        lm_params = api.init(jax.random.PRNGKey(0), cfg)
        ocfg = opt.AdamWConfig(lr=1e-2, warmup_steps=0,
                               schedule="constant")
        state = opt.init(ocfg, lm_params)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
            "labels": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
            "positions": jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None], (B, T)),
            "bits": bam.causal_bits(B, T),
            "valid": jnp.ones((B, T), bool),
        }
        # the plan balanced 8 CP ranks; this mesh folds them onto 4
        # devices — exact, but the step must say the balance is lost
        with pytest.warns(UserWarning, match="balanced for 8 ranks"):
            step_cp = steps.make_cp_train_step(cfg, layout, mesh, ocfg,
                                               axis_name="cp")
        _, _, m_cp = jax.jit(step_cp)(lm_params, state, batch)
        _, _, m_ref = jax.jit(steps.make_train_step(cfg, ocfg))(
            lm_params, state, batch)
        assert abs(float(m_cp["loss"]) - float(m_ref["loss"])) < 1e-4
        assert abs(float(m_cp["grad_norm"])
                   - float(m_ref["grad_norm"])) < 1e-3


# ---------------------------------------------------------------------------
# distributed memory validation: MemoryModelMismatch.first_divergence
# ---------------------------------------------------------------------------

@subprocess_test(2)
def test_spmd_memory_validation_passes_and_reports():
    """validate_schedule_memory(executor="spmd") cross-checks the
    distributed measurement against the simulator claim, exactly like
    the replay path."""
    g, sim = chain_case("zb-v", coarse=4)
    rep = validate_schedule_memory(g, M, "zb-v", virtual_chunks=2,
                                   sim=sim, executor="spmd")
    assert rep["executor"] == "spmd"
    assert rep["simulated_peaks"] == rep["executor_peaks"]


@subprocess_test(2)
def test_spmd_first_divergence_names_device_and_item():
    """Seeded divergence on the SPMD path: execute a timeline scheduled
    with the WRONG per-chunk caps (uncapped, GPipe-style) while
    claiming the proper zb-v timeline — the per-item diff must name the
    offending timeline item on its device."""
    coarse = sch.chain_graph(
        [sch.Stage("m", 1.0, 2.0, bwd_w=1.0) for _ in range(2)])
    fine = sch.refine_chain(coarse, 2)
    proper = sch.get_scheduler("zb-v", virtual_chunks=2).simulate(fine,
                                                                  M)
    wrong = sch.run_schedule(fine, M,
                             device_of=sch.v_shape_devices(4),
                             split_bw=True, stage_caps=[M] * 4)
    wrong["schedule"] = "zb-v"
    wrong["virtual_chunks"] = 2
    assert wrong["peak_activations_per_device"] != \
        proper["peak_activations_per_device"]
    with pytest.raises(MemoryModelMismatch) as ei:
        validate_schedule_memory(fine, M, "zb-v", sim=wrong,
                                 claim_sim=proper, executor="spmd")
    div = ei.value.first_divergence
    assert div is not None
    iid, sim_live, exe_live, _sb, _eb = div
    assert "@d" in iid                      # names the device
    assert "(" in iid and "m" in iid        # names stage + microbatch
    assert sim_live != exe_live or " vs " in iid


@subprocess_test(2)
def test_spmd_claim_doctoring_raises_without_item_diff():
    """A doctored summary claim over an honest timeline: the distributed
    measurement still catches it, and the diff correctly reports that
    the timelines agree item-for-item (divergence is None)."""
    g, sim = chain_case("zb-h1", coarse=2)
    claim = dict(sim)
    claim["peak_activations_per_device"] = \
        [p + 1 for p in sim["peak_activations_per_device"]]
    with pytest.raises(MemoryModelMismatch) as ei:
        validate_schedule_memory(g, M, "zb-h1", sim=sim,
                                 claim_sim=claim, executor="spmd")
    assert ei.value.first_divergence is None
    assert "summary claim" in str(ei.value)


# ---------------------------------------------------------------------------
# static guards (single device, no mesh needed)
# ---------------------------------------------------------------------------

def test_default_mesh_raises_with_xla_flags_hint():
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        default_mesh(1024)
    # forced host devices are no remedy on an accelerator: no such hint
    tpu = types.SimpleNamespace(platform="tpu")
    with pytest.raises(ValueError, match="has 1; run on a larger mesh$"):
        default_mesh(4, devices=[tpu])


def test_runner_rejects_wrong_mesh_axis_size():
    from repro.parallel.spmd import build_spmd_runner
    g, sim = chain_case("1f1b", coarse=2)
    mesh = default_mesh(1)
    with pytest.raises(ValueError, match="compiled for 2"):
        build_spmd_runner(lambda lp, x: x, g, sim, mesh=mesh)


def test_compile_rejects_unreachable_cotangent():
    """A trainable stage whose every successor computes no input grads
    can never receive a cotangent — the compile must refuse, not emit a
    program that silently trains on zeros."""
    g = sch.chain_graph([sch.Stage("a", 1.0, 2.0, bwd_w=1.0),
                         sch.Stage("b", 1.0, 0.0),
                         sch.Stage("c", 1.0, 2.0, bwd_w=1.0)])
    sim = sch.get_scheduler("1f1b").simulate(g, 2)
    with pytest.raises(ValueError, match="no successor produces"):
        compile_spmd_program(g, sim)


def test_plan_apply_unknown_mode_raises():
    from repro.models.mllm import build_paper_mllm
    from repro.parallel import MLLMParallelPlan
    plan = MLLMParallelPlan.load(str(GOLDEN_PLAN))
    mllm = build_paper_mllm("vlm", reduced=True, text_len=plan.text_len)
    with pytest.raises(ValueError, match="mode"):
        plan.apply(mllm, mode="telepathy")


# ---------------------------------------------------------------------------
# randomized chain property (seeded; the hypothesis twin lives in
# test_spmd_properties.py and runs where hypothesis is installed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
@subprocess_test(4)
def test_spmd_random_chain_matches_reference(seed):
    """Random chain length x freeze prefix x schedule: distributed
    loss/grads match the autodiff oracle, and the measured per-device
    peaks match the simulator's claim exactly."""
    rng = np.random.default_rng(seed)
    schedule = sch.SCHEDULES[int(rng.integers(len(sch.SCHEDULES)))]
    coarse = int(rng.integers(1, 3)) * 2          # 2 or 4
    frozen_prefix = int(rng.integers(0, coarse // 2 + 1))
    n_mb = int(rng.integers(2, 7))
    g, sim0 = chain_case(schedule, coarse=coarse,
                         frozen_prefix=frozen_prefix)
    kwargs = {"virtual_chunks": 2} if schedule in CHUNKED else {}
    sim = sch.get_scheduler(schedule, **kwargs).simulate(g, n_mb)
    fn, params = toy_stage_model(len(g.stages), 8, seed=seed)
    mbs = jax.random.normal(jax.random.PRNGKey(seed + 10),
                            (n_mb, 1, 4, 8))
    got = run_schedule_spmd(fn, params, mbs, g, sim)
    oloss, ograds = reference_dag_loss(fn, params, mbs, g)
    np.testing.assert_allclose(float(got["loss"]), float(oloss),
                               rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        got["param_grads"], ograds)
    assert got["peak_activations_per_device"] == \
        list(sim["peak_activations_per_device"])
