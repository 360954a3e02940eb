"""core.schedule: B/W-split work items, the three schedulers, and
their composition with frozen-aware costs (ZB-H1 / interleaved vs 1F1B
on frozen-MLLM fixtures; the glued-W regression anchor)."""
import numpy as np
import pytest

from repro.core import pipeline as pp
from repro.core import schedule as sch


# ---------------------------------------------------------------------------
# B/W cost decomposition
# ---------------------------------------------------------------------------

def test_bw_factor_decomposition():
    """frozen => W = 0; trainable => W = 1 fwd-equivalent; recompute
    time lands on B (it must precede the grad matmuls)."""
    frozen_head = pp.ModuleProfile("enc", np.ones(4), frozen=True)
    frozen_mid = pp.ModuleProfile("llm", np.ones(4), frozen=True,
                                  trainable_upstream=True)
    trainable = pp.ModuleProfile("proj", np.ones(4), frozen=False)
    for m in (frozen_head, frozen_mid, trainable):
        assert m.bwd_input_factor + m.bwd_weight_factor == m.bwd_factor
    assert frozen_head.bwd_weight_factor == 0.0
    assert frozen_mid.bwd_weight_factor == 0.0
    assert frozen_mid.bwd_input_factor == 1.0
    assert trainable.bwd_weight_factor == 1.0
    assert trainable.bwd_input_factor == 1.0
    trainable.recompute = True
    assert trainable.bwd_input_factor == 2.0      # recompute + B
    assert trainable.bwd_weight_factor == 1.0


def test_partition_carries_w_costs():
    m = pp.ModuleProfile("llm", np.ones(8) * 2.0, frozen=False)
    stages = pp.partition_module(m, 4)
    for s in stages:
        assert s.bwd_w == pytest.approx(s.fwd)     # W = 1 fwd-equivalent
        assert s.bwd_b == pytest.approx(s.bwd - s.bwd_w)
    frozen = pp.partition_module(
        pp.ModuleProfile("enc", np.ones(8), frozen=True,
                         trainable_upstream=True), 4)
    assert all(s.bwd_w == 0.0 and s.bwd > 0.0 for s in frozen)


# ---------------------------------------------------------------------------
# Regression anchor: glued B/W == legacy 1F1B
# ---------------------------------------------------------------------------

def test_bw_split_glued_reproduces_1f1b_closed_form():
    """All-trainable chain with explicit B/W split: when W runs
    immediately after B (the 1F1B scheduler's glued placement), the
    iteration time is the legacy closed form (M + S - 1)(f + b)."""
    for S, M, f, b in [(4, 8, 1.0, 2.0), (2, 4, 3.0, 1.0), (6, 12, 1.0, 1.0)]:
        g = sch.chain_graph(
            [sch.Stage("m", f, b, bwd_w=b / 2) for _ in range(S)])
        sim = sch.get_scheduler("1f1b").simulate(g, M)
        assert sim["iteration_time"] == pytest.approx((M + S - 1) * (f + b))
        assert sim["schedule"] == "1f1b"


def test_split_conserves_work():
    """Deferring W moves work around but never changes per-device busy
    totals — only the makespan."""
    g = sch.chain_graph(
        [sch.Stage("m", 1.0, 2.0, bwd_w=1.0) for _ in range(4)])
    glued = sch.get_scheduler("1f1b").simulate(g, 8)
    split = sch.get_scheduler("zb-h1").simulate(g, 8)
    np.testing.assert_allclose(sorted(glued["per_device_busy"]),
                               sorted(split["per_device_busy"]))
    assert split["iteration_time"] <= glued["iteration_time"] + 1e-9


# ---------------------------------------------------------------------------
# ZB-H1 / interleaved vs 1F1B on frozen-MLLM fixtures
# ---------------------------------------------------------------------------

def frozen_mllm_modules(llm_trainable: bool):
    """Frozen encoder + trainable projector (+ frozen or trainable
    LLM): the paper's fine-tuning settings."""
    enc = pp.ModuleProfile("vision", np.ones(48) * 2.0, frozen=True)
    llm = pp.ModuleProfile("llm", np.ones(32) * 1.5,
                           frozen=not llm_trainable)
    pp.analyze_chain([enc, llm], projector_trainable=[True, False])
    return enc, llm


def frozen_mllm_graph(llm_trainable: bool, stages: int = 8):
    return pp.build_chain_fused(list(frozen_mllm_modules(llm_trainable)),
                                stages, frozen_aware=True)


@pytest.mark.parametrize("llm_trainable", [False, True])
@pytest.mark.parametrize("microbatches", [8, 16, 24])
def test_zbh1_and_interleaved_not_worse_than_1f1b(llm_trainable,
                                                  microbatches):
    """At a fixed 8-device budget: ZB-H1 runs the same 8-stage graph
    with deferred W; interleaved searches its chunk count (a 2x-finer
    partition folded onto the same devices, or the v=1 degenerate).
    Neither may bubble more than plain 1F1B."""
    modules = list(frozen_mllm_modules(llm_trainable))
    sims = {s: pp.simulate_fused_chain(modules, 8, microbatches,
                                       schedule=s)[1]
            for s in sch.SCHEDULES}
    assert all(s["num_devices"] == 8 for s in sims.values())
    for name in ("zb-h1", "interleaved"):
        assert sims[name]["bubble_fraction"] <= \
            sims["1f1b"]["bubble_fraction"] + 1e-9, \
            (name, llm_trainable, microbatches)


def test_interleaved_megatron_order_beats_1f1b_on_homogeneous_chain():
    """On a homogeneous chain (the schedule's home turf) the Megatron
    item order realizes the ~v-fold fill/drain reduction outright —
    no fallback involved."""
    g8 = sch.chain_graph([sch.Stage("m", 2.0, 4.0) for _ in range(8)])
    g16 = sch.chain_graph([sch.Stage("m", 1.0, 2.0) for _ in range(16)])
    base = sch.get_scheduler("1f1b").simulate(g8, 24)
    il = sch.get_scheduler("interleaved", virtual_chunks=2).simulate(
        g16, 24)
    assert il["num_devices"] == base["num_devices"] == 8
    # busy/device = 144; fill: (D-1)(f+b) = 42 vs (D-1)(f+b)/v = 21
    assert base["iteration_time"] == pytest.approx(186.0)
    assert il["iteration_time"] == pytest.approx(165.0)


def test_zbh1_strictly_beats_1f1b_with_trainable_llm():
    """With a trainable LLM there is W work to defer: ZB-H1 must win
    outright, not just tie."""
    g = frozen_mllm_graph(llm_trainable=True)
    base = sch.get_scheduler("1f1b").simulate(g, 8)
    zb = sch.get_scheduler("zb-h1").simulate(g, 8)
    assert zb["iteration_time"] < base["iteration_time"]


def test_zbh1_equals_1f1b_when_fully_frozen():
    """Fully frozen backbone => no W passes anywhere => the split
    changes nothing."""
    g = frozen_mllm_graph(llm_trainable=False)
    base = sch.get_scheduler("1f1b").simulate(g, 8)
    zb = sch.get_scheduler("zb-h1").simulate(g, 8)
    assert zb["iteration_time"] == pytest.approx(base["iteration_time"])


def test_zbh1_on_modality_parallel_dag():
    """The W pass defers on DAG graphs (Fig. 6) too, not just chains."""
    e1 = pp.ModuleProfile("vision", np.ones(4) * 3.0, frozen=True)
    e2 = pp.ModuleProfile("audio", np.ones(6), frozen=True)
    llm = pp.ModuleProfile("llm", np.ones(8) * 2.0, frozen=False,
                           trainable_upstream=True)
    g = pp.build_modality_parallel([e1, e2], llm, [2, 2], 4)
    base = sch.get_scheduler("1f1b").simulate(g, 8)
    zb = sch.get_scheduler("zb-h1").simulate(g, 8)
    assert zb["iteration_time"] <= base["iteration_time"] + 1e-9


# ---------------------------------------------------------------------------
# ZB-V invariants
# ---------------------------------------------------------------------------

def test_v_shape_devices_map():
    """Device i hosts chunks i and 2p-1-i: down the column and back."""
    assert sch.v_shape_devices(8) == [0, 1, 2, 3, 3, 2, 1, 0]
    assert sch.v_shape_devices(2) == [0, 0]
    with pytest.raises(AssertionError):
        sch.v_shape_devices(7)


def test_refine_chain_conserves_costs():
    g = sch.chain_graph([sch.Stage("m", 2.0, 4.0, (0, 8), bwd_w=2.0)
                         for _ in range(3)])
    fine = sch.refine_chain(g, 2)
    assert len(fine.stages) == 6
    assert sum(s.fwd for s in fine.stages) == pytest.approx(6.0)
    assert sum(s.bwd for s in fine.stages) == pytest.approx(12.0)
    assert sum(s.bwd_w for s in fine.stages) == pytest.approx(6.0)
    assert fine.stages[0].layer_range == (0, 4)
    assert fine.stages[1].layer_range == (4, 8)
    assert sch.refine_chain(g, 1) is g


@pytest.mark.parametrize("llm_trainable", [False, True])
@pytest.mark.parametrize("microbatches", [8, 16, 24])
def test_zbv_bubble_ordering_on_chains(llm_trainable, microbatches):
    """At a fixed 8-device budget: bubble(zb-v) <= bubble(zb-h1) <=
    bubble(1f1b). zb-v searches {2, 1} and v=1 IS the ZB-H1 placement,
    so the first inequality is structural; the second is ZB-H1's
    glued-fallback guarantee."""
    modules = list(frozen_mllm_modules(llm_trainable))
    sims = {s: pp.simulate_fused_chain(modules, 8, microbatches,
                                       schedule=s)[1]
            for s in ("1f1b", "zb-h1", "zb-v")}
    assert all(s["num_devices"] == 8 for s in sims.values())
    assert sims["zb-v"]["bubble_fraction"] <= \
        sims["zb-h1"]["bubble_fraction"] + 1e-9
    assert sims["zb-h1"]["bubble_fraction"] <= \
        sims["1f1b"]["bubble_fraction"] + 1e-9


def test_zbv_beats_zbh1_on_homogeneous_chain():
    """On a homogeneous all-trainable chain the V fold has fill/drain
    to win outright over one-chunk-per-device ZB-H1."""
    coarse = sch.chain_graph(
        [sch.Stage("m", 2.0, 4.0, bwd_w=2.0) for _ in range(4)])
    fine = sch.refine_chain(coarse, 2)
    zh = sch.get_scheduler("zb-h1").simulate(coarse, 8)
    zv = sch.get_scheduler("zb-v").simulate(fine, 8)
    assert zv["num_devices"] == zh["num_devices"] == 4
    assert zv["virtual_chunks"] == 2
    assert zv["iteration_time"] <= zh["iteration_time"] + 1e-9
    base = sch.get_scheduler("1f1b").simulate(coarse, 8)
    assert zv["iteration_time"] < base["iteration_time"]


def test_zbv_peak_activations_within_1f1b_envelope():
    """ZB-V's defining memory claim: with 2 chunk-stages per device
    (each half a 1F1B stage), every device's peak live activations stay
    within 2p chunk-activations = the deepest 1F1B device's p coarse
    activations — and, unlike 1F1B's p..1 ramp, uniformly."""
    for p, M in [(2, 8), (4, 8), (4, 24)]:
        coarse = sch.chain_graph(
            [sch.Stage("m", 2.0, 4.0, bwd_w=2.0) for _ in range(p)])
        fine = sch.refine_chain(coarse, 2)
        zv = sch.get_scheduler("zb-v").simulate(fine, M)
        base = sch.get_scheduler("1f1b").simulate(coarse, M)
        envelope = 2 * max(base["peak_activations_per_device"])
        assert all(pk <= envelope
                   for pk in zv["peak_activations_per_device"]), \
            (p, M, zv["peak_activations_per_device"], envelope)


def test_zbv_frozen_stages_emit_no_w_items():
    """Frozen chunks have no W pass at all — zero-bubble deferral
    headroom concentrates on the trainable chunks."""
    stages = [sch.Stage(f"enc{i}", 1.0, 0.0) for i in range(4)] + \
        [sch.Stage(f"llm{i}", 1.0, 3.0, bwd_w=1.0) for i in range(4)]
    g = sch.chain_graph(stages)
    sim = sch.get_scheduler("zb-v").simulate(g, 8)
    frozen = {s for s, st in enumerate(g.stages) if st.bwd_w == 0}
    w_items = [(s, m) for _, _, _, kind, s, m in sim["items"]
               if kind == "W"]
    assert w_items, "trainable chunks must have W passes"
    assert not [it for it in w_items if it[0] in frozen]
    # fully frozen chain: no W anywhere
    g0 = sch.chain_graph([sch.Stage("enc", 1.0, 0.0) for _ in range(4)])
    sim0 = sch.get_scheduler("zb-v").simulate(g0, 8)
    assert not any(kind == "W" for _, _, _, kind, _, _ in sim0["items"])


def test_zbv_degenerate_v1_is_zbh1():
    g = frozen_mllm_graph(llm_trainable=True)
    zh = sch.get_scheduler("zb-h1").simulate(g, 8)
    zv1 = sch.get_scheduler("zb-v", virtual_chunks=1).simulate(g, 8)
    assert zv1["iteration_time"] == pytest.approx(zh["iteration_time"])
    assert zv1["virtual_chunks"] == 1 and zv1["schedule"] == "zb-v"


# ---------------------------------------------------------------------------
# Interleaved device mapping
# ---------------------------------------------------------------------------

def test_interleave_devices_round_robin():
    g = sch.chain_graph([sch.Stage("m", 1.0, 2.0) for _ in range(8)])
    assert sch.interleave_devices(g, 2) == [0, 1, 2, 3, 0, 1, 2, 3]
    assert sch.interleave_devices(g, 4) == [0, 1, 0, 1, 0, 1, 0, 1]
    assert sch.interleave_devices(g, 1) == list(range(8))


def test_interleaved_uses_fewer_devices_and_conserves_work():
    g = sch.chain_graph([sch.Stage("m", 1.0, 2.0) for _ in range(8)])
    base = sch.get_scheduler("1f1b").simulate(g, 16)
    il = sch.get_scheduler("interleaved", virtual_chunks=2).simulate(g, 16)
    assert il["num_devices"] == 4 and base["num_devices"] == 8
    assert sum(il["per_device_busy"]) == pytest.approx(
        sum(base["per_device_busy"]))
    assert il["bubble_fraction"] <= base["bubble_fraction"] + 1e-9


# ---------------------------------------------------------------------------
# Scheduler interface / Algorithm 1 integration
# ---------------------------------------------------------------------------

def test_get_scheduler_registry():
    for name in sch.SCHEDULES:
        s = sch.get_scheduler(name)
        assert s.name == name
    with pytest.raises(ValueError):
        sch.get_scheduler("gpipe")


def test_simulate_tags_schedule_name():
    g = sch.chain_graph([sch.Stage("m", 1.0, 2.0) for _ in range(4)])
    for name in sch.SCHEDULES:
        assert sch.simulate(g, 8, schedule=name)["schedule"] == name


def test_auto_parallelize_returns_schedule_name():
    e = pp.ModuleProfile("vision", np.ones(8) * 3.0, frozen=True)
    llm = pp.ModuleProfile("llm", np.ones(16) * 2.0, frozen=False,
                           trainable_upstream=True)
    best = pp.auto_parallelize([e], llm, total_devices=8,
                               num_microbatches=8)
    assert best["schedule"] in sch.SCHEDULES
    assert best["encoder_names"] == ["vision"]
    # schedules are compared at the same device budget: the simulated
    # device count must equal the allocated stage count
    assert best["devices"] == best["llm_stages"] + \
        sum(best["encoder_stages"])
    # searching more schedules can only improve on 1F1B-only
    base = pp.auto_parallelize([e], llm, total_devices=8,
                               num_microbatches=8, schedules=("1f1b",))
    assert best["tput_per_device"] >= base["tput_per_device"] - 1e-12
    assert base["schedule"] == "1f1b"


def test_auto_parallelize_joint_chunk_search():
    """Algorithm 1 searches (schedule, virtual_chunks) jointly: the
    winner carries its chunk count, every sim is tagged with one, and
    widening the v set can only improve throughput."""
    e = pp.ModuleProfile("vision", np.ones(8) * 3.0, frozen=True)
    llm = pp.ModuleProfile("llm", np.ones(16) * 2.0, frozen=False,
                           trainable_upstream=True)
    best = pp.auto_parallelize([e], llm, total_devices=8,
                               num_microbatches=8)
    assert best["schedule"] in sch.SCHEDULES
    assert best["virtual_chunks"] >= 1
    narrow = pp.auto_parallelize([e], llm, total_devices=8,
                                 num_microbatches=8,
                                 virtual_chunks=(1,))
    assert best["tput_per_device"] >= narrow["tput_per_device"] - 1e-12


def test_infeasible_explicit_chunk_tuple_degrades_to_v1():
    """An explicit virtual_chunks candidate set that fits nowhere
    (v=4 on an 8-layer module split 4 ways) degrades to the v=1
    placement instead of dying — the documented fold-back behavior."""
    llm = pp.ModuleProfile("llm", np.ones(8) * 2.0, frozen=False)
    g, sim = pp.simulate_fused_chain([llm], 4, 8, schedule="interleaved",
                                     virtual_chunks=(4,))
    assert sim["num_devices"] == 4 and sim["virtual_chunks"] == 1


def test_simulate_plan_zbv_keeps_device_budget():
    """zb-v folds its two chunks per device back onto the planned
    ranks, so the simulated device count equals the allocation."""
    e = pp.ModuleProfile("vision", np.ones(4) * 3.0, frozen=True)
    llm = pp.ModuleProfile("llm", np.ones(8) * 2.0, frozen=False,
                           trainable_upstream=True)
    g, sim = pp.simulate_plan([e], llm, [2], 4, 8, schedule="zb-v")
    assert sim["num_devices"] == 6
    assert len(g.stages) in (6, 12)
    assert sim["schedule"] == "zb-v"
    # not enough layers to chunk => the v=1 (ZB-H1 placement) degenerate
    tiny = pp.ModuleProfile("llm", np.ones(4), frozen=False)
    g, sim = pp.simulate_plan([], tiny, [], 4, 8, schedule="zb-v")
    assert sim["num_devices"] == 4 and len(g.stages) == 4
    assert sim["virtual_chunks"] == 1


def test_simulate_plan_keeps_device_budget():
    """Interleaved folds its virtual chunks onto the planned devices
    (and degrades v when a module lacks layers) — num_devices always
    equals the allocated stage count."""
    e = pp.ModuleProfile("vision", np.ones(4) * 3.0, frozen=True)
    llm = pp.ModuleProfile("llm", np.ones(8) * 2.0, frozen=False,
                           trainable_upstream=True)
    for schedule in sch.SCHEDULES:
        g, sim = pp.simulate_plan([e], llm, [2], 4, 8, schedule=schedule)
        assert sim["num_devices"] == 6, schedule
        # the winning interleaved graph is v=2 (12 stages) or the
        # degenerate v=1 (6 stages) — never anything else
        assert len(g.stages) in (6, 12)
    # not enough layers for chunking anywhere => only v=1 feasible
    tiny = pp.ModuleProfile("llm", np.ones(4), frozen=False)
    g, sim = pp.simulate_plan([], tiny, [], 4, 8, schedule="interleaved")
    assert sim["num_devices"] == 4 and len(g.stages) == 4


def test_parallel_spec_threads_schedule():
    """MultimodalParallelSpec carries the schedule choice end to end."""
    from repro.core.modality import (ModalityModule, MultimodalModule,
                                     MultimodalParallelSpec, ParallelSpec)
    from repro.configs.paper_mllm import llm_config, vision_encoder_config
    mllm = MultimodalModule(
        encoders={"vision": ModalityModule(
            "vision", vision_encoder_config("S", reduced=True),
            modality_id=1, num_tokens=16)},
        llm_cfg=llm_config("S", reduced=True))
    mllm.freeze("vision", module=True, projector=False)
    mllm.freeze("llm", module=False)      # trainable LLM => W exists
    spec = MultimodalParallelSpec(
        encoder_specs={"vision": ParallelSpec(pp_size=1)},
        llm_spec=ParallelSpec(pp_size=2), num_microbatches=8,
        schedule="zb-h1")
    plan = spec.apply(mllm, text_len=64)
    assert plan["schedule_name"] == "zb-h1"
    assert plan["schedule"]["bubble_fraction"] >= 0.0


def test_parallel_spec_graph_stays_one_stage_per_device():
    """Executor contract: plan["graph"] always has one stage per
    simulated device — interleaved's v-times finer simulation graph
    must fold back to the planned partition."""
    from repro.core.modality import (ModalityModule, MultimodalModule,
                                     MultimodalParallelSpec, ParallelSpec)
    from repro.configs.paper_mllm import llm_config, vision_encoder_config
    mllm = MultimodalModule(
        encoders={"vision": ModalityModule(
            "vision", vision_encoder_config("S"), modality_id=1,
            num_tokens=64)},
        llm_cfg=llm_config("S"))
    mllm.freeze("vision", module=True, projector=False)
    mllm.freeze("llm", module=False)
    for schedule in sch.SCHEDULES:
        spec = MultimodalParallelSpec(
            encoder_specs={"vision": ParallelSpec(pp_size=2)},
            llm_spec=ParallelSpec(pp_size=6), num_microbatches=16,
            schedule=schedule)
        plan = spec.apply(mllm, text_len=256)
        assert len(plan["graph"].stages) == \
            plan["schedule"]["num_devices"], schedule


def test_split_devices_accepts_auto_parallelize_plan():
    from repro.core import modality_parallel as mp
    from repro.parallel import MLLMParallelPlan, SchedulePlan, StagePlan

    class FakeMLLM:
        encoders = {"audio": None, "vision": None}

    # encoder_names carries the caller's profile order, so counts land
    # on the right encoder even when that order is not name-sorted;
    # stage counts stay COARSE (one per device) even for chunked
    # schedules — virtual chunks fold onto the same devices
    plan = MLLMParallelPlan(
        stage=StagePlan(encoder_names=("vision", "audio"),
                        encoder_stages=(2, 1), llm_stages=3),
        schedule=SchedulePlan(
            name="zb-v", virtual_chunks=2, num_microbatches=8,
            iteration_time=1.0, bubble_fraction=0.1, num_devices=6,
            peak_activations_per_device=(1,) * 6, tput_per_device=1.0),
        context=None, text_len=64)
    assert plan.schedule.name == "zb-v"
    assert plan.schedule.virtual_chunks == 2
    split = mp.split_devices(FakeMLLM(), list(range(6)), plan=plan)
    assert split == {"audio": [0], "vision": [1, 2], "llm": [3, 4, 5]}
