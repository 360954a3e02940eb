"""repro.parallel: the typed parallelize() entrypoint and the
MLLMParallelPlan it returns — search parity with Algorithm 1, JSON
round-trips, the golden 8-rank paper_mllm plan, the executor
fold-back contract, and ``split_devices`` placement."""
import json
import os

import numpy as np
import pytest

from repro.configs.paper_mllm import llm_config, vision_encoder_config
from repro.core import distribution as dist
from repro.core import pipeline as pp
from repro.core.modality import (ModalityModule, MultimodalModule,
                                 MultimodalParallelSpec, ParallelSpec)
from repro.parallel import (ClusterSpec, ContextPlan, MLLMParallelPlan,
                            SchedulePlan, StagePlan, WorkloadShape,
                            mllm_workload_bits, parallelize,
                            plan_context, search_plan)

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "paper_mllm_8rank_plan.json")

CLUSTER_8 = ClusterSpec(num_devices=8, cp_size=8)
SHAPE_1K = WorkloadShape(text_len=1024, num_microbatches=8)


@pytest.fixture(scope="module")
def paper_vlm():
    from repro.models.mllm import build_paper_mllm
    return build_paper_mllm("vlm")


@pytest.fixture(scope="module")
def paper_plan(paper_vlm):
    return parallelize(paper_vlm, CLUSTER_8, SHAPE_1K)


# ---------------------------------------------------------------------------
# parallelize(): joint search parity
# ---------------------------------------------------------------------------

def test_schedule_plan_matches_auto_parallelize_winner(paper_vlm,
                                                       paper_plan):
    """The typed entrypoint must pick EXACTLY what Algorithm 1 picks:
    same schedule, chunk count, stage allocation, and simulated
    figures (bit-for-bit — both run the same deterministic search)."""
    encs, llm = paper_vlm.profiles(1024)
    best = pp.auto_parallelize(encs, llm, total_devices=8,
                               num_microbatches=8)
    s = paper_plan.schedule
    assert s.name == best["schedule"]
    assert s.virtual_chunks == best["virtual_chunks"]
    assert s.iteration_time == best["iteration_time"]
    assert s.bubble_fraction == best["bubble_fraction"]
    assert s.tput_per_device == best["tput_per_device"]
    assert paper_plan.stage.llm_stages == best["llm_stages"]
    assert list(paper_plan.stage.encoder_stages) == \
        best["encoder_stages"]
    assert list(paper_plan.stage.encoder_names) == \
        best["encoder_names"]
    assert paper_plan.pp_devices == best["devices"]


def test_context_plan_reproduces_plan_tokens(paper_vlm, paper_plan):
    """The ContextPlan must reproduce plan_tokens' decision for the
    same workload: same balancer, same block->rank assignment, same
    makespan."""
    bits, pos = mllm_workload_bits(paper_vlm, 1024)
    ref = dist.plan_tokens(bits, pos, 8, block_size=128, method="lpt")
    c = paper_plan.context
    assert c.num_ranks == 8 and c.method == "lpt"
    assert list(c.assignment) == list(ref.assignment)
    assert c.makespan == ref.makespan
    np.testing.assert_allclose(np.array(c.loads), ref.loads)
    # the typed wrapper reconstructs a working core plan
    core = c.core_plan()
    assert core.makespan == ref.makespan
    assert sorted(np.concatenate(core.per_rank_blocks).tolist()) == \
        list(range(len(c.assignment)))


def test_plan_json_roundtrip_and_golden_stability(paper_plan):
    """Plans are plain data: to_json/from_json is lossless, and the
    recorded golden plan pins the search — an accidental regression in
    the partitioner, simulator, or balancer shows up as a diff against
    tests/data/paper_mllm_8rank_plan.json."""
    assert MLLMParallelPlan.from_json(paper_plan.to_json()) == \
        paper_plan
    golden = MLLMParallelPlan.load(GOLDEN)
    # the schedule choice must be stable (the headline guard) ...
    assert golden.schedule.name == paper_plan.schedule.name == "zb-v"
    assert golden.schedule.virtual_chunks == \
        paper_plan.schedule.virtual_chunks == 2
    # ... and so must everything else the search decided
    assert golden == paper_plan


def test_apply_instantiates_pinned_schedule(paper_vlm, paper_plan):
    """plan.apply re-simulates the PINNED (schedule, v) pair — the
    executor contract reproduces the recorded figures instead of
    re-searching."""
    ex = paper_plan.apply(paper_vlm)
    assert ex["schedule_name"] == paper_plan.schedule.name
    assert ex["virtual_chunks"] == paper_plan.schedule.virtual_chunks
    assert ex["schedule"]["bubble_fraction"] == \
        pytest.approx(paper_plan.schedule.bubble_fraction)
    assert len(ex["graph"].stages) == ex["devices"] == \
        paper_plan.pp_devices
    assert ex["plan"] is paper_plan
    assert ex["context"] == paper_plan.context
    # applying against a different encoder set fails loudly
    from repro.models.mllm import build_paper_mllm
    with pytest.raises(AssertionError):
        paper_plan.apply(build_paper_mllm("valm"))


# ---------------------------------------------------------------------------
# Executor fold-back: pinned before the port, equal after it
# ---------------------------------------------------------------------------

def _big_vlm():
    mllm = MultimodalModule(
        encoders={"vision": ModalityModule(
            "vision", vision_encoder_config("S"), modality_id=1,
            num_tokens=64)},
        llm_cfg=llm_config("S"))
    mllm.freeze("vision", module=True, projector=False)
    mllm.freeze("llm", module=False)
    return mllm


def test_spec_apply_folds_interleaved_sim_graph_back():
    """The fold-back path pinned by behavior: force an interleaved
    v=2 winner (24 sim stages on 8 devices); plan["graph"] must be the
    one-stage-per-device coarse partition — stage for stage equal to
    build_modality_parallel at the planned counts — while the sim dict
    keeps the finer graph's accounting."""
    mllm = _big_vlm()
    spec = MultimodalParallelSpec(
        encoder_specs={"vision": ParallelSpec(pp_size=2)},
        llm_spec=ParallelSpec(pp_size=6), num_microbatches=16,
        schedule="interleaved", virtual_chunks=(2,))
    plan = spec.apply(mllm, text_len=256)
    sim = plan["schedule"]
    assert sim["virtual_chunks"] == 2 and sim["num_devices"] == 8
    g = plan["graph"]
    assert len(g.stages) == 8
    encs, llm = mllm.profiles(256)
    ref = pp.build_modality_parallel(encs, llm, [2], 6,
                                     frozen_aware=True)
    assert sorted(g.edges) == sorted(ref.edges)
    for got, want in zip(g.stages, ref.stages):
        assert got.module == want.module
        assert got.layer_range == want.layer_range
        assert got.fwd == pytest.approx(want.fwd)
        assert got.bwd == pytest.approx(want.bwd)
        assert got.bwd_w == pytest.approx(want.bwd_w)


def test_typed_apply_equals_spec_apply_foldback():
    """MLLMParallelPlan.apply is the port of MultimodalParallelSpec.
    apply: for the same pinned allocation + (schedule, v) both emit
    identical executor contracts."""
    mllm = _big_vlm()
    spec = MultimodalParallelSpec(
        encoder_specs={"vision": ParallelSpec(pp_size=2)},
        llm_spec=ParallelSpec(pp_size=6), num_microbatches=16,
        schedule="interleaved", virtual_chunks=(2,))
    legacy = spec.apply(mllm, text_len=256)
    typed = MLLMParallelPlan(
        stage=StagePlan(("vision",), (2,), 6),
        schedule=SchedulePlan(
            name="interleaved", virtual_chunks=2, num_microbatches=16,
            iteration_time=legacy["schedule"]["iteration_time"],
            bubble_fraction=legacy["schedule"]["bubble_fraction"],
            num_devices=8,
            peak_activations_per_device=tuple(
                legacy["schedule"]["peak_activations_per_device"]),
            tput_per_device=0.0),
        context=None, text_len=256)
    ported = typed.apply(mllm)
    assert ported["schedule_name"] == legacy["schedule_name"]
    assert ported["virtual_chunks"] == legacy["virtual_chunks"]
    assert ported["schedule"]["iteration_time"] == \
        pytest.approx(legacy["schedule"]["iteration_time"])
    got, want = ported["graph"], legacy["graph"]
    assert sorted(got.edges) == sorted(want.edges)
    for a, b in zip(got.stages, want.stages):
        assert (a.module, a.layer_range) == (b.module, b.layer_range)
        assert a.fwd == pytest.approx(b.fwd)
        assert a.bwd == pytest.approx(b.bwd)
        assert a.bwd_w == pytest.approx(b.bwd_w)


# ---------------------------------------------------------------------------
# search_plan objectives / plan_context balancers
# ---------------------------------------------------------------------------

def small_profiles():
    enc = pp.ModuleProfile("vision", np.ones(8) * 3.0, frozen=True)
    llm = pp.ModuleProfile("llm", np.ones(16) * 2.0, frozen=False,
                           trainable_upstream=True)
    return enc, llm


def test_search_plan_objectives():
    enc, llm = small_profiles()
    cluster, shape = ClusterSpec(8), WorkloadShape(num_microbatches=8)
    tput = search_plan([enc], llm, cluster, shape)
    fast = search_plan([enc], llm, cluster, shape,
                       objective="iteration_time")
    # min-iteration-time spends devices freely; tput/device never
    # prefers a slower iteration at the same footprint
    assert fast.schedule.iteration_time <= \
        tput.schedule.iteration_time + 1e-9
    assert fast.pp_devices >= tput.pp_devices
    with pytest.raises(ValueError):
        search_plan([enc], llm, cluster, shape, objective="speed")
    with pytest.raises(ValueError):
        pp.auto_parallelize([enc], llm, 8, 8, objective="speed")


def test_plan_context_balancers_and_auto():
    from repro.core import bam
    bits, pos = bam.build_sample_bits(
        [("text", 0, 64), ("mod", 1, 32), ("text", 0, 32)], 128)
    plans = {m: plan_context(bits, pos, 4, block_size=8, method=m)
             for m in ("lpt", "zigzag", "ring")}
    auto = plan_context(bits, pos, 4, block_size=8, method="auto")
    assert auto.makespan == min(p.makespan for p in plans.values())
    assert auto.method in ("lpt", "zigzag", "ring")
    for p in plans.values():
        assert p.num_ranks == 4
        assert len(p.assignment) == 16
        assert p.imbalance >= 1.0 - 1e-12
    with pytest.raises(ValueError):
        plan_context(bits, pos, 4, method="greedy")


# ---------------------------------------------------------------------------
# Serialization hygiene + typed input validation
# ---------------------------------------------------------------------------

def test_from_json_rejects_malformed():
    enc, llm = small_profiles()
    plan = search_plan([enc], llm, ClusterSpec(4),
                       WorkloadShape(num_microbatches=8))
    d = json.loads(plan.to_json())
    d["format_version"] = 99
    with pytest.raises(ValueError):
        MLLMParallelPlan.from_json(json.dumps(d))
    d = json.loads(plan.to_json())
    del d["schedule"]["name"]
    with pytest.raises(ValueError):
        MLLMParallelPlan.from_json(json.dumps(d))
    with pytest.raises(ValueError):
        MLLMParallelPlan.from_json("{}")


def test_component_validation():
    with pytest.raises(AssertionError):
        SchedulePlan(name="gpipe", virtual_chunks=1, num_microbatches=8,
                     iteration_time=1.0, bubble_fraction=0.0,
                     num_devices=1, peak_activations_per_device=(1,),
                     tput_per_device=1.0)
    with pytest.raises(AssertionError):
        ContextPlan(method="greedy", num_ranks=2, block_size=8,
                    assignment=(0, 1), loads=(1.0, 1.0))
    with pytest.raises(AssertionError):
        StagePlan(("vision",), (1, 2), 1)
    with pytest.raises(AssertionError):
        ClusterSpec(0)
    with pytest.raises(AssertionError):
        WorkloadShape(text_len=0)


def test_describe_mentions_every_decision():
    enc, llm = small_profiles()
    plan = search_plan([enc], llm, ClusterSpec(4),
                       WorkloadShape(num_microbatches=8))
    text = plan.describe()
    assert plan.schedule.name in text
    assert "vision" in text and "llm" in text
    assert "cp     : none" in text         # no workload given
    assert plan.context is None


def test_split_devices_accepts_typed_plan(paper_vlm, paper_plan):
    from repro.core.modality_parallel import split_devices
    split = split_devices(paper_vlm,
                          list(range(paper_plan.pp_devices)),
                          plan=paper_plan)
    assert len(split["vision"]) == \
        paper_plan.stage_counts_by_name()["vision"]
    assert len(split["llm"]) == paper_plan.stage.llm_stages


def test_split_devices_respects_plan():
    from repro.core.modality_parallel import split_devices
    from repro.models.mllm import build_paper_mllm
    mllm = build_paper_mllm("valm", reduced=True)
    split = split_devices(mllm, list(range(8)),
                          plan={"vision": 2, "audio": 1})
    assert len(split["vision"]) == 2 and len(split["audio"]) == 1
    assert len(split["llm"]) == 5
