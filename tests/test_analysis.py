"""repro.analysis: every rule must catch its seeded violation and stay
quiet on the real repo.

The seeded mutations are the falsifiability half of the subsystem: a
valid schedule is doctored one invariant at a time (B before F, two
items overlapping on a device, a W pass on a frozen stage, a
program-order inversion that cross-waits two devices) and the matching
rule — and only a relevant set of rules — must fire. The kernel lint
rules get deliberately-bad source snippets; jaxprlint gets the XLA
attention path as its tripping control (see test_kernels /
test_context_parallel for the kernel-side controls, which import the
promoted helpers from here)."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import __main__ as cli
from repro.analysis import entrypoints, jaxprlint, kernellint, schedlint
from repro.analysis.findings import (Finding, RULES, Severity,
                                     filter_findings, finding, gate)
from repro.core import schedule as sch
from repro.core.modality_parallel import execute_schedule
from repro.core.schedule.memory import (MemoryModelMismatch,
                                        diff_activation_traces,
                                        simulated_activation_trace,
                                        validate_schedule_memory)
from repro.core.schedule.simulator import item_id

M = 4


def two_stage(frozen_head=False):
    return sch.chain_graph(
        [sch.Stage("enc", 1.0, 0.0) if frozen_head
         else sch.Stage("s0", 1.0, 2.0, bwd_w=1.0),
         sch.Stage("s1", 1.0, 2.0, bwd_w=1.0)])


def sim_of(schedule="zb-h1", frozen_head=False):
    g = two_stage(frozen_head)
    return g, sch.get_scheduler(schedule).simulate(g, M)


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# findings spine
# ---------------------------------------------------------------------------

def test_finding_requires_registered_rule():
    with pytest.raises(KeyError):
        finding("not-a-rule", "here", "boom")


def test_filter_rejects_unknown_rule_ids():
    fs = [finding("fbw-order", "x", "y")]
    assert filter_findings(fs, ["fbw-order"]) == fs
    assert filter_findings(fs, ["device-overlap"]) == []
    with pytest.raises(KeyError):
        filter_findings(fs, ["no-such-rule"])


def test_gate_severity_policy():
    err = finding("fbw-order", "x", "y")
    warn = finding("dtype-drift", "x", "y")   # WARNING by default
    info = Finding("fbw-order", Severity.INFO, "x", "y")
    assert gate([err]) and gate([err], strict=True)
    assert not gate([warn]) and gate([warn], strict=True)
    assert not gate([info]) and not gate([info], strict=True)


def test_item_id_format():
    assert item_id((0.0, 1.0, 3, "B", 2, 5)) == "B(s2,m5)@d3"


# ---------------------------------------------------------------------------
# schedlint: valid timelines are clean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", sch.SCHEDULES)
@pytest.mark.parametrize("frozen_head", [False, True])
def test_all_schedulers_lint_clean(schedule, frozen_head):
    g = two_stage(frozen_head)
    if schedule in ("interleaved", "zb-v"):
        g = sch.refine_chain(g, 2)
        sim = sch.get_scheduler(schedule, virtual_chunks=2).simulate(g, M)
    else:
        sim = sch.get_scheduler(schedule).simulate(g, M)
    assert schedlint.lint_timeline(g, sim) == []


# ---------------------------------------------------------------------------
# schedlint: each seeded violation trips its rule
# ---------------------------------------------------------------------------

def _replace_item(items, match, **changes):
    """Replace the first item matching (kind, stage, mb)."""
    out = []
    done = False
    for it in items:
        s0, e0, dev, kind, s, m = it
        if not done and (kind, s, m) == match:
            d = {"start": s0, "end": e0, "dev": dev, "kind": kind,
                 "s": s, "m": m, **changes}
            it = (d["start"], d["end"], d["dev"], d["kind"], d["s"],
                  d["m"])
            done = True
        out.append(it)
    assert done, f"no item {match}"
    return out


def test_seeded_b_before_f_trips_fbw_order():
    g, sim = sim_of()
    f = next(it for it in sim["items"] if it[3:] == ("F", 1, 0))
    sim["items"] = _replace_item(sim["items"], ("B", 1, 0),
                                 start=f[0] - 2.0, end=f[0] - 1.0)
    assert "fbw-order" in rules_of(schedlint.lint_timeline(g, sim))


def test_seeded_overlap_trips_device_overlap():
    g, sim = sim_of()
    a = next(it for it in sim["items"] if it[3:] == ("F", 0, 0))
    # stretch the second item on the same device into the first
    sim["items"] = _replace_item(sim["items"], ("F", 0, 1),
                                 start=a[0] + 0.25 * (a[1] - a[0]))
    assert "device-overlap" in rules_of(schedlint.lint_timeline(g, sim))


def test_seeded_w_on_frozen_stage_trips_frozen_no_w():
    g, sim = sim_of(frozen_head=True)
    t = max(it[1] for it in sim["items"])
    sim["items"] = list(sim["items"]) + [(t, t + 1.0, 0, "W", 0, 0)]
    assert "frozen-no-w" in rules_of(schedlint.lint_timeline(g, sim))


def test_seeded_dropped_item_trips_missing_item():
    g, sim = sim_of()
    sim["items"] = [it for it in sim["items"]
                    if it[3:] != ("B", 0, 2)]
    found = schedlint.lint_timeline(g, sim)
    assert any(f.rule == "missing-item" and "B(s0,m2)" in f.location
               for f in found)


def test_seeded_claim_doctoring_trips_peak_claim():
    g, sim = sim_of()
    sim["peak_activations_per_device"] = \
        [p + 1 for p in sim["peak_activations_per_device"]]
    assert "peak-claim" in rules_of(schedlint.lint_timeline(g, sim))


def test_gpipe_style_timeline_trips_activation_cap():
    """All forwards before any backward overflows 1F1B's
    depth_from_end envelope ([2, 1] on a 2-stage chain) — the schedule
    memory-policy violation the rule exists for."""
    g = two_stage()
    items = []
    for m in range(M):                       # all F first
        items.append((float(m), m + 1.0, 0, "F", 0, m))
        items.append((m + 1.0, m + 2.0, 1, "F", 1, m))
    t = M + 2.0
    for m in range(M):                       # then all B
        items.append((t, t + 1.0, 1, "B", 1, m))
        items.append((t + 1.0, t + 2.0, 0, "B", 0, m))
        t += 2.0
    sim = {"items": items, "device_of": [0, 1]}
    found = schedlint.lint_timeline(g, sim)
    assert "activation-cap" in rules_of(found)
    assert rules_of(found) <= {"activation-cap"}


def test_seeded_cross_wait_trips_send_recv_cycle():
    """The classic 2-device cross-wait: dev0 blocks on a cotangent
    dev1 only produces after a forward dev0 has scheduled later. The
    async-send/blocking-recv lowering deadlocks; the lint finds the
    4-item cycle instead of hanging a job."""
    g = two_stage()
    items = [
        (0.0, 1.0, 0, "F", 0, 0),
        (1.0, 2.0, 0, "B", 0, 0),            # needs B(s1,m0) — not yet
        (2.0, 3.0, 0, "F", 0, 1),
        (1.0, 2.0, 1, "F", 1, 0),
        (3.0, 4.0, 1, "F", 1, 1),            # needs F(s0,m1)
        (4.0, 5.0, 1, "B", 1, 1),
        (5.0, 6.0, 1, "B", 1, 0),
        (6.0, 7.0, 0, "B", 0, 1),
    ]
    sim = {"items": items, "device_of": [0, 1]}
    found = schedlint.lint_timeline(g, sim)
    assert "send-recv-cycle" in rules_of(found)
    msg = next(f for f in found if f.rule == "send-recv-cycle").message
    assert "B(s0,m0)@d0" in msg and "B(s1,m0)@d1" in msg


# ---------------------------------------------------------------------------
# schedlint: plan-level
# ---------------------------------------------------------------------------

def test_golden_plan_lints_clean():
    from repro.parallel.plan import MLLMParallelPlan
    plan = MLLMParallelPlan.load(entrypoints.GOLDEN_PLAN)
    assert schedlint.lint_plan(plan) == []


def test_doctored_plan_trips_plan_consistency():
    from repro.parallel.plan import MLLMParallelPlan
    plan = MLLMParallelPlan.load(entrypoints.GOLDEN_PLAN)
    bad = dataclasses.replace(
        plan, schedule=dataclasses.replace(plan.schedule,
                                           bubble_fraction=1.5))
    assert "plan-consistency" in rules_of(schedlint.lint_plan(bad))
    bad2 = dataclasses.replace(
        plan, context=dataclasses.replace(
            plan.context,
            assignment=tuple(plan.context.assignment[:-1])
            + (plan.context.num_ranks + 3,)))
    assert "plan-consistency" in rules_of(schedlint.lint_plan(bad2))


# ---------------------------------------------------------------------------
# jaxprlint
# ---------------------------------------------------------------------------

def test_quadratic_f32_trips_on_materialized_scores():
    T = 64
    a = jnp.zeros((T, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: jnp.sum(x @ x.T))(a)
    hits = jaxprlint.quadratic_f32(jaxpr, T)
    assert hits and any(shape == (T, T) for _p, shape, _d in hits)
    assert jaxprlint.check_no_quadratic_intermediate(jaxpr, T, "t")


def test_collect_avals_recurses_into_scan():
    def f(x):
        return jax.lax.scan(lambda c, _: (c + 1.0, c), x, None,
                            length=3)[0]
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((4,)))
    prims = {p for p, _s, _d in jaxprlint.collect_avals(jaxpr)}
    assert "add" in prims                    # from inside the scan body


def test_peak_live_bytes_linear_chain():
    # x f32[1024] -> y = x*2 -> z = y*3: two adjacent values live at a
    # time, 2 * 4096 bytes
    jaxpr = jax.make_jaxpr(lambda x: (x * 2.0) * 3.0)(
        jnp.zeros((1024,), jnp.float32))
    assert jaxprlint.peak_live_bytes(jaxpr) == 2 * 4096
    assert jaxprlint.check_peak_live_bytes(jaxpr, "t",
                                           budget_bytes=100)
    assert jaxprlint.check_peak_live_bytes(jaxpr, "t",
                                           budget_bytes=1 << 20) == []
    info = jaxprlint.check_peak_live_bytes(jaxpr, "t")
    assert [f.severity for f in info] == [Severity.INFO]


def test_dtype_drift_threshold():
    big = jnp.zeros((256, 256), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda x: x.astype(jnp.float32))(big)
    assert jaxprlint.check_dtype_drift(jaxpr, "t")
    small = jnp.zeros((16,), jnp.bfloat16)
    jaxpr_s = jax.make_jaxpr(lambda x: x.astype(jnp.float32))(small)
    assert jaxprlint.check_dtype_drift(jaxpr_s, "t") == []
    # threshold is tunable
    assert jaxprlint.check_dtype_drift(jaxpr_s, "t", min_elements=8)


# ---------------------------------------------------------------------------
# kernellint: seeded-bad source snippets
# ---------------------------------------------------------------------------

BAD_ARITY = """
import jax.experimental.pallas as pl
out = pl.pallas_call(
    kern,
    grid=(2, 2),
    in_specs=[pl.BlockSpec((16, 16), lambda i: (i, 0))],
)
"""

BAD_RANK = """
import jax.experimental.pallas as pl
out = pl.pallas_call(
    kern,
    grid=(2, 2),
    in_specs=[pl.BlockSpec((16, 16), lambda i, j: (i, j, 0))],
)
"""

BAD_PREFETCH_ARITY = """
from jax.experimental.pallas import tpu as pltpu
spec = pltpu.PrefetchScalarGridSpec(
    num_scalar_prefetch=3,
    grid=(2, 2),
    in_specs=[pl.BlockSpec((16, 16), lambda i, j: (i, j))],
)
"""

GOOD_CAPTURE = """
import jax.experimental.pallas as pl
n_rep = 4
out = pl.pallas_call(
    kern,
    grid=(2, 2),
    in_specs=[pl.BlockSpec((16, 16),
                           lambda i, j, n_rep=n_rep: (i, j))],
)
"""

GOOD_NAMED = """
import jax.experimental.pallas as pl
def imap(i, j):
    return (i, 0)
out = pl.pallas_call(
    kern,
    grid=(2, 2),
    in_specs=[pl.BlockSpec((16, 16), imap)],
)
"""

NON_LITERAL_GRID = """
import jax.experimental.pallas as pl
out = pl.pallas_call(
    kern,
    grid=grid,
    in_specs=[pl.BlockSpec((16, 16), lambda i: (i,))],
)
"""


def test_bad_index_arity_trips():
    found = kernellint.lint_source(BAD_ARITY)
    assert rules_of(found) == {"blockspec-index-arity"}
    assert "expected 2" in found[0].message


def test_bad_rank_trips():
    found = kernellint.lint_source(BAD_RANK)
    assert rules_of(found) == {"blockspec-rank-mismatch"}


def test_prefetch_arity_counts_scalar_operands():
    found = kernellint.lint_source(BAD_PREFETCH_ARITY)
    assert rules_of(found) == {"blockspec-index-arity"}
    assert "expected 5" in found[0].message


def test_capture_default_args_and_named_maps_are_clean():
    assert kernellint.lint_source(GOOD_CAPTURE) == []
    assert kernellint.lint_source(GOOD_NAMED) == []


def test_non_literal_grid_is_skipped_not_guessed():
    assert kernellint.lint_source(NON_LITERAL_GRID) == []


def test_real_kernels_lint_clean():
    assert kernellint.lint_kernels() == []


@pytest.mark.parametrize("block,ok", [
    ((1, 128, 1, 128), False),   # token-major, one head: refused
    ((1, 128, 4, 128), True),    # all heads: equals the array's dims
    ((1, 100, 4, 128), True),    # second-to-last is free once -1 fits
    ((1, 128, 4, 64), False),    # lane dim neither 128k nor the array's
])
def test_tpu_block_tiling_seeded(block, ok):
    """The tiling rule flags the token-major one-head block the chip
    refused, on the CPU and without a TPU compiler."""
    from jax.experimental import pallas as pl

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    shape = (1, 256, 4, 128)
    spec = pl.BlockSpec(block, lambda b, i: (b, i, 0, 0))
    fn = pl.pallas_call(copy, grid=(1, 2), in_specs=[spec], out_specs=spec,
                        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
                        interpret=True)
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros(shape, jnp.float32))
    found = kernellint.tiling_findings(jaxpr, "seeded")
    assert (found == []) == ok, found
    assert all(f.rule == "tpu-block-tiling" for f in found)


def test_coverage_findings_catch_missing_tile():
    dense = np.ones((8, 8), bool)
    bm = types.SimpleNamespace(
        nq=2, nk=2,
        # q-major grid silently lacks the (1, 1) tile
        q_steps=((0, 0, 1, 0, 1), (0, 1, 0, 1, 1), (1, 0, 1, 1, 1)),
        k_steps=((0, 0, 1, 0, 1), (1, 0, 0, 1, 1), (0, 1, 1, 0, 1),
                 (1, 1, 0, 1, 1)))
    found = kernellint._coverage_findings(dense, bm, 4, 4, "seeded")
    assert any(f.rule == "block-map-coverage"
               and "q_block=1, k_block=1" in f.message for f in found)


# ---------------------------------------------------------------------------
# executor trace <-> memory-model diff (satellite: shared item ids)
# ---------------------------------------------------------------------------

def _toy(S):
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (S, 8, 8)) * 0.1}
    mbs = jax.random.normal(jax.random.fold_in(key, 1), (M, 1, 2, 8))
    return (lambda lp, x: x + jnp.tanh(x @ lp["w"])), params, mbs


def test_executor_trace_matches_simulated_walk():
    g, sim = sim_of("1f1b")
    fn, params, mbs = _toy(len(g.stages))
    res = execute_schedule(fn, params, mbs, g, sim)
    assert res["activation_trace"] == simulated_activation_trace(g, sim)
    assert res["activation_nbytes"] == 2 * 8 * 4


def test_trace_diff_names_first_diverging_item():
    """A duplicated F makes the model count 2 live activations where
    the executor's real store holds 1 (same key overwritten) — the
    diff pins the exact item, with bytes."""
    g, sim = sim_of("1f1b")
    items = list(sim["items"])
    i = next(j for j, it in enumerate(items) if it[3:] == ("F", 0, 0))
    items.insert(i + 1, items[i])
    sim["items"] = items
    fn, params, mbs = _toy(len(g.stages))
    res = execute_schedule(fn, params, mbs, g, sim)
    div = diff_activation_traces(simulated_activation_trace(g, sim),
                                 res["activation_trace"],
                                 res["activation_nbytes"])
    assert div is not None
    iid, sim_live, exe_live, sim_bytes, exe_bytes = div
    assert iid == "F(s0,m0)@d0"
    assert (sim_live, exe_live) == (2, 1)
    assert (sim_bytes, exe_bytes) == (2 * 64, 64)


def test_mismatch_carries_divergence_field():
    g = two_stage()
    sim = sch.get_scheduler("zb-h1").simulate(g, M)
    sim["peak_activations_per_device"] = \
        [p + 1 for p in sim["peak_activations_per_device"]]
    with pytest.raises(MemoryModelMismatch) as ei:
        validate_schedule_memory(g, M, "zb-h1", sim=sim)
    # claim-only doctoring: the timelines agree item-for-item
    assert ei.value.first_divergence is None
    assert "timelines agree" in str(ei.value)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_list_exits_zero(capsys):
    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "kernels" in out and "fbw-order" in out


def test_cli_kernels_entrypoint_clean(capsys):
    assert cli.main(["--entrypoint", "kernels", "--strict"]) == 0
    assert "clean" in capsys.readouterr().out


def test_cli_reports_entrypoint_crash(monkeypatch, capsys):
    def boom():
        raise RuntimeError("kaboom")
    monkeypatch.setitem(entrypoints.ENTRYPOINTS, "kernels", boom)
    assert cli.main(["--entrypoint", "kernels"]) == 1
    assert "entrypoint-crash" in capsys.readouterr().out


def test_cli_rejects_unknown_rule():
    with pytest.raises(SystemExit):
        cli.main(["--entrypoint", "kernels", "--rule", "no-such-rule"])


# ---------------------------------------------------------------------------
# property sweep: auto_parallelize winners always lint clean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("enc_layers,llm_layers,devices,mbs,frozen", [
    (2, 4, 2, 4, True),
    (4, 8, 4, 8, False),
    (1, 6, 3, 6, True),
    (3, 6, 4, 4, False),
])
def test_auto_parallelize_winners_lint_clean(enc_layers, llm_layers,
                                             devices, mbs, frozen):
    """Deterministic slice of the property test (the hypothesis-driven
    version lives in test_analysis_properties.py): whatever schedule
    auto_parallelize picks, its shipped timeline passes every schedlint
    rule."""
    from repro.core import pipeline as pp
    encs = [pp.ModuleProfile("enc", np.full(enc_layers, 1.0),
                             frozen=frozen)]
    llm = pp.ModuleProfile("llm", np.full(llm_layers, 2.0),
                           frozen=False)
    best = pp.auto_parallelize(encs, llm, devices, mbs)
    assert schedlint.lint_timeline(best["graph"], best) == []


# ---------------------------------------------------------------------------
# launcher gate: resolve_plan refuses a plan schedlint rejects
# ---------------------------------------------------------------------------

def test_resolve_plan_lint_gate(tmp_path):
    """The training launcher runs schedlint on the resolved plan before
    step 0: a doctored plan dies with the findings in the message, and
    --no-lint (args.lint=False) bypasses the gate."""
    import argparse
    import dataclasses

    from repro.launch.train import resolve_plan
    from repro.models.mllm import build_paper_mllm
    from repro.parallel import MLLMParallelPlan

    plan = MLLMParallelPlan.load(entrypoints.GOLDEN_PLAN)
    bad = dataclasses.replace(
        plan, schedule=dataclasses.replace(plan.schedule,
                                           bubble_fraction=1.5))
    path = tmp_path / "bad_plan.json"
    bad.save(str(path))
    mllm = build_paper_mllm("vlm", reduced=True, text_len=plan.text_len)
    args = argparse.Namespace(plan=str(path), plan_out=None,
                              seq=plan.text_len, lint=True)
    with pytest.raises(SystemExit, match="plan-consistency"):
        resolve_plan(mllm, args)
    args.lint = False
    got, _executor = resolve_plan(mllm, args)
    assert got.schedule.bubble_fraction == 1.5


# ---------------------------------------------------------------------------
# lint_spmd_program: the emitted wave/ppermute program (not the model)
# ---------------------------------------------------------------------------

def spmd_program(schedule="zb-h1"):
    import copy

    from repro.parallel.spmd import compile_spmd_program
    chunked = schedule in ("interleaved", "zb-v")
    g = sch.refine_chain(two_stage(), 2) if chunked else two_stage()
    kwargs = {"virtual_chunks": 2} if chunked else {}
    sim = sch.get_scheduler(schedule, **kwargs).simulate(g, M)
    return copy.deepcopy(compile_spmd_program(g, sim))


@pytest.mark.parametrize("schedule", sch.SCHEDULES)
def test_compiled_spmd_programs_lint_clean(schedule):
    """What compile_spmd_program emits for every scheduler passes its
    own static contract: legal ppermute rounds, fresh send buffers,
    every cross-device input delivered before use."""
    assert schedlint.lint_spmd_program(spmd_program(schedule)) == []


def _first_round(prog, kind="fwd"):
    for w, wave in enumerate(prog.waves):
        for rnd in wave.rounds:
            if rnd.kind == kind:
                return w, rnd
    raise AssertionError(f"no {kind} round emitted")


def test_seeded_late_round_trips_send_recv_cycle():
    """Delaying a delivery past its consumer's wave is the blocking
    recv that never unblocks — and the moved round now ships a stale
    buffer too."""
    prog = spmd_program()
    w, rnd = _first_round(prog)
    prog.waves[w].rounds.remove(rnd)
    prog.waves[w + 1].rounds.append(rnd)
    found = schedlint.lint_spmd_program(prog)
    assert "send-recv-cycle" in rules_of(found)
    msg = next(f for f in found
               if f.rule == "send-recv-cycle").message
    assert "never satisfied" in msg and "device" in msg


def test_seeded_early_round_trips_stale_send():
    """Hoisting a round to an earlier wave makes it ship whatever the
    source device computed THEN — a stale send buffer."""
    prog = spmd_program()
    w, rnd = _first_round(prog, kind="bwd")
    assert w > 0
    prog.waves[w].rounds.remove(rnd)
    prog.waves[w - 1].rounds.append(rnd)
    found = schedlint.lint_spmd_program(prog)
    assert "ppermute-program" in rules_of(found)
    assert any("stale send" in f.message for f in found)


def test_seeded_duplicate_destination_trips_ppermute_program():
    import dataclasses as dc
    prog = spmd_program()
    w, rnd = _first_round(prog)
    t = rnd.transfers[0]
    rnd.transfers.append(dc.replace(t, src_dev=t.src_dev + 1))
    found = schedlint.lint_spmd_program(prog)
    assert "ppermute-program" in rules_of(found)
    assert any("not a partial permutation" in f.message for f in found)


def test_seeded_self_send_trips_ppermute_program():
    prog = spmd_program()
    _w, rnd = _first_round(prog)
    rnd.transfers[0].dst_dev = rnd.transfers[0].src_dev
    found = schedlint.lint_spmd_program(prog)
    assert "ppermute-program" in rules_of(found)
    assert any("self-send" in f.message for f in found)


def test_executor_contract_carries_spmd_program_lint():
    """An SPMD-mode executor contract ships its compiled program, and
    lint_executor_contract statically validates the ACTUAL emitted
    rounds under the contract's location."""
    g, sim = sim_of("zb-h1")
    prog = spmd_program()
    executor = {"sim_graph": g, "schedule": sim, "spmd_program": prog}
    assert schedlint.lint_executor_contract(executor) == []
    w, rnd = _first_round(prog)
    prog.waves[w].rounds.remove(rnd)
    prog.waves[w + 1].rounds.append(rnd)
    found = schedlint.lint_executor_contract(executor)
    assert "send-recv-cycle" in rules_of(found)
    assert all(f.location.startswith("executor:spmd")
               for f in found)
