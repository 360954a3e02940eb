"""Named analysis entrypoints: the concrete programs the CLI lints.

Each entrypoint is a zero-argument callable returning ``List[Finding]``
for one named target the repo's correctness story depends on:

* ``kernel-bwd``        traced fused BAM backward (kernel path) —
                        jaxprlint: no-quadratic-intermediate,
                        dtype-drift, peak-live-bytes
* ``cp-allgather-bwd`` / ``cp-ring-bwd``
                        traced CP-body backwards on the kernel path
* ``train-step``        a tiny transformer train step routed through
                        the fused attention path
* ``xla-control``       the discriminating control: the XLA attention
                        path (single-device AND both CP bodies) MUST
                        trip no-quadratic-intermediate — if it stops
                        tripping, the rule has gone vacuous and THAT
                        is the finding
* ``schedulers``        all four schedulers x frozen/trainable
                        fixtures through every schedlint timeline rule
* ``auto-parallelize``  the winners ``auto_parallelize`` actually
                        emits on MLLM-shaped profile fixtures
* ``golden-plan``       the pinned 8-rank paper plan JSON: plan-level
                        consistency + its re-simulated timeline
* ``kernels``           kernellint over ``src/repro/kernels``

Controls invert the gate: an *expected* finding is success, silence is
the error. That keeps every negative rule in this package falsifiable
from the CLI itself, not just from the test suite.
"""
from __future__ import annotations

import os
from typing import Callable, Dict, List

from .findings import Finding, Severity, finding, register_rule
from . import jaxprlint, kernellint, schedlint

register_rule(
    "control-not-discriminating", "jaxprlint",
    "a deliberately-bad control stopped tripping its rule — the rule "
    "has gone vacuous")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
GOLDEN_PLAN = os.path.join(REPO_ROOT, "tests", "data",
                           "paper_mllm_8rank_plan.json")

#: traced sequence length for the jaxpr entrypoints (big enough that a
#: quadratic buffer is unmistakable, small enough to trace in seconds)
_T = 64
#: generous byte budget for the tiny traced programs — they hold a few
#: MB at most; a blown budget means something quadratic leaked in
_BUDGET_BYTES = 64 << 20


def _attention_case():
    import jax.numpy as jnp
    from repro.core import bam
    bits_np, pos_np = bam.build_sample_bits(
        [("text", 0, 16), ("mod", 1, 16), ("text", 0, 32)], _T)
    bits = jnp.asarray(bits_np)[None]
    pos = jnp.asarray(pos_np)[None]
    q = jnp.zeros((1, _T, 2, 8))
    return q, bits, pos


def _attn_grad_jaxpr(impl: str):
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import bam_attention
    q, bits, pos = _attention_case()

    def loss(q, k, v):
        return jnp.sum(bam_attention(q, k, v, bits, bits, pos, pos,
                                     impl=impl, block_q=16,
                                     block_k=16) ** 2)
    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)


def _cp_grad_jaxpr(method: str, impl: str):
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.core import context_parallel as cp
    q, bits, pos = _attention_case()
    mesh = jax.make_mesh((1,), ("cp",),
                         axis_types=(AxisType.Auto,))

    def loss(q, k, v):
        return jnp.sum(cp.cp_attention(
            mesh, "cp", q, k, v, bits, bits, pos, pos, method=method,
            impl=impl, block_q=16, block_k=16) ** 2)
    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)


def _jaxpr_rules(jaxpr, location: str) -> List[Finding]:
    out = jaxprlint.check_no_quadratic_intermediate(jaxpr, _T, location)
    out += jaxprlint.check_dtype_drift(jaxpr, location)
    out += jaxprlint.check_peak_live_bytes(
        jaxpr, location, budget_bytes=_BUDGET_BYTES)
    return out


def kernel_bwd() -> List[Finding]:
    """Fused BAM attention backward (kernel path) through jaxprlint."""
    return _jaxpr_rules(_attn_grad_jaxpr("bam_interpret"), "kernel-bwd")


def cp_allgather_bwd() -> List[Finding]:
    """All-gather CP-body backward (kernel path) through jaxprlint."""
    return _jaxpr_rules(_cp_grad_jaxpr("allgather", "bam_interpret"),
                        "cp-allgather-bwd")


def cp_ring_bwd() -> List[Finding]:
    """Ring CP-body backward (kernel path) through jaxprlint."""
    return _jaxpr_rules(_cp_grad_jaxpr("ring", "bam_interpret"),
                        "cp-ring-bwd")


def train_step() -> List[Finding]:
    """Trace one full train-step gradient of a tiny transformer whose
    attention routes through the fused kernel path, and run every
    jaxprlint rule over it."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig
    from repro.core import bam
    from repro.models import transformer as tf
    # every non-sequence dim stays < T, and T exceeds the kernels'
    # auto_block cap (128), so the ONLY tensors with two >= T dims are
    # genuine O(T^2) attention materializations — per-tile [block_q,
    # block_k] buffers stay below the bar
    T = 256
    cfg = ModelConfig(name="tiny-analysis", family="dense",
                      num_layers=2, d_model=32, num_heads=4,
                      num_kv_heads=2, d_ff=48, vocab_size=48,
                      dtype="float32", remat=False,
                      seq_shard_activations=False,
                      attn_impl="bam_interpret")
    bits_np, pos_np = bam.build_sample_bits(
        [("text", 0, 64), ("mod", 1, 64), ("text", 0, 128)], T)
    batch = {"tokens": jnp.zeros((1, T), jnp.int32),
             "labels": jnp.zeros((1, T), jnp.int32),
             "positions": jnp.asarray(pos_np)[None],
             "bits": jnp.asarray(bits_np)[None]}
    params = tf.init(jax.random.PRNGKey(0), cfg)

    def loss(p):
        from repro.training.steps import cross_entropy
        logits, _aux = tf.forward(p, cfg, batch)
        return cross_entropy(logits, batch["labels"])

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    out = jaxprlint.check_no_quadratic_intermediate(
        jaxpr, T, "train-step")
    out += jaxprlint.check_dtype_drift(jaxpr, "train-step")
    out += jaxprlint.check_peak_live_bytes(
        jaxpr, "train-step", budget_bytes=_BUDGET_BYTES)
    return out


def xla_control() -> List[Finding]:
    """The XLA attention path (single-device and both CP bodies) must
    trip no-quadratic-intermediate; if any of them traces clean the
    rule is vacuous and the CONTROL reports the error."""
    out: List[Finding] = []
    controls = [("xla-control/attn", _attn_grad_jaxpr("xla")),
                ("xla-control/cp-allgather",
                 _cp_grad_jaxpr("allgather", "xla")),
                ("xla-control/cp-ring", _cp_grad_jaxpr("ring", "xla"))]
    for loc, jaxpr in controls:
        hits = jaxprlint.quadratic_f32(jaxpr, _T)
        if not hits:
            out.append(finding(
                "control-not-discriminating", loc,
                "the XLA path traced NO O(Tq*Tk) f32 intermediate — "
                "no-quadratic-intermediate can no longer distinguish "
                "kernel from fallback"))
        else:
            out.append(finding(
                "control-not-discriminating", loc,
                f"control OK: XLA path trips with {len(hits)} "
                f"quadratic intermediates (e.g. "
                f"{hits[0][0]} f32{list(hits[0][1])})",
                severity=Severity.INFO))
    return out


# ---------------------------------------------------------------------------
# Schedule entrypoints
# ---------------------------------------------------------------------------

def _fixture_graphs():
    """MLLM-shaped schedule fixtures: (name, coarse chain) pairs
    covering trainable, frozen-encoder, and deeper frozen-heavy
    chains."""
    from repro.core import schedule as sch
    return [
        ("trainable-2", sch.chain_graph([
            sch.Stage("s0", 1.0, 2.0, bwd_w=1.0),
            sch.Stage("s1", 1.0, 2.0, bwd_w=1.0)])),
        ("frozen-head-2", sch.chain_graph([
            sch.Stage("enc", 1.0, 0.0),
            sch.Stage("llm", 1.0, 2.0, bwd_w=1.0)])),
        ("frozen-mid-4", sch.chain_graph([
            sch.Stage("enc", 0.8, 0.0),
            sch.Stage("proj", 0.2, 0.4, bwd_w=0.2),
            sch.Stage("llm0", 1.0, 2.0, bwd_w=1.0),
            sch.Stage("llm1", 1.0, 2.0, bwd_w=1.0)])),
    ]


def schedulers() -> List[Finding]:
    """Every schedule x every fixture through every schedlint timeline
    rule (chunked schedules on their refined chains)."""
    from repro.core import schedule as sch
    from repro.core.schedule.graph import refine_chain
    out: List[Finding] = []
    for fname, g in _fixture_graphs():
        for name in sch.SCHEDULES:
            if name in ("interleaved", "zb-v"):
                graph = refine_chain(g, 2)
                sim = sch.get_scheduler(name, virtual_chunks=2) \
                    .simulate(graph, 8)
            else:
                graph = g
                sim = sch.get_scheduler(name).simulate(graph, 8)
            out += schedlint.lint_timeline(
                graph, sim, location=f"schedulers/{name}/{fname}")
    return out


def auto_parallelize() -> List[Finding]:
    """The winners ``auto_parallelize`` actually emits, re-simulated
    and linted — the schedules a real launch would run."""
    import numpy as np
    from repro.core import pipeline as pp
    out: List[Finding] = []
    cases = [
        ("vlm-frozen", [pp.ModuleProfile(
            "vision", np.full(4, 1.0), frozen=True)], False),
        ("vlm-ft", [pp.ModuleProfile(
            "vision", np.full(4, 1.0), frozen=False)], True),
    ]
    for cname, encs, _ in cases:
        llm = pp.ModuleProfile("llm", np.full(8, 2.0), frozen=False)
        best = pp.auto_parallelize(encs, llm, 4, 8)
        # the winner dict IS a sim dict (items/device_of/peaks) plus
        # the chunked graph its stage indices refer to
        out += schedlint.lint_timeline(
            best["graph"], best,
            location=f"auto-parallelize/{cname}/{best['schedule']}")
    return out


def golden_plan() -> List[Finding]:
    """The pinned 8-rank paper plan: plan-level consistency, then the
    pinned (schedule, virtual_chunks) re-simulated on the paper
    profiles and linted as a timeline."""
    from repro.configs.paper_mllm import llm_config, vision_encoder_config
    from repro.core import pipeline as pp
    from repro.parallel.plan import MLLMParallelPlan
    plan = MLLMParallelPlan.load(GOLDEN_PLAN)
    out = schedlint.lint_plan(plan, location="golden-plan")
    encs = [pp.profile_from_config(
        vision_encoder_config(), 1024, frozen=True, name="vision")]
    llm = pp.profile_from_config(llm_config(), plan.text_len,
                                 frozen=False, name="llm")
    graph, sim = pp.simulate_plan(
        encs, llm, list(plan.stage.encoder_stages),
        plan.stage.llm_stages, plan.schedule.num_microbatches,
        schedule=plan.schedule.name,
        virtual_chunks=plan.schedule.virtual_chunks,
        frozen_aware=plan.stage.frozen_aware)
    out += schedlint.lint_timeline(graph, sim,
                                   location="golden-plan/timeline")
    return out


def kernels() -> List[Finding]:
    """kernellint over src/repro/kernels (AST + dynamic checks)."""
    return kernellint.lint_kernels()


#: name -> entrypoint (CLI order = reporting order)
ENTRYPOINTS: Dict[str, Callable[[], List[Finding]]] = {
    "kernels": kernels,
    "kernel-bwd": kernel_bwd,
    "cp-allgather-bwd": cp_allgather_bwd,
    "cp-ring-bwd": cp_ring_bwd,
    "train-step": train_step,
    "xla-control": xla_control,
    "schedulers": schedulers,
    "auto-parallelize": auto_parallelize,
    "golden-plan": golden_plan,
}
