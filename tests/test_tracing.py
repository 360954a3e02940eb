"""The program's tracing: the trainer's host spans, read back from a
profiler trace recorded on the CPU, and the named scopes in the compiled
guarded step of a tiny VLM."""
import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.optim import optimizer as opt
from repro.resilience import (CheckpointManager, CursorStream, Fault,
                              FaultInjector, FaultPlan, HealthMonitor,
                              MonitorConfig, ResilientTrainer,
                              default_controls, init_health,
                              make_resilient_train_step)

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro")
#: the benchmark's own span names, which the program must not take
BENCHMARK_SPANS = {"data", "train_step", "window"}
#: what the trainer writes inside each ``trainer.step``, in loop order:
#: the dispatch, the next step's batch drawn while the device runs this
#: one, the host read; a pass with no batch drawn ahead (the first, and
#: the first after a restore) draws its own batch first
STEP_SPANS = ["trainer.dispatch", "trainer.batch", "trainer.health_read"]
FIRST_STEP_SPANS = ["trainer.batch"] + STEP_SPANS
SCOPES = ("encoder", "projector", "merge", "llm", "attention", "sdpa", "mlp",
          "lm_head", "optimizer", "health")
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def _loss_fn(params, batch):
    return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2), {}


def _batches():
    rng = np.random.default_rng(0)
    while True:
        x = rng.normal(size=(8, 4)).astype(np.float32)
        yield {"x": jnp.asarray(x),
               "y": jnp.asarray(x.sum(1, keepdims=True))}


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    """The trainer's spans, (start_ns, end_ns, name) in start order, from
    three steps with a save every two and a NaN step at step 2 that rolls
    back to the save: iterations 0, 1 (+ save), 2 (rollback: restore,
    which drops the batch drawn ahead), 2 again."""
    ckpt = tmp_path_factory.mktemp("ckpt")
    log = tmp_path_factory.mktemp("trace")
    params = {"w": jnp.zeros((4, 1), jnp.float32)}
    ocfg = opt.AdamWConfig(lr=1e-2, warmup_steps=0, schedule="constant")
    step_fn = jax.jit(make_resilient_train_step(_loss_fn, ocfg),
                      donate_argnums=(0, 1, 2))
    trainer = ResilientTrainer(
        step_fn, params, opt.init(ocfg, params), CursorStream(_batches),
        monitor=HealthMonitor(MonitorConfig(skip_limit=0)),
        manager=CheckpointManager(str(ckpt)),
        injector=FaultInjector(FaultPlan.make([Fault("nan_grads", 2)])),
        ckpt_every=2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log), profiler_options=opts)
    try:
        summary = trainer.run(3)
    finally:
        jax.profiler.stop_trace()
    assert summary["rollbacks"] == 1
    assert sorted(summary["losses"]) == [0, 1, 2]
    path = sorted(glob.glob(os.path.join(str(log), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    out = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("trainer.") or e.name in BENCHMARK_SPANS]
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _inside(spans, outer):
    s, e, _ = outer
    return [sp for sp in spans if sp is not outer and s <= sp[0]
            and sp[1] <= e]


def test_every_iteration_runs_its_spans_in_loop_order(spans):
    steps = [sp for sp in spans if sp[2] == "trainer.step"]
    assert len(steps) == 4
    # the first pass and the first after the rollback's restore draw in
    # series; the others dispatch on the batch drawn in the pass before
    want = [FIRST_STEP_SPANS, STEP_SPANS, STEP_SPANS, FIRST_STEP_SPANS]
    for sp, order in zip(steps, want):
        names = [n for _, _, n in _inside(spans, sp)
                 if n in STEP_SPANS]
        assert names == order, names


def test_save_and_restore_spans_sit_in_their_iterations(spans):
    steps = [sp for sp in spans if sp[2] == "trainer.step"]
    inner = [[n for _, _, n in _inside(spans, sp)] for sp in steps]
    assert [n.count("trainer.checkpoint") for n in inner] == [0, 1, 0, 0]
    assert [n.count("trainer.restore") for n in inner] == [0, 0, 1, 0]
    # the save follows the health read, the restore the rollback's read
    assert inner[1][-1] == "trainer.checkpoint"
    assert inner[2][-1] == "trainer.restore"


def test_span_counts_are_the_work_done(spans):
    count = {n: sum(1 for *_, m in spans if m == n)
             for n in ("trainer.step", "trainer.batch", "trainer.dispatch",
                       "trainer.health_read", "trainer.checkpoint",
                       "trainer.restore")}
    # four iterations: four dispatches and host reads; six batches
    # drawn, one ahead in each pass and one in series in the first and
    # in the first after the restore, which dropped the batch drawn
    # ahead of the rolled-back step; one save, one restore; nothing
    # outside a step
    assert count == {"trainer.step": 4, "trainer.batch": 6,
                     "trainer.dispatch": 4, "trainer.health_read": 4,
                     "trainer.checkpoint": 1, "trainer.restore": 1}
    steps = [sp for sp in spans if sp[2] == "trainer.step"]
    assert all(any(s[0] <= sp[0] and sp[1] <= s[1] for s in steps)
               for sp in spans)


def test_no_program_span_takes_a_benchmark_name(spans):
    assert not [n for *_, n in spans if n in BENCHMARK_SPANS]
    named = set()
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            named |= set(re.findall(
                r"(?:Step)?TraceAnnotation\(\s*[\"']([^\"']+)[\"']",
                f.read()))
    assert named >= {"trainer.step", "trainer.batch", "trainer.dispatch",
                     "trainer.health_read", "trainer.checkpoint",
                     "trainer.restore"}
    assert not named & BENCHMARK_SPANS


def _strip(component):
    m = _WRAPPED.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPED.match(component)
    return component


def _op_names(attn_impl="xla"):
    """Every ``op_name`` of the compiled guarded step of a tiny VLM."""
    from repro.models.mllm import build_paper_mllm
    from repro.training import steps
    mllm = build_paper_mllm("vlm", reduced=True, text_len=32)
    mllm.llm_cfg = mllm.llm_cfg.replace(attn_impl=attn_impl)
    params = mllm.init(jax.random.PRNGKey(0))
    frozen = mllm.frozen_mask(params)
    ocfg = opt.AdamWConfig()
    _, loss_fn = steps.make_mllm_train_step(mllm, ocfg)
    step = jax.jit(make_resilient_train_step(loss_fn, ocfg, frozen))
    enc = mllm.encoders["vision"]
    rng = np.random.default_rng(0)
    batch = {"text_tokens": jnp.asarray(rng.integers(0, 100, (2, 32)),
                                        jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 100, (2, 32)), jnp.int32),
             "vision_embeds": jnp.asarray(rng.normal(
                 size=(2, enc.num_tokens, enc.cfg.d_model)), jnp.float32)}
    text = step.lower(params, opt.init(ocfg, params, frozen), init_health(),
                      batch, default_controls()).compile().as_text()
    return [n.split(";")[0] for n in re.findall(r'op_name="([^"]*)"', text)]


@pytest.fixture(scope="module")
def op_names():
    return _op_names()


@pytest.mark.parametrize("scope", SCOPES)
def test_compiled_step_carries_scope(op_names, scope):
    assert any(scope in map(_strip, n.split("/")) for n in op_names), scope


@pytest.mark.parametrize("scope", ["llm", "sdpa", "lm_head", "projector"])
def test_backward_carries_scope_under_transpose(op_names, scope):
    """Backward ops name the scope inside ``transpose(jvp(...))``."""
    assert any(any(c.startswith("transpose(jvp(") for c in n.split("/"))
               and scope in map(_strip, n.split("/")) for n in op_names), \
        scope


def test_kernel_attention_runs_under_sdpa():
    """The Pallas BAM path (interpret mode here) is the ``sdpa`` part of
    the LLM's attention too, forward and backward."""
    names = [[_strip(c) for c in n.split("/")] for n in
             _op_names("bam_interpret")]
    assert any("llm" in n and "sdpa" in n for n in names)


# ---------------------------------------------------------------------------
# The SPMD pipeline step: stage, handoff and reduction scopes
# ---------------------------------------------------------------------------

#: the pipeline runner's own scopes, and the model scopes its stage fns
#: carry as the one-chip step does
SPMD_SCOPES = ("handoff", "pipeline_reduce", "encoder", "projector", "llm",
               "lm_head", "attention", "sdpa", "mlp")

_SPMD_CHILD = r'''
import json
import re

import numpy as np

import jax

from repro.data.synthetic import MultimodalDataset
from repro.launch.train import spmd_parts
from repro.models.mllm import build_paper_mllm
from repro.optim import optimizer as opt
from repro.parallel import ClusterSpec, WorkloadShape, parallelize
from repro.resilience import (default_controls, init_health,
                              make_resilient_train_step)

mllm = build_paper_mllm("vlm", reduced=True, text_len=16)
plan = parallelize(mllm, ClusterSpec(num_devices=3),
                   WorkloadShape(text_len=16, num_microbatches=2,
                                 microbatch_size=1, block_size=8))
ex = plan.apply(mllm, text_len=16, mode="spmd")
bundle, rep, vgf = spmd_parts(mllm, plan, ex)
params = jax.jit(lambda k: bundle.partition(mllm.init(k)),
                 out_shardings=rep)(jax.random.PRNGKey(0))
mask = bundle.frozen_masks(params)
ocfg = opt.AdamWConfig()
step = jax.jit(make_resilient_train_step(None, ocfg, mask,
                                         value_and_grad_fn=vgf))
batch = next(iter(MultimodalDataset(
    vocab_size=mllm.llm_cfg.vocab_size, text_len=16, batch_size=2,
    encoder_dims={n: e.cfg.d_model for n, e in mllm.encoders.items()},
    encoder_tokens={n: e.num_tokens for n, e in mllm.encoders.items()},
    modality_ids={n: e.modality_id for n, e in mllm.encoders.items()},
    seed=0)))
text = step.lower(params, opt.init(ocfg, params, mask), init_health(),
                  batch, default_controls()).compile().as_text()
names = sorted({n.split(";")[0]
                for n in re.findall(r'op_name="([^"]*)"', text)})
print("SPMD " + json.dumps({"stages": len(bundle.specs), "names": names}))
'''


@pytest.fixture(scope="module")
def spmd_step():
    """The stage count and every ``op_name`` of the compiled guarded
    SPMD step of a tiny VLM on three forced host devices."""
    import json

    from .helpers import run_in_subprocess
    out = run_in_subprocess(_SPMD_CHILD, 3)
    line = next(s for s in out.splitlines() if s.startswith("SPMD "))
    got = json.loads(line[len("SPMD "):])
    return got["stages"], got["names"]


def _scoped(names, scope):
    return [n for n in names if scope in map(_strip, n.split("/"))]


def test_spmd_step_carries_every_stage_scope(spmd_step):
    """Each stage's work sits under ``stage{s}``, with a child scope for
    the kind of work: ``F`` everywhere, ``B`` on the stages that
    propagate or take gradients."""
    stages, names = spmd_step
    assert stages >= 3
    for s in range(stages):
        chains = [[_strip(c) for c in n.split("/")]
                  for n in _scoped(names, f"stage{s}")]
        assert chains, f"stage{s}"
        assert any(c[c.index(f"stage{s}") + 1] == "F" for c in chains
                   if c.index(f"stage{s}") + 1 < len(c)), f"stage{s}/F"
    kids = {c[c.index(s) + 1] for n in names
            for c in [[_strip(x) for x in n.split("/")]]
            for s in c if re.fullmatch(r"stage\d+", s) and
            c.index(s) + 1 < len(c)}
    assert {"F", "B"} <= kids


@pytest.mark.parametrize("scope", SPMD_SCOPES)
def test_spmd_step_carries_scope(spmd_step, scope):
    assert _scoped(spmd_step[1], scope), scope


@pytest.mark.parametrize("scope", ["llm", "lm_head"])
def test_spmd_backward_carries_scope_under_transpose(spmd_step, scope):
    """The B items' input and weight VJPs name the model scopes inside
    ``transpose(jvp(...))``, as the one-chip step's backward does."""
    assert any(any(c.startswith("transpose(jvp(") for c in n.split("/"))
               for n in _scoped(spmd_step[1], scope)), scope
