"""The system under test, assembled from the program's own calls.

Builds the cell's ``MultimodalModule`` from a configuration file, makes
its plan with ``launch.train.resolve_plan`` and its guarded step and
``ResilientTrainer`` the way ``launch.train``'s ``_run_resilient`` (one
chip, replay mode) and ``_train_mllm_spmd`` (``--spmd``) do. Every
argument the cell does not fix is ``launch.train.parse_args``'s default,
so a later change to the normal path shows in the cells.

The benchmark's spans (``jax.profiler.TraceAnnotation``) wrap the calls
into each layer from here: ``data`` around every batch the trainer
draws, ``train_step`` around every call of the guarded step.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

import reference
import traffic as traffic_mod

#: health bundles kept from the first steps (the ones compared)
KEEP_BUNDLES = 3


def model_configs(config: Dict[str, Any]):
    """(llm ModelConfig, vision ModelConfig) from a configuration file;
    everything the file does not state is ``ModelConfig``'s default."""
    from repro.configs.base import ModelConfig
    reference.check_config(config)
    v = config["vision"]
    llm = ModelConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        use_qk_norm=bool(config.get("qk_norm", False)),
        qkv_bias=bool(config.get("attention_bias", False)),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        act="silu", norm="rmsnorm", dtype=config["torch_dtype"],
        source=config["source"])
    vis = ModelConfig(
        name=config["name"] + "-vision", family="dense",
        num_layers=v["num_hidden_layers"], d_model=v["hidden_size"],
        num_heads=v["num_attention_heads"],
        num_kv_heads=v["num_attention_heads"], head_dim=v["head_dim"],
        d_ff=v["intermediate_size"], vocab_size=1, norm="layernorm",
        act="gelu", dtype=config["torch_dtype"], source=v["source"])
    return llm, vis


def build_mllm(config: Dict[str, Any], traffic: Dict[str, Any]):
    """The paper's section-6 VLM: frozen vision tower and LLM, trainable
    linear projector, the image at the traffic's ``image_at``."""
    from repro.core.modality import ModalityModule, MultimodalModule
    llm, vis = model_configs(config)
    enc = ModalityModule("vision", vis, modality_id=1, projector="linear",
                         num_tokens=int(config["vision"]["num_tokens"]))
    text_len, at = int(traffic["text_len"]), int(traffic["image_at"])
    mllm = MultimodalModule(
        encoders={"vision": enc}, llm_cfg=llm, frozen_llm=True,
        layout=[("text", at), ("vision",), ("text", text_len - at)])
    mllm.freeze("vision", module=True, projector=False)
    return mllm


def train_args(traffic: Dict[str, Any], seed: int, plan_path=None):
    """``launch.train`` arguments for the cell: what the traffic file
    fixes, the rest at their defaults."""
    from repro.launch import train
    argv = ["--mllm", "vlm", "--seq", str(traffic["text_len"]),
            "--batch", str(traffic["batch"]), "--seed", str(seed),
            "--steps", str(traffic["optimizer"]["total_steps"]),
            "--lr", str(traffic["optimizer"]["lr"])]
    if traffic.get("microbatches"):
        argv += ["--microbatches", str(traffic["microbatches"])]
    if plan_path:
        argv += ["--plan", plan_path]
    if traffic["mode"] == "spmd":
        argv.append("--spmd")
    return train.parse_args(argv)


class _Annotated:
    """An iterator whose every ``next`` runs inside a ``data`` span."""

    def __init__(self, it):
        self._it = it

    def __iter__(self):
        return self

    def __next__(self):
        with jax.profiler.TraceAnnotation("data"):
            b = next(self._it)
            return {k: jnp.asarray(x) for k, x in b.items()}


@dataclasses.dataclass
class System:
    trainer: Any
    trainable: Callable[[Any], List[np.ndarray]]   # params -> leaves
    first_moment: Callable[[Any], List[np.ndarray]]  # opt state -> m
    leaf_names: List[str]
    plan_s: float
    init_s: float
    b1: float
    bundles: List[Any]          # health bundles of the first steps
    #: () -> the compiled step's memory analysis at the window's shapes
    step_memory: Callable[[], Dict[str, int]]


def _plan_for_spmd(mllm, traffic, path: str) -> None:
    """The cell's pipeline plan, searched as the traffic file states and
    saved for ``--plan``."""
    from repro.parallel import ClusterSpec, WorkloadShape, parallelize
    ps = traffic["plan"]
    plan = parallelize(
        mllm, ClusterSpec(num_devices=int(ps["devices"])),
        WorkloadShape(text_len=int(traffic["text_len"]),
                      num_microbatches=int(traffic["microbatches"]),
                      microbatch_size=int(ps["microbatch_size"]),
                      block_size=int(ps["block_size"])),
        objective=ps["objective"],
        virtual_chunks=tuple(ps["virtual_chunks"]))
    plan.save(path)


def build(config, traffic, seed: int, *, work_dir: str,
          step_wrapper: Optional[Callable] = None) -> System:
    """Plan, weights, optimizer state and trainer for one run.
    ``step_wrapper(step_fn) -> step_fn`` lets a test break the timed
    path underneath the harness."""
    from repro.launch import train
    from repro.optim import optimizer as opt
    from repro.resilience import (CursorStream, EventLog, HealthMonitor,
                                  MonitorConfig, ResilientTrainer,
                                  make_resilient_train_step)
    from repro.training import steps

    mllm = build_mllm(config, traffic)
    spmd = traffic["mode"] == "spmd"
    t0 = time.perf_counter()
    plan_path = None
    if spmd:
        os.makedirs(work_dir, exist_ok=True)
        plan_path = os.path.join(work_dir, "plan.json")
        _plan_for_spmd(mllm, traffic, plan_path)
    args = train_args(traffic, seed, plan_path)
    plan, executor = train.resolve_plan(mllm, args)
    plan_s = time.perf_counter() - t0

    spec = reference.Spec.from_config(config)
    key = reference.base_key(seed)
    dtype = jnp.dtype(config["torch_dtype"])
    o = traffic["optimizer"]
    ocfg = opt.AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"],
                           eps=o["eps"], weight_decay=o["weight_decay"],
                           grad_clip=o["grad_clip"],
                           warmup_steps=o["warmup_steps"],
                           total_steps=o["total_steps"],
                           schedule="cosine")
    t0 = time.perf_counter()
    if spmd:
        # the stage list straight from the seed, replicated over the
        # pipeline mesh: making the whole tree on one chip first and
        # partitioning it there would hold two copies of the weights
        bundle, rep, vgf = _spmd_parts(mllm, plan, executor)
        want = jax.eval_shape(lambda k: bundle.partition(mllm.init(k)), key)
        params = jax.jit(lambda k: bundle.partition(
            reference.init_params(k, spec, dtype)), out_shardings=rep)(key)
        frozen_mask = bundle.frozen_masks(params)
        loss_fn = None
    else:
        want = jax.eval_shape(mllm.init, key)
        params = jax.jit(reference.init_params, static_argnums=(1, 2))(
            key, spec, dtype)
        frozen_mask = mllm.frozen_mask(params)
        _, loss_fn = steps.make_mllm_train_step(mllm, ocfg)
        vgf = None
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    have = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        params)
    if jax.tree.structure(have) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(have), jax.tree.leaves(want))):
        raise SystemExit("the benchmark's weight layout no longer matches "
                         "the program's MultimodalModule.init")
    state = opt.init(ocfg, params, frozen_mask)
    raw = make_resilient_train_step(loss_fn, ocfg, frozen_mask,
                                    value_and_grad_fn=vgf)
    if step_wrapper is not None:
        raw = step_wrapper(raw)
    step_fn = jax.jit(raw, donate_argnums=(0, 1, 2))

    bundles: List[Any] = []
    shapes: List[Any] = []

    def annotated_step(*a):
        if not shapes:
            shapes.extend(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), a))
        with jax.profiler.TraceAnnotation("train_step"):
            out = step_fn(*a)
        if len(bundles) < KEEP_BUNDLES:
            bundles.append(out[3])
        return out

    def step_memory() -> Dict[str, int]:
        """What the compiled step holds on the device at once: its
        arguments, and the temporaries it allocates while it runs."""
        m = step_fn.lower(*shapes).compile().memory_analysis()
        return {k: int(getattr(m, k + "_size_in_bytes")) for k in
                ("argument", "output", "alias", "temp", "generated_code")}

    def factory():
        return _Annotated(traffic_mod.batches(traffic, config, seed))

    monitor = HealthMonitor(MonitorConfig(spike_sigma=args.spike_sigma),
                            EventLog(None))
    trainer = ResilientTrainer(annotated_step, params, state,
                               CursorStream(factory), monitor=monitor,
                               ckpt_every=args.ckpt_every,
                               meta={"seed": seed},
                               log_every=args.log_every)
    flat_mask, _ = jax.tree_util.tree_flatten_with_path(frozen_mask)
    picks = [i for i, (_, frz) in enumerate(flat_mask) if not frz]
    names = [_trainable_name(p) for p, frz in flat_mask if not frz]

    def trainable(tree):
        leaves = jax.tree.leaves(tree)
        return [np.asarray(jax.device_get(leaves[i]).astype(np.float32),
                           np.float64) for i in picks]

    def first_moment(state):
        return trainable(state["m"])

    return System(trainer=trainer, trainable=trainable,
                  first_moment=first_moment, leaf_names=names,
                  plan_s=plan_s, init_s=init_s, b1=ocfg.b1,
                  bundles=bundles, step_memory=step_memory)


def _trainable_name(path) -> str:
    """A trainable leaf's path, in whole-model terms where the tree is a
    stage list (only the projector is trainable in these cells)."""
    keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
    if keys[-2:] == ["projector", "w1"]:
        return "encoders/vision/projector/w1"
    return "/".join(keys)


def _spmd_parts(mllm, plan, executor):
    """``_train_mllm_spmd``'s assembly: the stage runner over the plan's
    mesh, and the runner's value-and-grad. Returns (stage bundle, the
    replicated sharding the stage params live in, value_and_grad)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.parallel.spmd import build_spmd_runner, mesh_from_plan
    D = int(executor["schedule"]["num_devices"])
    bundle = executor["stage_bundle"]
    M = int(plan.schedule.num_microbatches)
    mesh = mesh_from_plan(plan, mllm, D)
    runner = build_spmd_runner(
        bundle.stage_fns, executor["sim_graph"], executor["schedule"],
        mesh=mesh, microbatch_loss=bundle.microbatch_loss,
        program=executor["spmd_program"], trainable=list(bundle.trainable))
    scale = 1.0 / M

    def value_and_grad_fn(sp, batch):
        mbs = bundle.encode_microbatches(batch, M)
        _out, loss, grads_repr, _occ, _wocc = runner.core(
            runner.prepare(sp), mbs, hetero=True)
        grads = jax.tree.map(lambda g: g * scale,
                             runner.finish_grads(grads_repr))
        loss = loss * scale
        return (loss, {"ce": loss}), grads

    return bundle, NamedSharding(mesh, PartitionSpec()), value_and_grad_fn

