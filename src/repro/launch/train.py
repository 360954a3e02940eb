"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
        --steps 300 --seq 128 --batch 4 [--reduced] [--mllm valm] \
        [--llm-size S] [--vision-size S] [--audio-size S] \
        [--ckpt-dir ckpts/run0] [--ckpt-every 50] [--resume] \
        [--fault-plan faults.json] [--log-every 10]

Two modes:
  * LM mode (``--arch``): any registered architecture; synthetic LM
    stream (repro.data.synthetic.TextLMDataset).
  * MLLM mode (``--mllm vlm|alm|valm``): the Cornstarch path — frozen
    encoders + LLM, trainable projectors, multimodal batches, with each
    module at a Table-1 size (``--llm-size`` / ``--vision-size`` /
    ``--audio-size``, S|M|L); the
    frozen mask drives both stop_gradient and optimizer masking. The
    parallelization decision is a typed ``MLLMParallelPlan``
    (repro.parallel): load a cached one with ``--plan plan.json``, or
    let the driver search one (``--plan-devices`` / ``--cp-size`` /
    ``--microbatches``) and persist it with ``--plan-out``. Adding
    ``--spmd`` trains the SAME model distributed: the MLLM is
    partitioned into per-stage callables (repro.models.stages), the
    plan's wave/collective program is compiled and lint-gated, and
    every train step replays it under ``shard_map`` across the
    pipeline mesh. ``--resume`` works across modes — a replay-mode
    checkpoint resumes an ``--spmd`` run and vice versa (params are
    re-partitioned; optimizer moments reset).

Both modes run under the fault-tolerant runtime (repro.resilience):
the train step is health-guarded (NaN/Inf and grad-norm gated in-jit,
EMA loss-spike scored), verdicts and faults land in
``<ckpt-dir>/events.jsonl``, and ``--ckpt-dir`` names a
``CheckpointManager`` root of atomic ``step_XXXXXXXX`` checkpoints
bundling params + optimizer + health EMA + data cursor in one
manifest. ``--resume`` restarts from ``latest()`` bit-exactly — an
interrupted-and-resumed run logs the same losses as an uninterrupted
one (asserted in tests/test_resilience.py). ``--fault-plan`` replays a
deterministic ``FaultPlan`` JSON (NaN grads, crash, kill-mid-save,
device loss) against the run — the chaos-testing entry point.

Runs on whatever devices exist (data-parallel over the host mesh when
more than one); this is the driver the smoke/e2e examples call into.
``main`` keeps JAX's persistent compilation cache where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import os
import time

import jax

from repro.configs.base import get_config
from repro.data.synthetic import MultimodalDataset, TextLMDataset
from repro.models import api
from repro.optim import optimizer as opt
from repro.training import steps

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it and nothing is set here; otherwise the cache
    lives at the fixed ``<repo>/.jax_cache`` (its path is part of the
    cache key, so it must not move between runs)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _run_resilient(args, loss_fn, params, ocfg, *, frozen_mask=None,
                   ds_factory, frozen_ckpt_paths=None,
                   on_device_loss=None, meta=None,
                   value_and_grad_fn=None,
                   convert_checkpoint=None) -> dict:
    """The shared fault-tolerant loop both modes run: guarded step,
    monitor + JSONL events, atomic checkpoints, rollback/resume.

    ``value_and_grad_fn`` replaces the default autodiff sweep inside
    the guarded step (the SPMD path computes grads by replaying the
    schedule's B/W items). ``convert_checkpoint(manager, peek_meta) ->
    (params, step, cursor)`` handles cross-mode resume: when the
    newest checkpoint's ``meta["mode"]`` differs from this run's, the
    converter loads it under the SOURCE layout and re-partitions the
    params; optimizer moments and the health EMA are layout-bound and
    restart fresh (``ResilientTrainer.adopt_state``)."""
    from repro.resilience import (CheckpointManager, CursorStream,
                                  EventLog, FaultInjector, FaultPlan,
                                  HealthMonitor, MonitorConfig,
                                  ResilientTrainer,
                                  make_resilient_train_step)
    if args.resume and not args.ckpt_dir:
        raise SystemExit("--resume needs --ckpt-dir")
    state = opt.init(ocfg, params, frozen_mask)
    step_fn = jax.jit(
        make_resilient_train_step(loss_fn, ocfg, frozen_mask,
                                  value_and_grad_fn=value_and_grad_fn),
        donate_argnums=(0, 1, 2))
    manager = log_path = None
    if args.ckpt_dir:
        manager = CheckpointManager(args.ckpt_dir, keep=args.keep,
                                    frozen_paths=frozen_ckpt_paths)
        log_path = os.path.join(args.ckpt_dir, "events.jsonl")
    monitor = HealthMonitor(
        MonitorConfig(spike_sigma=args.spike_sigma), EventLog(log_path))
    injector = None
    if args.fault_plan:
        injector = FaultInjector(FaultPlan.load(args.fault_plan))
        print(f"fault plan armed: {len(injector.plan.faults)} fault(s) "
              f"from {args.fault_plan}")
    resume, adopted, src_mode = args.resume, None, None
    if args.resume and manager is not None \
            and convert_checkpoint is not None:
        peek = manager.peek_meta()
        src_mode = peek.get("mode")
        want = (meta or {}).get("mode")
        if peek and src_mode and want and src_mode != want:
            adopted = convert_checkpoint(manager, peek)
            resume = False  # like-tree restore can't span layouts
    trainer = ResilientTrainer(
        step_fn, params, state, CursorStream(ds_factory),
        monitor=monitor, manager=manager, injector=injector,
        ckpt_every=args.ckpt_every, resume=resume,
        meta={"seed": args.seed, **(meta or {})},
        on_device_loss=on_device_loss, log_every=args.log_every)
    if adopted is not None:
        a_params, a_step, a_cursor = adopted
        trainer.adopt_state(a_params,
                            opt.init(ocfg, a_params, frozen_mask),
                            step=a_step, cursor=a_cursor)
        print(f"cross-mode resume: converted a {src_mode!r} checkpoint "
              f"at step {a_step} into this run's layout (optimizer "
              f"moments and health EMA reset)")
    if args.resume and trainer.step:
        print(f"resumed from {manager.latest()} at step {trainer.step}")
    t0 = time.time()
    res = trainer.run(args.steps)
    took = time.time() - t0
    if manager is not None:
        trainer.save_checkpoint()
        print(f"saved checkpoint to {manager.latest()}")
    n_params = sum(x.size for x in jax.tree.leaves(trainer.params))
    losses = [v for _, v in sorted(res["losses"].items())]
    if res["rollbacks"] or res["skipped"]:
        print(f"resilience: {res['skipped']} skipped step(s), "
              f"{res['rollbacks']} rollback(s), "
              f"{len(res['fired_faults'])} fault(s) fired")
    done = max(len(losses), 1)
    print(f"trained {len(losses)} step(s) in {took:.1f}s "
          f"({took / done:.2f}s/step)")
    return {"params": n_params, "first_loss": losses[0],
            "last_loss": losses[-1], "losses": losses,
            "step_seconds": res["step_seconds"],
            "final_params": trainer.params, "resilience": res}


def train_lm(args) -> dict:
    cfg = get_config(args.arch, reduced=args.reduced)
    if args.vocab:
        cfg = cfg.replace(vocab_size=args.vocab)
    params = api.init(jax.random.PRNGKey(args.seed), cfg)
    ocfg = opt.AdamWConfig(lr=args.lr, warmup_steps=min(50, args.steps // 10
                                                        or 1),
                           total_steps=args.steps)

    def ds_factory():
        return TextLMDataset(cfg.vocab_size, args.seq, args.batch,
                             seed=args.seed)

    return _run_resilient(args, steps.make_loss_fn(cfg), params, ocfg,
                          ds_factory=ds_factory,
                          meta={"arch": args.arch})


def resolve_plan(mllm, args):
    """The MLLMParallelPlan this run trains under: loaded from
    ``--plan`` (a launch script's cached search) or searched fresh via
    ``parallelize`` — the single typed entrypoint for the joint
    PP x CP decision. ``--plan-out`` persists it for the next launch."""
    from repro.parallel import (ClusterSpec, MLLMParallelPlan,
                                WorkloadShape, parallelize)
    if args.plan:
        plan = MLLMParallelPlan.load(args.plan)
    else:
        # paper block size at paper lengths; on reduced sequences keep
        # at least ~2 blocks per CP rank so the balancer has choices
        block = min(128, max(8, mllm.merged_length(args.seq)
                             // (2 * args.cp_size)))
        plan = parallelize(
            mllm, ClusterSpec(num_devices=args.plan_devices,
                              cp_size=args.cp_size),
            WorkloadShape(text_len=args.seq,
                          num_microbatches=args.microbatches,
                          microbatch_size=args.batch,
                          block_size=block))
    # instantiating the plan validates it against THIS mllm (stage
    # counts vs layer counts, encoder set) before any step runs; in
    # --spmd mode the contract also carries the compiled wave/ppermute
    # program, which the lint gate below then statically validates
    mode = "spmd" if getattr(args, "spmd", False) else "replay"
    executor = plan.apply(mllm, text_len=args.seq, mode=mode)
    if getattr(args, "lint", True):
        # the schedlint gate: a plan whose timeline would race,
        # overflow the activation caps, or deadlock a ring lowering
        # must die here, not N steps into a run (--no-lint to bypass)
        from repro.analysis import (format_findings, gate,
                                    lint_executor_contract, lint_plan)
        found = lint_plan(plan) + lint_executor_contract(executor)
        if gate(found):
            raise SystemExit(format_findings(
                found, header="plan failed the schedule lint "
                              "(--no-lint to bypass):"))
        if found:
            print(format_findings(found, header="plan lint notes:"))
    if args.plan_out:
        plan.save(args.plan_out)
        print(f"saved plan to {args.plan_out}")
    return plan, executor


def shrink_plan(mllm, plan, lost: int, args):
    """Graceful degradation on device loss: re-run ``parallelize()``
    over the shrunken ``ClusterSpec`` and return the degraded plan the
    run continues under (Cornstarch's planner answers the same
    question, just for fewer devices)."""
    from repro.parallel import ClusterSpec, WorkloadShape, parallelize
    # an MLLM plan needs at least one LLM stage plus one stage per
    # encoder; losses below that floor can't be re-planned away
    floor = 1 + len(mllm.encoders)
    devices = max(floor, plan.pp_devices - lost)
    block = min(128, max(8, mllm.merged_length(args.seq)
                         // (2 * max(plan.cp_ranks, 1))))
    degraded = parallelize(
        mllm, ClusterSpec(num_devices=devices, cp_size=plan.cp_ranks),
        WorkloadShape(text_len=args.seq,
                      num_microbatches=args.microbatches,
                      microbatch_size=args.batch, block_size=block))
    print(f"device loss: re-planned {plan.pp_devices} -> "
          f"{degraded.pp_devices} pipeline devices "
          f"(bubble {degraded.schedule.bubble_fraction:.3f})")
    return degraded


def _mllm_ds_factory(args, mllm):
    """Shared multimodal stream factory — replay and SPMD modes must
    consume the identical batch sequence (the loss-parity and
    cross-mode-resume tests depend on it)."""
    def ds_factory():
        return MultimodalDataset(
            vocab_size=mllm.llm_cfg.vocab_size, text_len=args.seq,
            batch_size=args.batch,
            encoder_dims={n: e.cfg.d_model
                          for n, e in mllm.encoders.items()},
            encoder_tokens={n: e.num_tokens
                            for n, e in mllm.encoders.items()},
            modality_ids={n: e.modality_id
                          for n, e in mllm.encoders.items()},
            seed=args.seed)
    return ds_factory


def spmd_parts(mllm, plan, executor):
    """What ``--spmd`` trains with: the plan's stage runner over its
    mesh, and the runner's value-and-grad. Returns (stage bundle, the
    replicated sharding the stage params live in, value_and_grad). Loss
    and grads are the per-microbatch sums rescaled by ``1/M``, which
    makes them comparable to (and tested against) the single-process
    ``make_mllm_train_step``."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.parallel.spmd import build_spmd_runner, mesh_from_plan
    D = int(executor["schedule"]["num_devices"])
    bundle = executor["stage_bundle"]
    M = int(plan.schedule.num_microbatches)
    mesh = mesh_from_plan(plan, mllm, D)
    print("pipeline ranks -> devices: " + ", ".join(
        f"{r}:{d}" for r, d in enumerate(mesh.devices.flat)))
    runner = build_spmd_runner(
        bundle.stage_fns, executor["sim_graph"], executor["schedule"],
        mesh=mesh,
        microbatch_loss=bundle.microbatch_loss,
        program=executor["spmd_program"],
        trainable=list(bundle.trainable))
    scale = 1.0 / M

    def value_and_grad_fn(sp, batch):
        # the schedule's B/W items ARE the backward pass — one jitted
        # shard_map core per step instead of an autodiff sweep
        mbs = bundle.encode_microbatches(batch, M)
        _out, loss, grads_repr, _occ, _wocc = runner.core(
            runner.prepare(sp), mbs, hetero=True)
        grads = jax.tree.map(lambda g: g * scale,
                             runner.finish_grads(grads_repr))
        loss = loss * scale
        return (loss, {"ce": loss}), grads

    return bundle, NamedSharding(mesh, PartitionSpec()), value_and_grad_fn


def _train_mllm_spmd(args, mllm, plan, executor) -> dict:
    """Real-model distributed training: the plan's compiled wave
    program drives the MLLM's own stage partition (``models.stages``)
    through the ``shard_map`` runner every step (``spmd_parts``) — no
    toy stages anywhere on this path.
    """
    import json

    from repro.resilience.monitor import init_health

    D = int(executor["schedule"]["num_devices"])
    if len(jax.devices()) < D:
        msg = (f"--spmd needs {D} devices for this plan but the process "
               f"has {len(jax.devices())}")
        if jax.default_backend() == "cpu":     # forced host devices
            msg += (f"; relaunch with XLA_FLAGS=--xla_force_host_"
                    f"platform_device_count={D}")
        raise SystemExit(msg)
    M = int(plan.schedule.num_microbatches)
    if args.batch % M != 0:
        raise SystemExit(
            f"--spmd needs --batch divisible by the plan's "
            f"{M} microbatches, got --batch {args.batch}")
    bundle, replicated, value_and_grad_fn = spmd_parts(mllm, plan, executor)

    key = jax.random.PRNGKey(args.seed)
    # the stage list straight from the seed, replicated over the
    # pipeline mesh, where every step's outputs land (inputs placed
    # elsewhere would recompile step two); the whole tree never sits on
    # one device first
    stage_params = jax.jit(lambda k: bundle.partition(mllm.init(k)),
                           out_shardings=replicated)(key)
    frozen_mask = bundle.frozen_masks(stage_params)
    ocfg = opt.AdamWConfig(lr=args.lr, warmup_steps=min(50, args.steps // 10
                                                        or 1),
                           total_steps=args.steps)

    def convert_checkpoint(manager, peek):
        # replay-mode checkpoint -> stage list: load under the
        # whole-model layout, then partition per this plan's stages
        params = jax.eval_shape(mllm.init, key)
        like = {"params": params,
                "opt": jax.eval_shape(lambda p: opt.init(
                    ocfg, p, mllm.frozen_mask(p)), params),
                "health": init_health()}
        tree, step, src = manager.restore(like)
        return (bundle.partition(tree["params"]),
                int(src.get("step", step)),
                int(src.get("cursor", src.get("step", step))))

    def on_device_loss(lost: int) -> None:
        shrink_plan(mllm, plan, lost, args)

    # frozen-shard hardlinking keys on whole-model paths; stage-list
    # checkpoints use per-stage paths, so skip the optimization here
    return _run_resilient(args, None, stage_params, ocfg,
                          frozen_mask=frozen_mask,
                          ds_factory=_mllm_ds_factory(args, mllm),
                          frozen_ckpt_paths=None,
                          on_device_loss=on_device_loss,
                          meta={"mllm": args.mllm,
                                "plan": plan.to_json(),
                                "mode": "spmd",
                                "spmd_layout":
                                    json.dumps(bundle.layout_meta)},
                          value_and_grad_fn=value_and_grad_fn,
                          convert_checkpoint=convert_checkpoint)


def train_mllm(args, mllm=None) -> dict:
    """MLLM-mode training. ``mllm`` is the ``MultimodalModule`` to train;
    by default the paper's ``args.mllm`` composition at the sizes the
    arguments name."""
    if mllm is None:
        from repro.models.mllm import build_paper_mllm
        mllm = build_paper_mllm(args.mllm, llm_size=args.llm_size,
                                vision_size=args.vision_size,
                                audio_size=args.audio_size,
                                reduced=args.reduced, text_len=args.seq)
    if args.train_llm:
        # the paper's ft1 fine-tune: frozen encoders, trainable LLM —
        # the scenario where zero-bubble W passes have work to defer
        mllm.freeze("llm", module=False)
    plan, executor = resolve_plan(mllm, args)
    print(plan.describe())
    print(f"executor graph: {len(executor['graph'].stages)} stages, "
          f"simulated bubble "
          f"{executor['schedule']['bubble_fraction']:.3f}")
    if getattr(args, "spmd", False):
        return _train_mllm_spmd(args, mllm, plan, executor)
    params = mllm.init(jax.random.PRNGKey(args.seed))
    ocfg = opt.AdamWConfig(lr=args.lr, warmup_steps=min(50, args.steps // 10
                                                        or 1),
                           total_steps=args.steps)
    frozen_mask = mllm.frozen_mask(params)
    _, loss_fn = steps.make_mllm_train_step(mllm, ocfg)

    # frozen modules' shards are written once and hardlinked forward by
    # the CheckpointManager (checkpoint-I/O face of frozen awareness)
    frozen_ckpt_paths = {f"params/encoders/{n}/module"
                         for n in mllm.encoders}
    if not args.train_llm:
        frozen_ckpt_paths.add("params/llm")

    def convert_checkpoint(manager, peek):
        # spmd-mode checkpoint -> whole-model tree: rebuild the stage
        # layout the checkpoint was written under, load the stage list,
        # and concatenate it back (models.stages.StageBundle round-trip)
        import json

        from repro.models.stages import build_mllm_stages
        from repro.resilience.monitor import init_health
        bundle = build_mllm_stages(mllm, executor, text_len=args.seq)
        want = peek.get("spmd_layout")
        if want and json.loads(want) != bundle.layout_meta:
            raise SystemExit(
                "the newest checkpoint was written under a different "
                "SPMD stage layout than this plan resolves to; resume "
                "with the plan that wrote it (--plan)")
        sp0 = bundle.partition(params)
        like = {"params": sp0,
                "opt": opt.init(ocfg, sp0, bundle.frozen_masks(sp0)),
                "health": init_health()}
        tree, step, src = manager.restore(like)
        return (bundle.unpartition(tree["params"]),
                int(src.get("step", step)),
                int(src.get("cursor", src.get("step", step))))

    def on_device_loss(lost: int) -> None:
        shrink_plan(mllm, plan, lost, args)

    return _run_resilient(args, loss_fn, params, ocfg,
                          frozen_mask=frozen_mask,
                          ds_factory=_mllm_ds_factory(args, mllm),
                          frozen_ckpt_paths=frozen_ckpt_paths,
                          on_device_loss=on_device_loss,
                          meta={"mllm": args.mllm,
                                "plan": plan.to_json(),
                                "mode": "replay"},
                          convert_checkpoint=convert_checkpoint)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--mllm", default=None, choices=[None, "vlm", "alm",
                                                     "valm"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--llm-size", default="M", choices=["S", "M", "L"],
                    help="MLLM mode: Table-1 size of the LLM")
    ap.add_argument("--vision-size", default="S", choices=["S", "M", "L"],
                    help="MLLM mode: Table-1 size of the vision encoder")
    ap.add_argument("--audio-size", default="S", choices=["S", "M", "L"],
                    help="MLLM mode: Table-1 size of the audio encoder")
    ap.add_argument("--log-every", type=int, default=10)
    # fault tolerance (repro.resilience)
    ap.add_argument("--ckpt-dir", default=None,
                    help="CheckpointManager root (atomic step_XXXXXXXX "
                    "checkpoints + events.jsonl)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint cadence in steps (0 = only the "
                    "final checkpoint)")
    ap.add_argument("--keep", type=int, default=3,
                    help="checkpoints retained under --ckpt-dir")
    ap.add_argument("--resume", action="store_true",
                    help="restart from the newest checkpoint under "
                    "--ckpt-dir (bit-exact continuation)")
    ap.add_argument("--fault-plan", default=None,
                    help="FaultPlan JSON to inject deterministically "
                    "(see repro.resilience.faults)")
    ap.add_argument("--spike-sigma", type=float, default=8.0,
                    help="EMA loss-spike z-score that triggers a "
                    "rollback verdict")
    # MLLM-mode parallelization plan (repro.parallel typed API)
    ap.add_argument("--plan", default=None,
                    help="MLLMParallelPlan JSON to train under "
                    "(default: search one via parallelize())")
    ap.add_argument("--plan-out", default=None,
                    help="write the resolved plan JSON here")
    ap.add_argument("--plan-devices", type=int, default=8,
                    help="pipeline device budget for the plan search")
    ap.add_argument("--cp-size", type=int, default=1,
                    help="context-parallel ranks for the plan search")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--no-lint", dest="lint", action="store_false",
                    help="skip the schedlint gate on the resolved plan")
    ap.add_argument("--spmd", action="store_true",
                    help="MLLM mode: partition the model into pipeline "
                    "stages, compile the plan's timeline to the "
                    "shard_map executor (lint-gated), and train the "
                    "real model distributed — every step replays the "
                    "schedule's wave program across the device mesh")
    ap.add_argument("--train-llm", action="store_true",
                    help="MLLM mode: unfreeze the LLM (ft1 fine-tune)")
    args = ap.parse_args(argv)
    if (args.arch is None) == (args.mllm is None):
        raise SystemExit("pass exactly one of --arch / --mllm")
    return args


def main(argv=None):
    args = parse_args(argv)
    enable_compilation_cache()
    res = train_mllm(args) if args.mllm else train_lm(args)
    print(f"done: {res['params']:,} params, "
          f"loss {res['first_loss']:.3f} -> {res['last_loss']:.3f}")
    return res


if __name__ == "__main__":
    main()
