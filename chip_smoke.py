"""Bring-up smoke run: train the paper's S-size VLM on a TPU through the
normal entry points, with the fused BAM kernels on the LLM.

    python3 chip_smoke.py             # one chip: phases (a) and (b)
    python3 chip_smoke.py --chips 4   # four chips: SPMD vs replay, CP

One chip. ``repro.launch.train``'s MLLM path (``train_mllm`` ->
``resolve_plan`` -> ``_run_resilient``) trains EVA-CLIP-S (40 x 1408)
plus LLM-S (16 x 2048, vocab 128256) at full widths and depth in bf16,
with the paper's section-6 freezing (frozen encoder and LLM, trainable
projector), on 1024 text tokens plus 576 vision tokens (1600 merged):

  (a) the default XLA attention;
  (b) the same model, seed and batches with the LLM on the fused Pallas
      BAM kernels (``attn_impl="bam_kernel"``): forward, dQ and dK/dV run
      on the merged multimodal bitfields, since the projector's gradient
      flows back through the frozen LLM.

Four chips (``--chips 4``), and nothing else:

  (i)  ``launch/train --mllm vlm --spmd`` on a 4-device plan, its LLM
       stages on the default attention (the fused BAM kernels on a TPU),
       against a replay-mode run over the same steps on XLA attention;
  (ii) ``make_cp_train_step`` on a 4-way ``cp`` mesh at LLM-S widths
       (depth cut to 2 layers) with ep/ee/mp masks, allgather and ring,
       on the fused kernel chunks, against ``make_train_step`` on one
       device on XLA attention.

Every phase runs in this one process: a chip belongs to one process at a
time. The script refuses to run where JAX finds no TPU, and outside a
checkout of the repository. Earlier lines report each phase (losses,
compile and step seconds, peak device memory, where the params live);
the last line is one JSON object with ``ok`` and the device.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

#: one warm-up step (it compiles) and then the measured steps
WARMUP_STEPS = 1
MEASURED_STEPS = 4
#: loss agreement between two attention paths or two executors, relative
#: per step, and why (printed beside every comparison)
LOSS_RTOL = 1e-2
LOSS_RTOL_WHY = (
    "both sides run the same bf16 weights on the same batches but round "
    "at different points (the kernel keeps its tiles' softmax in f32 and "
    "sums tiles in another order than XLA; the pipeline sums microbatch "
    "losses where the replay takes the whole batch); bf16 keeps 8 "
    "significant bits (relative spacing 2^-8 = 3.9e-3) and such "
    "roundings compound over the layers into a few spacings in the "
    "logits, while the loss, a mean over thousands of tokens, moves far "
    "less; 1e-2 admits that and still fails a NaN, a diverged step or a "
    "path that trains another model")


def _load_repro() -> None:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit("chip_smoke.py runs from a checkout of the "
                         f"repository: {SRC}/repro not found")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def vlm_args(*, reduced: bool, extra=()):
    """The training driver's arguments for the paper's S-size VLM."""
    from repro.launch import train
    text_len = 64 if reduced else 1024
    argv = ["--mllm", "vlm", "--llm-size", "S", "--vision-size", "S",
            "--seq", str(text_len), "--batch", "2",
            "--steps", str(WARMUP_STEPS + MEASURED_STEPS),
            "--microbatches", "2", "--log-every", "1", "--seed", "0",
            *extra]
    if reduced:
        argv.append("--reduced")
    return train.parse_args(argv)


def build_vlm(args, attn_impl: str):
    """``build_paper_mllm`` at the arguments' sizes, with the LLM on
    ``attn_impl``."""
    from repro.models.mllm import build_paper_mllm
    mllm = build_paper_mllm("vlm", llm_size=args.llm_size,
                            vision_size=args.vision_size,
                            reduced=args.reduced, text_len=args.seq)
    mllm.llm_cfg = mllm.llm_cfg.replace(attn_impl=attn_impl)
    return mllm


def _placement(params) -> dict:
    """Top-level module (or pipeline stage) -> devices its params are
    on."""
    import jax
    if isinstance(params, list):                 # SPMD: one per stage
        groups = {str(s): p for s, p in enumerate(params)}
    else:
        groups = {f"encoders/{n}": p for n, p in params["encoders"].items()}
        groups["llm"] = params["llm"]
    return {k: sorted({str(d) for leaf in jax.tree.leaves(v)
                       for d in leaf.devices()})
            for k, v in groups.items()}


def _peak_bytes():
    """Highest ``peak_bytes_in_use`` over the devices, or None where the
    backend keeps no stats. A peak since the process started: a later
    phase reports at least an earlier phase's."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_phase(name: str, args, attn_impl: str) -> dict:
    """Train ``args``' VLM with the LLM on ``attn_impl`` through
    ``train_mllm``; print and return the phase's report."""
    import gc
    import math

    from repro.launch import train
    # an earlier phase's ~5 GB of params must not linger in a reference
    # cycle while this one allocates its own on the same chip
    gc.collect()
    res = train.train_mllm(args, mllm=build_vlm(args, attn_impl))
    secs = res["step_seconds"]
    steady = secs[WARMUP_STEPS:]
    step_s = statistics.median(steady) if steady else float("nan")
    report = {
        "phase": name, "attn_impl": attn_impl,
        "spmd": bool(getattr(args, "spmd", False)),
        "losses": res["losses"],
        "finite": all(math.isfinite(x) for x in res["losses"]),
        "first_step_s": secs[0],
        # the first call compiles; what it took beyond a steady step
        "compile_s": secs[0] - step_s,
        "step_s": step_s, "step_seconds": secs,
        "peak_bytes_in_use": _peak_bytes(),
        "params_on": _placement(res["final_params"]),
    }
    print("PHASE " + json.dumps(report), flush=True)
    return report


def loss_gap(a, b) -> float:
    """Largest relative per-step loss difference between two runs."""
    if len(a) != len(b):
        return float("inf")
    return max(abs(x - y) / max(abs(x), 1e-12) for x, y in zip(a, b))


def compare(label: str, a: dict, b: dict) -> bool:
    gap = loss_gap(a["losses"], b["losses"])
    ok = a["finite"] and b["finite"] and gap <= LOSS_RTOL
    print(f"COMPARE {label}: max relative loss gap {gap:.3e} "
          f"(tolerance {LOSS_RTOL:g}: {LOSS_RTOL_WHY}) -> "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    return ok


def one_chip(*, reduced: bool = False, kernel_impl: str = "bam_kernel"):
    """Phases (a) and (b). ``reduced``/``kernel_impl`` let a CPU test run
    the same code at small widths in interpret mode."""
    args = vlm_args(reduced=reduced)
    a = run_phase("a", args, "xla")
    b = run_phase("b", args, kernel_impl)
    return compare("(b) bam kernel vs (a) xla", a, b), [a, b]


def four_device_plan(args, path: str):
    """Search the VLM's plan for four pipeline devices and save it to
    ``path`` for ``--plan``. Ranked by iteration time: the default rank,
    throughput per device, keeps this VLM on fewer devices than four.
    Virtual chunks are pinned to 1 (zb-v keeps its two per device): at
    four microbatches the interleaved v=4 plan the open search returns
    at full widths exceeds the schedule lint's activation caps, and the
    launcher refuses it."""
    from repro.parallel import ClusterSpec, WorkloadShape, parallelize
    mllm = build_vlm(args, "xla")
    block = min(128, max(8, mllm.merged_length(args.seq) // 2))
    plan = parallelize(
        mllm, ClusterSpec(num_devices=4),
        WorkloadShape(text_len=args.seq,
                      num_microbatches=args.microbatches,
                      microbatch_size=args.batch, block_size=block),
        objective="iteration_time", virtual_chunks=(1,))
    plan.save(path)
    return plan


def spmd_vs_replay(*, reduced: bool = False):
    """(i): the same VLM, steps and batches through the SPMD pipeline on
    a four-device plan, with the LLM's attention as the platform picks it
    (``attn_impl="auto"``: the fused kernels on a TPU), and through the
    single-device replay trainer on XLA attention."""
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "plan.json")
        extra = ("--batch", "4", "--microbatches", "4", "--plan", path)
        args = vlm_args(reduced=reduced, extra=extra)
        plan = four_device_plan(args, path)
        print(f"four-device plan: {plan.pp_devices} pipeline ranks",
              flush=True)
        replay = run_phase("replay", args, "xla")
        spmd = run_phase("spmd", vlm_args(reduced=reduced,
                                          extra=extra + ("--spmd",)), "auto")
    return compare("(i) spmd vs replay", replay, spmd), [replay, spmd]


def cp_vs_single(*, reduced: bool = False, kernel_impl: str = "bam_kernel",
                 cp: int = 4):
    """(ii): ``make_cp_train_step`` over a ``cp``-way mesh (fused kernel
    chunks, allgather and ring) against ``make_train_step`` on one device
    (XLA attention), per mask mode; compares loss and grad norm."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType

    from repro.configs.paper_mllm import llm_config
    from repro.data.synthetic import random_multimodal_bits
    from repro.models import api
    from repro.optim import optimizer as opt
    from repro.parallel import plan_context
    from repro.training import steps

    cfg = llm_config("S", reduced=reduced)
    if not reduced:
        cfg = cfg.replace(num_layers=2)          # depth cut, widths kept
    T, B = (128, 2) if reduced else (2048, 2)
    mesh = jax.make_mesh((cp,), ("cp",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:cp])
    print(f"CP mesh devices: {[str(d) for d in mesh.devices.flat]}",
          flush=True)
    params = api.init(jax.random.PRNGKey(0), cfg)
    ocfg = opt.AdamWConfig(lr=1e-3, warmup_steps=0, schedule="constant")
    state = opt.init(ocfg, params)
    rng = np.random.default_rng(0)
    ok, rows = True, []
    for mode in ("ep", "ee", "mp"):
        bits, pos = random_multimodal_bits(T, mode, seed=1)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                                  jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                                  jnp.int32),
            "positions": jnp.broadcast_to(jnp.asarray(pos)[None], (B, T)),
            "bits": jnp.broadcast_to(jnp.asarray(bits)[None], (B, T)),
            "valid": jnp.broadcast_to(jnp.asarray(bits != 0)[None], (B, T)),
        }
        layout = plan_context(bits, pos, cp, block_size=min(128, T // cp),
                              method="lpt").apply(T)
        # keep only each step's metrics: at LLM-S widths the updated
        # params and optimizer state are ~6 GB a copy on a 16 GB chip
        ref = jax.jit(steps.make_train_step(cfg.replace(attn_impl="xla"),
                                            ocfg))(params, state, batch)[2]
        want = (float(ref["loss"]), float(ref["grad_norm"]))
        for method in ("allgather", "ring"):
            step = jax.jit(steps.make_cp_train_step(
                cfg.replace(attn_impl=kernel_impl), layout, mesh, ocfg,
                method=method))
            got = step(params, state, batch)[2]
            have = (float(got["loss"]), float(got["grad_norm"]))
            gap = max(abs(h - w) / max(abs(w), 1e-12)
                      for h, w in zip(have, want))
            good = all(np.isfinite(have)) and gap <= LOSS_RTOL
            ok &= good
            row = {"mode": mode, "method": method, "loss": have[0],
                   "grad_norm": have[1], "ref_loss": want[0],
                   "ref_grad_norm": want[1], "max_rel_gap": gap,
                   "ok": good}
            rows.append(row)
            print("CP " + json.dumps(row), flush=True)
    print(f"COMPARE (ii) cp vs single device: "
          f"{'ok' if ok else 'FAILED'} (tolerance {LOSS_RTOL:g})",
          flush=True)
    return ok, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: phases (a) and (b); 4: the SPMD and CP "
                    "phases only")
    args = ap.parse_args(argv)
    _load_repro()
    import jax

    from repro.launch.train import enable_compilation_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU devices; JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    print(f"compilation cache: {enable_compilation_cache()}", flush=True)
    if args.chips == 1:
        ok, _ = one_chip()
    else:
        ok_i, _ = spmd_vs_replay()
        ok_ii, _ = cp_vs_single()
        ok = ok_i and ok_ii
    if not ok:
        print("chip_smoke.py: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
