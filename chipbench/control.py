"""Readings that set a cell's limits, outside the benchmark's own runs.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 \
        [--variants fp8,half_batch]

For each seed it runs the reference (float32 at HIGHEST) over the cell's
first steps, at the cell's own sizes, and then each variant put in the
program's place:

- ``fp8``: the control, the reference with every matmul operand in
  float8 e4m3 (the step below the configuration's bfloat16);
- ``half_batch``: the fault of a step that leaves half of the batch out
  and takes the mean over the rest (cells with more than one row).

and prints the comparison's numbers for each, as ``run.py`` takes them,
with the verdict that the cell's limits (``limits/<cell>.json``) give
them: a control or fault that the limits pass prints ``"correct": true``
and the limits are wrong. A state left unchanged reads 1 on
``change_diff``, and a reversed update 2, by construction; they need no
run. Refuses to run without a TPU, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import compare  # noqa: E402
import reference  # noqa: E402
import run as harness  # noqa: E402
import traffic as traffic_mod  # noqa: E402


def readings(config, traffic, seed: int, variants, steps: int = 3):
    """{variant: numbers} against the float32 reference for one seed."""
    import jax
    spec = reference.Spec.from_config(config)
    params = jax.jit(reference.init_params, static_argnums=(1, 2))(
        reference.base_key(seed), spec,
        jax.numpy.dtype(config["torch_dtype"]))
    optim = reference.Optim.from_traffic(traffic)
    batches = traffic_mod.first_batches(traffic, config, seed, steps)
    at = int(traffic["image_at"])
    ref = reference.train_steps(spec, optim, params, batches, image_at=at)
    out = {}
    for v in variants:
        if v == "fp8":
            got = reference.train_steps(spec, optim, params, batches,
                                        image_at=at, numerics="fp8")
        elif v == "half_batch":
            got = reference.train_steps(spec, optim, params, batches,
                                        image_at=at, fault="half_batch")
        else:
            raise SystemExit(f"unknown variant {v!r}")
        out[v] = compare.numbers(got, ref)
    return out


def verdict(cell: str, seed: int, variant: str, nums, limits):
    """One printed line: the numbers, and ``correct`` as the cell's
    limits judge them (each compared number beside its limit)."""
    ok, rows = compare.judge(nums, limits)
    return {"cell": cell, "seed": seed, "variant": variant, **nums,
            "correct": ok,
            "checks": {k: {"value": v, "limit": lim} for k, v, lim in rows}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="fp8,half_batch")
    args = ap.parse_args(argv)
    _, cell, config, traffic = harness.load_cell(args.workload)
    limits = compare.load_limits(harness.ROOT, cell["name"])
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control.py needs a TPU", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    variants = [v for v in args.variants.split(",") if v]
    if int(traffic["batch"]) < 2:
        variants = [v for v in variants if v != "half_batch"]
    for seed in (int(s) for s in args.seeds.split(",")):
        for v, nums in readings(config, traffic, seed, variants).items():
            print(json.dumps(verdict(cell["name"], seed, v, nums, limits)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
