"""Multimodality-aware context parallelism end to end (paper §4.3):
build a multimodal sequence, plan LPT token distribution from BAM
workloads, and run all-gather CP attention on 4 host devices — checking
exactness against single-device attention and reporting the balance win
over zigzag.

    python examples/cp_multimodal_attention.py   (re-execs with 4 devices)
"""
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.execv(sys.executable, [sys.executable] + sys.argv)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.core import bam, context_parallel as cp, distribution as dist
from repro.data.synthetic import random_multimodal_bits
from repro.models.layers import sdpa


def main():
    T, B, H, hd, G = 512, 1, 4, 32, 4
    bits_np, pos_np = random_multimodal_bits(T, "ee", seed=0)
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, T, H, hd))
               for i in range(3))
    bits = jnp.asarray(bits_np)[None]
    pos = jnp.asarray(pos_np)[None]

    for method in ("lpt", "zigzag"):
        plan = dist.plan_tokens(bits_np, pos_np, G, block_size=16,
                                method=method)
        loads = cp.simulate_rank_workloads(plan, bits_np, pos_np)
        print(f"{method:8s} rank workloads {loads.astype(int)} "
              f"imbalance {plan.imbalance:.3f}")

    plan = dist.plan_tokens(bits_np, pos_np, G, block_size=16, method="lpt")
    perm = cp.plan_permutation(plan, T)
    inv = cp.invert_perm(perm)
    mesh = jax.make_mesh((G,), ("cp",),
                         axis_types=(AxisType.Auto,))
    args = [jnp.take(a, perm, axis=1) for a in (q, k, v)]
    bp = jnp.take(bits, perm, axis=1)
    pp_ = jnp.take(pos, perm, axis=1)
    out = cp.cp_attention(mesh, "cp", *args, bp, bp, pp_, pp_)
    out = jnp.take(out, inv, axis=1)
    ref = sdpa(q, k, v, bam.allowed_mask(bits, bits, pos, pos)[:, None])
    err = float(jnp.abs(out - ref).max())
    print(f"CP(4 ranks, LPT) vs reference max err: {err:.2e}")
    assert err < 5e-6
    print("cp_multimodal_attention OK")


if __name__ == "__main__":
    main()
