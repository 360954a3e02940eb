"""Paper Table 4 / §6.5: context-parallel attention time under the four
token distributions (LPT, random, naive ring, zigzag) × three mask
types (EP, EE, MP) × sequence lengths.

Two measurement levels (CPU container, per DESIGN.md):
  * full scale (16k/32k/64k): per-rank attention *workload model*
    (row-sums of the BAM mask, the exact quantity all-gather CP time is
    proportional to) — ``pred_ms`` = max-rank workload / v5e attention
    throughput;
  * reduced scale (2k, "control"): wall-clock of the worst-loaded
    rank through the DENSE XLA path. These come out ~equal by design —
    a dense kernel computes every masked entry anyway, which is exactly
    why the workload win requires a mask-skipping kernel (our Pallas
    BAM kernel's block-skip; see bench_bam_kernel).

``derived`` reports imbalance + LPT speedup over zigzag/ring — the
paper's Table 4 shows LPT/random ≥ zigzag > naive ring for EE/MP.

Since CP went differentiable, ``cp-bwd/*`` rows time a full
forward+backward through ``cp_attention`` per method × per-step body
(dense XLA vs the Pallas stats kernel, interpret mode — ordering check
on CPU, not TPU perf) and report the analytic backward-memory term
(dense logits vs (out, lse) flash residuals). Mirrored into
``BENCH_cp_bwd.json``.
"""
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.core import context_parallel as cp
from repro.data.synthetic import random_multimodal_bits
from repro.launch.mesh import PEAK_FLOPS_BF16
from repro.parallel import plan_context

from .common import emit, timeit

RANKS = 8
BLOCK = 128
PLANNERS = ["lpt", "random", "ring", "zigzag"]
HEADS, HEAD_DIM = 8, 128   # one Llama-70B attention layer slice

CP_BWD_JSON = os.environ.get("BENCH_CP_BWD_JSON", "BENCH_cp_bwd.json")


def full_scale(seq_len: int, mode: str, seeds=range(3)):
    loads = {m: [] for m in PLANNERS}
    for seed in seeds:
        bits, pos = random_multimodal_bits(seq_len, mode, seed=seed)
        for m in PLANNERS:
            kw = {"seed": seed} if m == "random" else {}
            plan = plan_context(bits, pos, RANKS, block_size=BLOCK,
                                method=m, **kw)
            loads[m].append(plan.makespan)
    out = {}
    for m in PLANNERS:
        mean_makespan = float(np.mean(loads[m]))
        flops = 4.0 * mean_makespan * HEADS * HEAD_DIM  # scores + AV
        out[m] = flops / PEAK_FLOPS_BF16 * 1e3          # ms on one chip
    return out


def reduced_scale_measured(mode: str, seq_len: int = 2048):
    bits_np, pos_np = random_multimodal_bits(seq_len, mode, seed=0)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(jax.random.fold_in(key, 0),
                          (1, seq_len, 4, 64), jnp.float32)
    k, v = q, q
    bits = jnp.asarray(bits_np)[None]
    pos = jnp.asarray(pos_np)[None]

    @jax.jit
    def rank_attn(q_r, b_r, p_r):
        return cp.cp_reference(q_r, k, v, b_r, bits, p_r, pos)

    out = {}
    for m in PLANNERS:
        plan = plan_context(bits_np, pos_np, RANKS,
                            block_size=BLOCK // 4, method=m)
        loads = cp.simulate_rank_workloads(plan.core_plan(), bits_np,
                                           pos_np)
        worst = int(np.argmax(loads))
        sl = plan.rank_token_slices()[worst]
        sl = jnp.asarray(sl[:seq_len // RANKS])
        q_r = jnp.take(q, sl, axis=1)
        b_r = jnp.take(bits, sl, axis=1)
        p_r = jnp.take(pos, sl, axis=1)
        out[m] = timeit(rank_attn, q_r, b_r, p_r, iters=3, warmup=1) / 1e3
    return out   # ms


def cp_fwd_bwd(smoke: bool = False):
    """Differentiable-CP rows: forward and forward+backward wall time
    through ``cp_attention`` for each method × per-step body, plus the
    analytic backward-memory term. Single-rank mesh (the bodies and
    their custom_vjps are what is being timed; collectives are
    identity), reduced scale, interpret-mode kernels."""
    T = 64 if smoke else 128
    B, H, hd = 1, 2, 32
    bits_np, pos_np = random_multimodal_bits(T, "ee", seed=0)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, T, H, hd), jnp.float32)
    bits = jnp.asarray(bits_np)[None]
    pos = jnp.asarray(pos_np)[None]
    mesh = jax.make_mesh((1,), ("cp",),
                         axis_types=(AxisType.Auto,))
    iters = 1 if smoke else 2
    if os.path.exists(CP_BWD_JSON):
        os.remove(CP_BWD_JSON)
    # backward-memory term per rank: the XLA body re-materializes the
    # [B,H,Tq,Tk] f32 logits per step; the kernel body saves only the
    # (out, lse) flash residuals
    mem_xla = B * H * T * T * 4
    mem_kernel = B * H * T * 4 + B * T * H * hd * 4
    for method in ("allgather", "ring"):
        for impl in ("xla", "bam_interpret"):
            def fwd(q):
                return cp.cp_attention(
                    mesh, "cp", q, q, q, bits, bits, pos, pos,
                    method=method, impl=impl, block_q=32, block_k=32)

            grad_fn = jax.jit(jax.grad(lambda q: jnp.sum(fwd(q) ** 2)))
            us_f = timeit(jax.jit(fwd), q, iters=iters, warmup=1)
            us_b = timeit(grad_fn, q, iters=iters, warmup=1)
            mem = mem_xla if impl == "xla" else mem_kernel
            emit(f"cp-bwd/{method}-{impl}-T{T}", us_b,
                 f"fwd_us={us_f:.1f};bwd_bytes={mem};"
                 f"mem_vs_xla={mem_xla / mem:.1f}x",
                 json_path=CP_BWD_JSON, method=method, impl=impl,
                 seq_len=T, fwd_us=round(us_f, 1), bwd_bytes=mem)


def run(smoke: bool = False):
    rows = []
    seq_lens = (4096,) if smoke else (16384, 32768, 65536)
    modes = ("ee",) if smoke else ("ep", "ee", "mp")
    seeds = range(1) if smoke else range(3)
    for seq_len in seq_lens:
        for mode in modes:
            t0 = time.perf_counter()
            pred = full_scale(seq_len, mode, seeds=seeds)
            us = (time.perf_counter() - t0) * 1e6
            name = f"table4/T{seq_len}-{mode}"
            emit(name, us,
                 ";".join(f"{m}_pred_ms={pred[m]:.3f}" for m in PLANNERS)
                 + f";lpt_vs_zigzag={pred['zigzag'] / pred['lpt']:.3f}"
                 + f";lpt_vs_ring={pred['ring'] / pred['lpt']:.3f}")
            rows.append((name, pred))
    # reduced-scale wall-clock confirmation (one setting per mask type)
    ctrl_seq = 1024 if smoke else 2048
    for mode in modes:
        t0 = time.perf_counter()
        ms = reduced_scale_measured(mode, seq_len=ctrl_seq)
        us = (time.perf_counter() - t0) * 1e6
        emit(f"table4-densecontrol/T{ctrl_seq}-{mode}", us,
             ";".join(f"{m}_ms={ms[m]:.2f}" for m in PLANNERS))
    cp_fwd_bwd(smoke=smoke)
    return rows


if __name__ == "__main__":
    run()
