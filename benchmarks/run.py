"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--smoke] [table3 table4 ...]

Emits ``name,us_per_call,derived`` CSV rows:
  table3    — frozen-aware vs -unaware pipeline partitioning (§6.4)
  table2    — modality parallelism vs colocated/replicated (§6.2/§6.3)
  table4    — CP token distribution: LPT/random/ring/zigzag (§6.5)
  kernel    — BAM Pallas kernel block-sparsity & memory wins
  roofline  — §Roofline terms from the dry-run artifacts
  schedmem  — simulator-vs-executor peak-activation validation for
              every pipeline schedule (fails loudly on divergence)
  spmd      — distributed shard_map executor vs sequential replay
              (multi-device subprocess; fails loudly on grad or
              peak divergence)
  spmdtrain — real-MLLM SPMD train step (stage bundle through the
              wave program) + the runner's compile time against wave
              count; writes BENCH_spmd_train.json
  serve     — paged-cache serving throughput: tokens/sec vs batch
              size, xla gather vs paged flash-decode kernel, plus
              the multimodal page-skip fraction
  resil     — fault-tolerance runtime cost: in-jit health-monitor
              overhead per train step (guarded vs plain), atomic
              checkpoint save/restore MB/s

``--smoke`` shrinks every benchmark to a tiny grid with one repeat —
seconds, not minutes — so CI can execute all of them on every push and
the scripts cannot rot silently when the API moves under them. The
figures a smoke run emits are NOT the paper's numbers; only the full
grids are.
"""
import sys


def main() -> None:
    argv = list(sys.argv[1:])
    smoke = "--smoke" in argv
    if smoke:
        argv = [a for a in argv if a != "--smoke"]
    want = set(argv)

    def on(name):
        return not want or name in want

    print("name,us_per_call,derived", flush=True)
    if on("table3"):
        from benchmarks import bench_frozen_aware_pp
        bench_frozen_aware_pp.run(smoke=smoke)
    if on("table2"):
        from benchmarks import bench_modality_parallel
        bench_modality_parallel.run(smoke=smoke)
    if on("table4"):
        from benchmarks import bench_cp_distribution
        bench_cp_distribution.run(smoke=smoke)
    if on("kernel"):
        from benchmarks import bench_bam_kernel
        bench_bam_kernel.run(smoke=smoke)
    if on("roofline"):
        from benchmarks import bench_roofline
        bench_roofline.run(smoke=smoke)
    if on("schedmem"):
        from benchmarks import bench_schedule_memory
        bench_schedule_memory.run(smoke=smoke)
    if on("spmd"):
        from benchmarks import bench_spmd_executor
        bench_spmd_executor.run(smoke=smoke)
    if on("spmdtrain"):
        from benchmarks import bench_spmd_train
        bench_spmd_train.run(smoke=smoke)
    if on("serve"):
        from benchmarks import bench_serve
        bench_serve.run(smoke=smoke)
    if on("resil"):
        from benchmarks import bench_resilience
        bench_resilience.run(smoke=smoke)


if __name__ == '__main__':
    main()
