"""Real-model SPMD training: distributed step time + runner compile
scaling.

Two childs, each in a subprocess with forced host devices (the same
harness the multi-device tests use):

* **train** — partitions the reduced paper VLM into its stage bundle
  (``repro.models.stages``), runs the plan's compiled wave program
  through the ``shard_map`` runner to steady state, and replays the
  identical timeline + stage fns on the sequential executor. The child
  ASSERTS the distributed loss matches the replay, so a row only ever
  appears for a run that computed the right thing.

* **compile** — times the first (trace + XLA compile) call of the
  runner's rolled instruction-table loop as the wave count grows with
  the microbatch count. Its compile time scales with *distinct*
  instructions, not timeline length — the derived fields carry the
  wave counts so the sublinear growth is visible in
  ``BENCH_spmd_train.json``.
"""
import os
import subprocess
import sys

from .common import emit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON_PATH = os.path.join(REPO, "BENCH_spmd_train.json")

_CHILD_TRAIN = """
import time
import numpy as np
import jax
from repro.core.modality_parallel import execute_schedule
from repro.data.synthetic import MultimodalDataset
from repro.models.mllm import build_paper_mllm
from repro.parallel import ClusterSpec, WorkloadShape, parallelize
from repro.parallel.spmd import build_spmd_runner, mesh_from_plan

TEXT, M, BATCH = 16, 2, 2
iters = {iters}
mllm = build_paper_mllm("vlm", reduced=True, text_len=TEXT)
plan = parallelize(mllm, ClusterSpec(num_devices=3),
                   WorkloadShape(text_len=TEXT, num_microbatches=M,
                                 microbatch_size=1, block_size=8))
ex = plan.apply(mllm, text_len=TEXT, mode="spmd")
bundle = ex["stage_bundle"]
D = int(ex["schedule"]["num_devices"])
runner = build_spmd_runner(
    bundle.stage_fns, ex["sim_graph"], ex["schedule"],
    mesh=mesh_from_plan(plan, mllm, D),
    microbatch_loss=bundle.microbatch_loss,
    program=ex["spmd_program"], trainable=list(bundle.trainable))
params = mllm.init(jax.random.PRNGKey(0))
sp = bundle.partition(params)
ds = MultimodalDataset(
    vocab_size=mllm.llm_cfg.vocab_size, text_len=TEXT, batch_size=BATCH,
    encoder_dims={{n: e.cfg.d_model for n, e in mllm.encoders.items()}},
    encoder_tokens={{n: e.num_tokens for n, e in mllm.encoders.items()}},
    modality_ids={{n: e.modality_id for n, e in mllm.encoders.items()}},
    seed=0)
mbs = bundle.encode_microbatches(next(iter(ds)), M)
t0 = time.perf_counter()
res = runner(sp, mbs)
jax.block_until_ready(res["loss"])
first_us = (time.perf_counter() - t0) * 1e6
times = []
for _ in range(iters):
    t0 = time.perf_counter()
    res = runner(sp, mbs)
    jax.block_until_ready(res["loss"])
    times.append(time.perf_counter() - t0)
times.sort()
us = times[len(times) // 2] * 1e6
t0 = time.perf_counter()
ref = execute_schedule(bundle.stage_fns, sp, mbs, ex["sim_graph"],
                       ex["schedule"],
                       microbatch_loss=bundle.microbatch_loss,
                       trainable=list(bundle.trainable))
replay_us = (time.perf_counter() - t0) * 1e6
diff = abs(float(res["loss"]) - float(ref["loss"]))
assert diff < 1e-4 * max(1.0, abs(float(ref["loss"]))), diff
c = ex["spmd_program"].counts()
n_params = sum(int(x.size) for x in jax.tree.leaves(sp))
print(f"ROW spmdtrain/vlm-d{{D}} {{us:.1f}} "
      f"first_us={{first_us:.0f}};replay_us={{replay_us:.0f}};"
      f"waves={{c['waves']}};items={{c['items']}};"
      f"params={{n_params}};loss_diff={{diff:.1e}};match=1", flush=True)
"""

_CHILD_COMPILE = """
import time
import jax
from repro.core import schedule as sch
from repro.parallel.spmd import (build_spmd_runner, compile_spmd_program,
                                 toy_stage_model)

Ms = {Ms!r}
d = 16
for M in Ms:
    g = sch.chain_graph([sch.Stage(f"s{{i}}", 1.0, 2.0, bwd_w=1.0)
                         for i in range(4)])
    sim = sch.get_scheduler("zb-h1").simulate(g, M)
    prog = compile_spmd_program(g, sim)
    fn, params = toy_stage_model(4, d)
    mbs = jax.random.normal(jax.random.PRNGKey(1), (M, 1, 4, d))
    runner = build_spmd_runner(fn, g, sim, program=prog)
    t0 = time.perf_counter()
    res = runner(params, mbs)
    jax.block_until_ready(res["loss"])
    us = (time.perf_counter() - t0) * 1e6
    print(f"ROW spmdtrain/compile-M{{M}} {{us:.1f}} "
          f"microbatches={{M}};waves={{prog.counts()['waves']}}",
          flush=True)
"""


def _child(code: str, n_devices: int):
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_devices}"
    # the child checks CPU virtual-device behaviour; it must not
    # contend for an accelerator the parent process holds
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1200,
                          cwd=REPO)
    assert proc.returncode == 0, \
        f"spmdtrain bench child failed:\n{proc.stdout}\n{proc.stderr}"
    rows = []
    for line in proc.stdout.splitlines():
        if not line.startswith("ROW "):
            continue
        _tag, name, us, derived = line.split(" ", 3)
        emit(name, float(us), derived, json_path=JSON_PATH)
        rows.append((name, float(us), derived))
    return rows


def run(smoke: bool = False):
    if os.path.exists(JSON_PATH):
        os.remove(JSON_PATH)
    ms = (4, 8) if smoke else (4, 8, 16, 32)
    rows = _child(_CHILD_TRAIN.format(iters=2 if smoke else 5), 3)
    rows += _child(_CHILD_COMPILE.format(Ms=tuple(ms)), 4)
    assert len(rows) == 1 + len(ms), rows
    return rows


if __name__ == "__main__":
    run()
