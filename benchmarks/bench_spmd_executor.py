"""SPMD schedule executor: distributed shard_map execution vs the
sequential replay, per schedule.

The bench process itself keeps the host's single real device (like the
test suite), so each scenario runs in a subprocess with a forced host
device count — the same harness the multi-device tests use. Per
schedule the child

* compiles the timeline to the wave/ppermute program
  (``repro.parallel.spmd.compile_spmd_program``),
* runs the shard_map executor once (trace + XLA compile) and then to
  steady state, and
* replays the identical timeline on the sequential executor
  (``core.modality_parallel.execute_schedule``),

and reports steady-state microseconds per distributed iteration with
``derived`` carrying the compile/first-call costs, the replay time,
the program shape (waves/rounds), and the max elementwise grad
difference against the replay — which the child ASSERTS is tiny, so a
row only ever appears for a program that computed the right thing.
"""
import os
import subprocess
import sys

from .common import emit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import time
import numpy as np
import jax
import jax.numpy as jnp
from repro.core import schedule as sch
from repro.core.modality_parallel import execute_schedule
from repro.parallel.spmd import (build_spmd_runner, compile_spmd_program,
                                 toy_stage_model)

scheds = {scheds!r}
iters = {iters}
M, d = {M}, 16
CHUNKED = ("interleaved", "zb-v")
for sched in scheds:
    stages = [sch.Stage(f"s{{s}}", 1.0, 2.0, bwd_w=1.0)
              for s in range(4)]
    g = sch.chain_graph(stages)
    if sched in CHUNKED:
        g = sch.refine_chain(sch.chain_graph(stages[:2]), 2)
    kwargs = {{"virtual_chunks": 2}} if sched in CHUNKED else {{}}
    sim = sch.get_scheduler(sched, **kwargs).simulate(g, M)
    t0 = time.perf_counter()
    prog = compile_spmd_program(g, sim)
    compile_us = (time.perf_counter() - t0) * 1e6
    fn, params = toy_stage_model(len(g.stages), d)
    mbs = jax.random.normal(jax.random.PRNGKey(1), (M, 1, 4, d))
    runner = build_spmd_runner(fn, g, sim, program=prog)
    t0 = time.perf_counter()
    res = runner(params, mbs)
    first_us = (time.perf_counter() - t0) * 1e6
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        res = runner(params, mbs)
        times.append(time.perf_counter() - t0)
    times.sort()
    us = times[len(times) // 2] * 1e6
    t0 = time.perf_counter()
    ref = execute_schedule(fn, params, mbs, g, sim)
    replay_us = (time.perf_counter() - t0) * 1e6
    diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(res["param_grads"]),
        jax.tree.leaves(ref["param_grads"])))
    assert diff < 1e-4, (sched, diff)
    assert res["peak_activations_per_device"] == \\
        ref["peak_activations_per_device"], sched
    c = prog.counts()
    print(f"ROW spmd/{{sched}}-d{{c['devices']}} {{us:.1f}} "
          f"compile_us={{compile_us:.0f}};first_us={{first_us:.0f}};"
          f"replay_us={{replay_us:.0f}};waves={{c['waves']}};"
          f"rounds={{c['rounds']}};items={{c['items']}};"
          f"grad_diff={{diff:.1e}};match=1", flush=True)
"""


def run(smoke: bool = False):
    scheds = ("1f1b", "zb-v") if smoke else tuple(
        __import__("repro.core.schedule",
                   fromlist=["SCHEDULES"]).SCHEDULES)
    code = _CHILD.format(scheds=tuple(scheds), iters=2 if smoke else 5,
                         M=4 if smoke else 8)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    # the child checks CPU virtual-device behaviour; it must not
    # contend for an accelerator the parent process holds
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1200,
                          cwd=REPO)
    assert proc.returncode == 0, \
        f"spmd bench child failed:\n{proc.stdout}\n{proc.stderr}"
    rows = []
    for line in proc.stdout.splitlines():
        if not line.startswith("ROW "):
            continue
        _tag, name, us, derived = line.split(" ", 3)
        emit(name, float(us), derived)
        rows.append((name, float(us), derived))
    assert len(rows) == len(scheds), proc.stdout
    return rows


if __name__ == "__main__":
    run()
