"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration and traffic files are found by name (``configs/``,
``traffic/``), as are the readers of its per-layer metrics
(``metrics/<metric>.py``) and its limits (``limits/<cell>.json``).

One process, in order: refuse without a TPU or with fewer chips than the
cell asks for; turn on the compilation cache (inside the checkout); build
the program's trainer for the cell (``sut.py``) with weights made on the
device from the seed; run its first steps, which compile and which the
reference follows; train for ``--seconds``; read the device's peak
memory; free the program's state; run the reference over the same first
steps and compare. With ``--trace 1`` the window runs under the
profiler and the result carries the per-layer metrics instead of the
end-to-end ones. The last stdout line is one JSON object; the numbers
compared, each beside its limit, are the last lines on stderr and the
last key of that object.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

#: the first steps: the first compiles, and the reference follows all
CHECK_STEPS = 3
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".chipbench", "jax_cache")


def _load_json(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_cell(name: str):
    """(manifest, cell entry, configuration file, traffic file)."""
    manifest = _load_json("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == name)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    config = _load_json(entry["file"])
    traffic = _load_json("chipbench", "traffic", cell["traffic"] + ".json")
    return manifest, cell, config, traffic


def metrics_of(manifest, cell, group: str):
    """The ``group`` metrics that the cell reports."""
    e2e = {m["name"] for m in manifest["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])}
    if group == "end_to_end":
        return [m for m in manifest["end_to_end"] if m["name"] in e2e]
    return [m for m in manifest["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in e2e]


def read_metric(name: str, record):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


def _peak_bytes(devices):
    """The most device memory a chip held, the highest over ``devices``:
    the allocator's peak of buffers in use plus the runtime's peak of
    memory reserved for the programs' temporaries, which the TPU runtime
    keeps apart from the buffers (and which ``peak_bytes_in_use`` leaves
    out). None where the runtime reports neither."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(st["peak_bytes_in_use"]
                         + st.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def first_steps(system, steps: int):
    """Drive the trainer through its first ``steps`` steps (the window's
    own call and feed) and keep what the comparison needs before later
    steps donate it."""
    import numpy as np

    from repro.resilience.monitor import BUNDLE_KEYS
    tr = system.trainer
    p0 = system.trainable(tr.params)
    tr.run(1)
    m1 = system.first_moment(tr.opt_state)
    tr.run(steps)
    p3 = system.trainable(tr.params)
    names = system.leaf_names
    gnorm = float(np.asarray(system.bundles[0])[
        BUNDLE_KEYS.index("grad_norm")])
    return {
        "losses": [tr.losses.get(i, float("nan")) for i in range(steps)],
        "gnorm": gnorm,
        "grad": {n: np.asarray(m) / (1.0 - system.b1)
                 for n, m in zip(names, m1)},
        "change": {n: b - a for n, a, b in zip(names, p0, p3)},
    }


def window(system, seconds: float):
    """Train until ``seconds`` have passed; the last step is the first to
    end after that. Returns (t_start, t_end, steps, failed, traces):
    ``traces`` counts the tracings and compilations inside the window,
    which set-up should have left none of."""
    import jax
    from repro.resilience import TrainingAborted
    tr = system.trainer
    n0, aborted, traces = tr.step, 0, []

    def listen(event, _secs, **_kw):
        if event.endswith(("jaxpr_trace_duration",
                           "backend_compile_duration")):
            traces.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("window"):
            while True:
                try:
                    tr.run(tr.step + 1)
                except TrainingAborted as e:
                    print(f"training aborted in the window: {e}",
                          file=sys.stderr)
                    aborted = 1
                    break
                if time.perf_counter() - t0 >= seconds:
                    break
    finally:
        t1 = time.perf_counter()
        jax.monitoring.unregister_event_duration_listener(listen)
    steps = tr.step - n0 + aborted
    good = sum(1 for k, v in tr.losses.items()
               if k >= n0 and math.isfinite(v))
    return t0, t1, steps, steps - good, len(traces)


def run(args, manifest, cell, config, traffic, *, limits=None,
        step_wrapper=None, peaks=None, phases=None) -> int:
    """Everything after the look for a chip. ``limits``,
    ``step_wrapper`` and ``peaks`` let a test run a small cell on the
    CPU with the timed path broken underneath. ``phases`` holds the
    seconds from process start at which each part of set-up ended."""
    import jax

    from repro.launch.train import enable_compilation_cache
    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    import compare
    import flops
    import reference
    import sut
    import trace_reduce
    import traffic as traffic_mod

    devices = jax.devices()
    chips = int(cell["chips"])
    phases = dict(phases or {})
    if peaks is None:
        peaks = _load_json("chipbench", "peaks.json")["devices"]
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"no peaks for device kind {kind!r} in peaks.json",
              file=sys.stderr)
        return 1
    system = sut.build(config, traffic, args.seed,
                       work_dir=os.path.join(ROOT, ".chipbench",
                                             cell["name"]),
                       step_wrapper=step_wrapper)
    phases["built"] = time.perf_counter() - T0
    prog = first_steps(system, CHECK_STEPS)
    phases["first_steps"] = time.perf_counter() - T0
    first_step_s = system.trainer.step_seconds[0]
    warm = len(system.trainer.step_seconds)

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_w0, t_w1, steps, failed, traces = window(system, args.seconds)
    setup_s = t_w0 - T0
    if trace_dir:
        jax.profiler.stop_trace()
    peak = _peak_bytes(devices[:chips])
    stats_after_window = devices[0].memory_stats()
    step_seconds = system.trainer.step_seconds[warm:]
    plan_s, init_s = system.plan_s, system.init_s
    step_memory = system.step_memory

    # the program's state goes before the reference allocates its own
    system.trainer.params = system.trainer.opt_state = None
    del system
    gc.collect()

    t_ref = time.perf_counter()
    spec = reference.Spec.from_config(config)
    ref_params = jax.jit(reference.init_params, static_argnums=(1, 2))(
        reference.base_key(args.seed), spec,
        jax.numpy.dtype(config["torch_dtype"]))
    ref = reference.train_steps(
        spec, reference.Optim.from_traffic(traffic), ref_params,
        traffic_mod.first_batches(traffic, config, args.seed, CHECK_STEPS),
        image_at=int(traffic["image_at"]))
    del ref_params
    ref_s = time.perf_counter() - t_ref
    # the compiler's account of the step, beside the runtime's peak
    step_mem = step_memory()
    got = compare.numbers(prog, ref)
    if limits is None:
        limits = compare.load_limits(ROOT, cell["name"])
    correct, rows = compare.judge(got, limits)
    correct = correct and failed == 0 and steps > 0

    wall = t_w1 - t_w0
    record = {"steps": steps, "window_s": wall, "chips": chips,
              "plan_s": plan_s, "first_step_s": first_step_s,
              "step_seconds": step_seconds,
              "step_flops": flops.step_flops(config, traffic)["total"],
              "trace": None}
    record["peak_flops"] = peaks[kind]["bf16_flops_per_s"]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": steps, "failed": failed}
    if trace_dir:
        path = trace_reduce.latest_xplane(trace_dir)
        dev_ops, async_ops, host = trace_reduce.load(path)
        span = next((s, e) for s, e, n in host if n == "window")
        dev_ops = {d: ops for d, ops in dev_ops.items()
                   if any(span[0] <= s < span[1] for s, _, _ in ops)}
        red = trace_reduce.reduce(dev_ops, host, span, async_ops=async_ops)
        shutil.rmtree(trace_dir, ignore_errors=True)
        record["trace"] = red
        busy = [d["busy_s"] for d in red["devices"].values()]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = red["window_s"]
        metrics = {}
        for m in metrics_of(manifest, cell, "per_layer"):
            v = read_metric(m["name"], record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
        print("trace: " + json.dumps({k: red[k] for k in
                                      ("devices", "span_s", "span_count")}),
              flush=True)
    else:
        tokens = traffic_mod.merged_tokens_per_step(traffic, config)
        values = {"tokens_per_s": steps * tokens / wall,
                  "peak_hbm_gib": (peak or 0) / 2 ** 30,
                  "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in metrics_of(manifest, cell, "end_to_end")}
        out["device"] = device
    print("run: " + json.dumps({
        "cell": cell["name"], "seed": args.seed, "setup_s": setup_s,
        "phases": phases, "init_s": init_s, "plan_s": plan_s,
        "window_s": wall, "steps": steps, "window_compiles": traces,
        "first_step_s": first_step_s, "step_seconds": step_seconds,
        "losses": prog["losses"], "ref_losses": ref["losses"],
        "gnorm": prog["gnorm"], "ref_gnorm": ref["gnorm"],
        "numbers": got, "ref_s": ref_s,
        "memory_stats": stats_after_window, "step_memory": step_mem,
        "cache": jax.config.jax_compilation_cache_dir}), flush=True)
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        print(f"check {k} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    print(f"check failed_steps {failed} limit 0", file=sys.stderr,
          flush=True)
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    args = ap.parse_args(argv)
    try:
        manifest, cell, config, traffic = load_cell(args.workload)
    except (OSError, StopIteration, KeyError, ValueError) as e:
        print(f"cannot load cell {args.workload!r}: {e!r}", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"the program under test is not in this checkout ({src})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    # the compilation cache lives at a fixed path inside the checkout,
    # the benchmark's own, set before JAX reads its configuration; the
    # program's entry-point helper (called in run) then keeps it there
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    devices = jax.devices()
    phases = {"jax_ready": time.perf_counter() - T0}
    if devices[0].platform != "tpu":
        print(f"the benchmark needs a TPU; JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < int(cell["chips"]):
        print(f"cell {cell['name']} needs {cell['chips']} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    return run(args, manifest, cell, config, traffic, phases=phases)


if __name__ == "__main__":
    sys.exit(main())
