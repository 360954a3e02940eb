"""The reduction to the program's scopes and spans (``scopes.py``), on
intervals and HLO text worked by hand and on a small trace recorded on the
CPU inside the test; and the readers of the metrics built on it."""
import importlib.util
import os
import time

import pytest

import scopes
import trace_reduce as tr
from conftest import BENCH

READERS = ("encoder.ms_per_step", "attention.ms_per_step",
           "llm.head_ms_per_step", "optimizer.ms_per_step",
           "trainer.health_read_ms", "trainer.batch_idle_ms",
           "trainer.dispatch_idle_ms")


def reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("component,want", [
    ("transpose(jvp(llm))", "llm"), ("jvp(encoder)", "encoder"),
    ("jvp()", ""), ("jit(_where)", "_where"), ("sdpa", "sdpa"),
    ("bqhd,bkhd->bhqk", "bqhd,bkhd->bhqk")])
def test_strip_wrappers(component, want):
    assert scopes.strip(component) == want


def test_chain_keeps_the_program_scopes_in_order():
    assert scopes.chain("jit(step)/transpose(jvp(llm))/while/body/"
                        "closed_call/checkpoint/rematted_computation/"
                        "attention/sdpa/dot_general") == "llm/attention/sdpa"
    assert scopes.chain("jit(step)/jvp(encoder)/while/body/mlp/tanh") \
        == "encoder/mlp"
    # a fused list counts under its first (root) op
    assert scopes.chain("jit(step)/health/mul;jit(step)/optimizer/mul") \
        == "health"
    assert scopes.chain("jit(step)/jvp(jit(take_along_axis))/gather") \
        == "none"
    assert scopes.top("llm/attention/sdpa") == "llm"
    # ops hoisted out of the layer loop keep their inner scopes only
    assert scopes.top("attention/sdpa") == "" == scopes.top("none")


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %tanh.1 = f32[4]{0} tanh(%param_0), metadata={op_name="jit(step)/jvp(llm)/while/body/attention/sdpa/tanh"}
}

%body.2 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %copy-start.3 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%p)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(llm)/while/body/attention/sdpa/tanh"}
}

%cond.4 (p: (s32[], f32[4])) -> pred[] {
  ROOT %lt.1 = pred[] compare(%p), direction=LT, metadata={op_name="jit(step)/jvp(llm)/while/cond/lt"}
}

ENTRY %main.5 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %while.6 = (s32[], f32[4]{0}) while(%a), condition=%cond.4, body=%body.2, metadata={op_name="jit(step)/jvp(llm)/while"}
  %copy.7 = f32[4]{0} copy(%a)
  ROOT %fusion.8 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/optimizer/mul;jit(step)/health/mul"}
}
"""


def test_hlo_op_names_inherit_from_the_calling_instruction():
    names = scopes.hlo_op_names(HLO)
    assert names["fusion.1"].endswith("attention/sdpa/tanh")
    # a copy XLA put into the loop body takes the while's op_name
    assert names["copy-start.3"] == "jit(step)/jvp(llm)/while"
    # one in the entry computation has none
    assert names["copy.7"] == ""
    assert scopes.chain(names["fusion.8"]) == "optimizer"
    assert {"while.6", "lt.1", "param_0", "tanh.1"} <= set(names)


NAMES = {"while.6": "jit(step)/jvp(llm)/while",
         "fusion.1": "jit(step)/transpose(jvp(llm))/while/body/attention/"
                     "sdpa/dot_general",
         "fusion.2": "jit(step)/jvp(llm)/while/body/mlp/dot_general",
         "fusion.3": "jit(step)/jvp(encoder)/while/body/attention/sdpa/exp",
         "fusion.4": "jit(step)/optimizer/mul",
         "fusion.5": "jit(step)/health/select_n",
         "copy.7": "",
         "fusion.9": "jit(step)/attention/sdpa/and"}


def test_self_time_by_scope_with_nesting_and_shares():
    # a while loop holding two ops; the rest flat; one op not in the
    # module; the window cuts the last op
    ops = {"d": [(0, 100, "while.6"), (10, 40, "fusion.1"),
                 (50, 70, "fusion.2"), (100, 120, "fusion.3"),
                 (120, 130, "fusion.4"), (130, 135, "fusion.5"),
                 (135, 145, "copy.7"), (145, 150, "fusion.9"),
                 (150, 160, "fusion.99"), (190, 220, "fusion.2")]}
    red = scopes.reduce(ops, [], (0, 200), NAMES)
    s = {k: round(v * 1e9, 6) for k, v in red["scope_s"].items()}
    assert s == {"llm": 50, "llm/attention/sdpa": 30, "llm/mlp": 30,
                 "encoder/attention/sdpa": 20, "optimizer": 10,
                 "health": 5, "none": 10, "attention/sdpa": 5,
                 "unmatched": 10}
    busy = 170
    assert red["matched_share"] == pytest.approx(1 - 10 / busy)
    # the entry copy, the hoisted mask op and the unknown op
    assert red["unscoped_share"] == pytest.approx(25 / busy)


def test_self_time_is_the_mean_over_devices():
    ops = [(0, 10, "fusion.4")]
    red = scopes.reduce({"a": ops, "b": ops}, [], (0, 20), NAMES)
    assert red["scope_s"] == {"optimizer": pytest.approx(10e-9)}


def test_idle_split_by_the_innermost_trainer_span():
    ns = 1e9
    devices = {"d": [(3 * ns, 8 * ns, "fusion.2"),
                     (12 * ns, 18 * ns, "fusion.2")]}
    host = [(0, 10 * ns, "trainer.step"), (0, 2 * ns, "trainer.batch"),
            (2 * ns, 3 * ns, "trainer.dispatch"),
            (3 * ns, 9 * ns, "trainer.health_read"),
            (10 * ns, 20 * ns, "trainer.step"),
            (10 * ns, 11 * ns, "trainer.batch"),
            (11 * ns, 12.5 * ns, "trainer.dispatch"),
            (12.5 * ns, 18.5 * ns, "trainer.health_read"),
            (0, 25 * ns, "window"), (0, 2 * ns, "data")]
    red = scopes.reduce(devices, host, (0, 25 * ns), NAMES)
    idle = red["idle_by_span_s"]
    # idle: [0,3) [8,12) [18,25)
    assert idle == {"trainer.batch": pytest.approx(3.0),
                    "trainer.dispatch": pytest.approx(2.0),
                    "trainer.health_read": pytest.approx(1.5),
                    "trainer.step": pytest.approx(2.5),
                    "none": pytest.approx(5.0)}
    assert sum(idle.values()) == pytest.approx(25 - 11)
    assert red["spans"]["trainer.batch"] == {"s": pytest.approx(3.0),
                                             "count": 2}
    assert red["spans"]["trainer.health_read"]["s"] == pytest.approx(12.0)
    # the benchmark's own spans are not the program's
    assert set(red["spans"]) == {"trainer.step", "trainer.batch",
                                 "trainer.dispatch", "trainer.health_read"}


def test_innermost_prefers_the_latest_start_then_the_first_end():
    pieces = scopes.innermost([(0, 10, "trainer.step"),
                               (0, 4, "trainer.batch"),
                               (6, 12, "trainer.checkpoint")], 0, 14)
    assert pieces == [(0, 4, "trainer.batch"), (4, 6, "trainer.step"),
                      (6, 12, "trainer.checkpoint"), (12, 14, "none")]


def _record(program, steps=4):
    return {"steps": steps, "trace": {"window_s": 10.0, "devices": {},
                                      "program": program}}


PROGRAM = {
    "scope_s": {"encoder": 0.1, "encoder/attention/sdpa": 0.1,
                "llm": 0.4, "llm/attention/sdpa": 0.8, "llm/mlp": 0.6,
                "attention/sdpa": 0.02, "lm_head": 0.3, "optimizer": 0.01,
                "health": 0.03, "none": 0.05},
    "matched_share": 0.99, "unscoped_share": 0.01,
    "spans": {"trainer.health_read": {"s": 3.6, "count": 4},
              "trainer.batch": {"s": 0.2, "count": 4}},
    "idle_by_span_s": {"trainer.batch": 0.2, "trainer.dispatch": 0.02,
                       "none": 0.01}}


@pytest.mark.parametrize("name,want", [
    ("encoder.ms_per_step", 50.0), ("attention.ms_per_step", 200.0),
    ("llm.head_ms_per_step", 75.0), ("optimizer.ms_per_step", 10.0),
    ("trainer.health_read_ms", 900.0), ("trainer.batch_idle_ms", 50.0),
    ("trainer.dispatch_idle_ms", 5.0)])
def test_reader_on_a_program_record(name, want):
    assert reader(name)(_record(PROGRAM)) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_on_a_parent_record(name):
    """A record from a program without scopes and spans, as the parent's
    and an untraced run's are."""
    parent = {"steps": 4, "trace": {"window_s": 10.0, "devices": {},
                                    "span_s": {"data": 0.2},
                                    "span_count": {"data": 4}}}
    assert reader(name)(parent) is None
    assert reader(name)({"steps": 4, "trace": None}) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace recorded on the CPU: three steps of a small jitted function
    with named scopes, under the benchmark's spans and the trainer's."""
    jax = pytest.importorskip("jax")
    jnp = jax.numpy

    def f(x):
        with jax.named_scope("llm"):
            with jax.named_scope("sdpa"):
                y = jnp.tanh(x @ x)
        with jax.named_scope("lm_head"):
            return y @ x
    fn = jax.jit(f)
    x = jnp.ones((128, 128), jnp.float32)
    names = scopes.hlo_op_names(fn.lower(x).compile().as_text())
    fn(x).block_until_ready()
    log = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for i in range(3):
            with jax.profiler.StepTraceAnnotation("trainer.step",
                                                  step_num=i):
                with jax.profiler.TraceAnnotation("trainer.batch"):
                    with jax.profiler.TraceAnnotation("data"):
                        time.sleep(0.005)
                with jax.profiler.TraceAnnotation("trainer.dispatch"):
                    with jax.profiler.TraceAnnotation("train_step"):
                        out = fn(x)
                with jax.profiler.TraceAnnotation("trainer.health_read"):
                    out.block_until_ready()
    jax.profiler.stop_trace()
    devices, async_ops, host = tr.load(tr.latest_xplane(str(log)))
    window = next((s, e) for s, e, n in host if n == "window")
    return devices, async_ops, host, window, names


def test_recorded_cpu_trace_maps_ops_to_scopes(recorded):
    devices, _, host, window, names = recorded
    red = scopes.reduce(devices, host, window, names)
    assert {"llm/sdpa", "lm_head"} <= set(red["scope_s"])
    assert red["scope_s"]["llm/sdpa"] > 0 and red["scope_s"]["lm_head"] > 0
    assert 0.0 < red["matched_share"]
    assert {n: v["count"] for n, v in red["spans"].items()} == {
        "trainer.step": 3, "trainer.batch": 3, "trainer.dispatch": 3,
        "trainer.health_read": 3}
    # the device waits for the batch drawn: 5 ms of sleep a step
    assert red["idle_by_span_s"]["trainer.batch"] >= 0.015
    # every idle moment of the window is somewhere, once
    lo, hi = window
    for ops in devices.values():
        busy = tr.union(tr.clip([(s, e) for s, e, _ in ops], lo, hi))
        idle = (hi - lo - tr.measure(busy)) * 1e-9
        assert sum(red["idle_by_span_s"].values()) == \
            pytest.approx(idle / len(devices), rel=1e-9)


def test_trace_reduce_is_unchanged_by_the_trainer_spans(recorded):
    """The benchmark's own reduction reads the same numbers with the
    program's spans in the trace as without them."""
    devices, async_ops, host, window, _ = recorded
    bare = [op for op in host if not op[2].startswith("trainer.")]
    assert len(bare) < len(host)
    with_spans = tr.reduce(devices, host, window, async_ops=async_ops)
    without = tr.reduce(devices, bare, window, async_ops=async_ops)
    assert with_spans == without
    assert with_spans["span_count"] == {"data": 3, "train_step": 3}
    assert all(label.rsplit(":", 1)[1] in ("data", "train_step", "other")
               for label, _ in with_spans["idle_gaps"])
