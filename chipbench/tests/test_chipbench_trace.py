"""The trace reduction, on interval sets worked by hand and on a small
trace recorded on the CPU inside the test."""
import time

import pytest

import trace_reduce as tr


def test_union_clip_measure():
    iv = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert iv == [(0, 3), (5, 8)]
    assert tr.measure(tr.clip(iv, 2, 6)) == 2


def test_subtract():
    a = [(0, 10), (20, 30)]
    b = [(2, 3), (5, 12), (25, 40)]
    assert tr.subtract(a, b) == [(0, 2), (3, 5), (20, 25)]
    assert tr.subtract(a, []) == a


def test_self_times_nested():
    ops = [(0, 10, "while"), (1, 3, "fusion.1"), (4, 6, "fusion.2"),
           (12, 14, "fusion.1")]
    st = tr.self_times(ops)
    assert st == {"while": 6, "fusion.1": 4, "fusion.2": 2}


def test_op_name():
    assert tr.op_name("%fusion.549 = bf16[4,16]{1,0} fusion(f32[4] %a)") \
        == "fusion.549"
    assert tr.op_name("all-gather.3") == "all-gather.3"


def test_async_collectives_count_as_collective_not_busy():
    devices = {"d": [(0, 4, "fusion.1")]}
    async_ops = {"d": [(2, 8, "collective-permute-start.1"),
                       (1, 9, "copy-start.2")]}
    red = tr.reduce(devices, [], (0, 10), async_ops=async_ops)
    d = red["devices"]["d"]
    assert d["busy_s"] == pytest.approx(4e-9)
    assert d["collective_s"] == pytest.approx(6e-9)
    assert d["exposed_collective_s"] == pytest.approx(4e-9)


def test_collective_names():
    for n in ("all-gather.3", "all-gather-start.1", "all-reduce",
              "collective-permute-done.2", "reduce-scatter.7",
              "all_gather.19", "collective_permute_start.4"):
        assert tr.COLLECTIVE.match(n), n
    for n in ("fusion.12", "all-gatherer", "convolution.4", "sendmail"):
        assert not tr.COLLECTIVE.match(n), n


def test_reduce_busy_collective_exposed_and_gaps():
    ns = 1e9
    devices = {"/device:TPU:0": [
        (0.0, 2 * ns, "fusion.1"),
        (1.5 * ns, 3 * ns, "all-gather.1"),     # 1 s of it exposed
        (6 * ns, 8 * ns, "convolution.2"),
    ]}
    host = [(3 * ns, 6 * ns, "data"), (8 * ns, 10 * ns, "train_step"),
            (0, 10 * ns, "window")]
    red = tr.reduce(devices, host, (0, 10 * ns))
    d = red["devices"]["/device:TPU:0"]
    assert d["busy_s"] == pytest.approx(5.0)
    assert d["idle_share"] == pytest.approx(0.5)
    assert d["collective_s"] == pytest.approx(1.5)
    assert d["exposed_collective_s"] == pytest.approx(1.0)
    assert red["idle_gaps"] == [["/device:TPU:0:data", pytest.approx(3.0)],
                                ["/device:TPU:0:train_step",
                                 pytest.approx(2.0)]]
    assert red["device_ops"][0][0] in ("fusion.1", "convolution.2")
    assert red["span_count"] == {"data": 1, "train_step": 1}


def test_recorded_cpu_trace(tmp_path):
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("data"):
                time.sleep(0.005)
            with jax.profiler.TraceAnnotation("train_step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.latest_xplane(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    devices, async_ops, host = tr.load(path)
    assert devices, "no device ops found"
    window = next((s, e) for s, e, n in host if n == "window")
    red = tr.reduce(devices, host, window, async_ops=async_ops)
    assert red["window_s"] > 0.015
    assert red["span_count"] == {"data": 3, "train_step": 3}
    assert red["span_s"]["data"] >= 0.015
    for d in red["devices"].values():
        assert 0.0 <= d["idle_share"] <= 1.0
        assert d["collective_s"] == 0.0
    assert any(d["busy_s"] > 0 for d in red["devices"].values())
    assert red["device_ops"] and red["idle_gaps"]
    assert any(label.endswith(":data") for label, _ in red["idle_gaps"])
