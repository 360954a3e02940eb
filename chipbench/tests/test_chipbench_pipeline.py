"""The harness on the pipeline path, at test widths on four virtual CPU
devices: a ``vlm-qwen2vl-7b-d8``-shaped model (q/k/v biases, an untied
head, no QK-norm, 7 query heads to each KV head) under the
``pp4-align-1600`` traffic, trained by the program's ``--spmd`` runner
through ``run.run``, comes out correct under the pipeline cell's limits,
and with its update reversed comes out not correct. (Half a batch, the
one-chip cells' other fault, is no batch the runner takes: 4 rows do not
split into the plan's 8 microbatches.) Also the reader of
``pipeline.stage_imbalance`` on synthetic traces."""
import argparse
import importlib.util
import json
import os
import subprocess
import sys

import pytest

import compare
import run as harness
from conftest import BENCH, ROOT
from test_chipbench_faults import reversed_update

CELL = "vlm-qwen2vl-7b-d8.pp4-align-1600"
DEVICES = 4
_CHILD = "CHIPBENCH_PIPELINE_CHILD"


def pipeline_config():
    with open(os.path.join(BENCH, "configs", "vlm-qwen2vl-7b-d8.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=4,
               num_attention_heads=7, num_key_value_heads=1, head_dim=16,
               vocab_size=512)
    cfg["vision"] = dict(cfg["vision"], hidden_size=48, num_hidden_layers=2,
                         num_attention_heads=2, head_dim=24,
                         intermediate_size=96, num_tokens=8)
    cfg["projector"] = dict(cfg["projector"], in_features=48,
                            out_features=64)
    assert cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    assert not cfg["qk_norm"]
    return cfg


def pipeline_traffic():
    with open(os.path.join(BENCH, "traffic", "pp4-align-1600.json"),
              encoding="utf-8") as f:
        t = json.load(f)
    t.update(text_len=32, image_at=16)
    t["plan"] = dict(t["plan"], block_size=8)
    return t


def _run(capsys, step_wrapper=None, seed=2 ** 31 + 41):
    manifest = harness._load_json("BENCHMARK.json")
    cell = {"name": "small-pp4", "config": "small", "traffic": "small",
            "chips": DEVICES}
    manifest["workloads"].append(cell)
    args = argparse.Namespace(seed=seed, seconds=0.5, trace=0)
    rc = harness.run(args, manifest, cell, pipeline_config(),
                     pipeline_traffic(),
                     limits=compare.load_limits(ROOT, CELL),
                     step_wrapper=step_wrapper,
                     peaks={"cpu": {"bf16_flops_per_s": 1.0}})
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_pipeline_cell_is_correct_and_a_reversed_update_is_not(request,
                                                              capsys):
    import jax
    if jax.device_count() < DEVICES and os.environ.get(_CHILD) != "1":
        env = dict(os.environ, JAX_PLATFORMS="cpu", **{_CHILD: "1"})
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={DEVICES}"
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "-x", "-q", "-p",
             "no:cacheprovider",
             f"{os.path.abspath(__file__)}::{request.node.name}"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        return
    sound = _run(capsys)
    assert sound["correct"] is True, sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["device"]["count"] == DEVICES
    bad = _run(capsys, reversed_update)
    assert bad["correct"] is False, bad["checks"]
    assert bad["checks"]["change_diff"]["value"] > 1.5


def _imbalance(record):
    path = os.path.join(BENCH, "metrics", "pipeline.stage_imbalance.py")
    spec = importlib.util.spec_from_file_location("imbalance", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


def _record(*chips):
    """A trace record of chips given as (busy_s, collective_s)."""
    return {"trace": {"window_s": 1.0, "devices": {
        f"/device:TPU:{i}": {"busy_s": b, "collective_s": c}
        for i, (b, c) in enumerate(chips)}}}


@pytest.mark.parametrize("chips,want", [
    (((0.6, 0.0),) * 4, 0.0),
    (((0.8, 0.0), (0.0, 0.0), (0.8, 0.0), (0.8, 0.0)), 100.0),
    (((0.8, 0.0), (0.6, 0.0), (0.4, 0.0), (0.8, 0.0)), 50.0),
    # equally busy, but three chips spend part of it waiting in handoffs
    (((0.9, 0.1), (0.9, 0.3), (0.9, 0.5), (0.9, 0.5)), 50.0),
    (((0.9, 0.9), (0.9, 0.9)), None),
    (((0.7, 0.1),), None),
], ids=["equal", "one_idle", "spread", "waits_in_collectives",
        "nothing_but_collectives", "one_chip"])
def test_stage_imbalance_reader(chips, want):
    got = _imbalance(_record(*chips))
    assert got == pytest.approx(want) if want is not None else got is None
