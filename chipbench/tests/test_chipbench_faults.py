"""A whole run past the look for a chip, at test widths on the CPU: sound,
it comes out correct; with the timed path broken underneath, once for
each fault a one-chip training cell can have, and with its update
reversed, ``correct`` comes out false. And the control, the reference in
float8, reads far above the sound program and is judged not correct."""
import argparse
import json

import pytest

import jax
import jax.numpy as jnp

import compare
import control
import run as harness
from conftest import ROOT, small_config, small_traffic

CELL = "vlm-qwen3-1.7b.align-1600"


def unchanged(step):
    """A step that returns its state unchanged."""
    def f(params, opt_state, health, batch, controls):
        _, _, h, bundle = step(params, opt_state, health, batch, controls)
        return params, opt_state, h, bundle
    return f


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def f(params, opt_state, health, batch, controls):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step(params, opt_state, health, half, controls)
    return f


def reversed_update(step):
    """An update that goes the wrong way: the step's change subtracted
    where it should be added."""
    def f(params, opt_state, health, batch, controls):
        new, o, h, bundle = step(params, opt_state, health, batch,
                                 controls)
        back = jax.tree.map(lambda p, q: (2 * p.astype(jnp.float32)
                                          - q.astype(jnp.float32)
                                          ).astype(p.dtype), params, new)
        return back, o, h, bundle
    return f


def _run(capsys, step_wrapper=None, seed=2 ** 31 + 11):
    manifest = harness._load_json("BENCHMARK.json")
    cell = {"name": "small", "config": "small", "traffic": "small",
            "chips": 1}
    manifest["workloads"].append(cell)
    args = argparse.Namespace(seed=seed, seconds=0.5, trace=0)
    rc = harness.run(args, manifest, cell, small_config(), small_traffic(),
                     limits=compare.load_limits(ROOT, CELL),
                     step_wrapper=step_wrapper,
                     peaks={"cpu": {"bf16_flops_per_s": 1.0}})
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


@pytest.mark.parametrize("fault", [None, unchanged, half_batch,
                                   reversed_update],
                         ids=["sound", "unchanged", "half_batch",
                              "reversed_update"])
def test_faults_come_out_incorrect(capsys, fault):
    out = _run(capsys, fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                   "setup_s"}


@pytest.mark.parametrize("seed", [21, 2 ** 31 + 22])
def test_control_reads_far_above_the_program(capsys, seed):
    """The control (the reference in float8) against the float32
    reference reads several times what the bfloat16 program does, at
    test widths; at the cell's own widths its readings set the limits
    (PERF.md)."""
    sound = _run(capsys, seed=seed)["checks"]
    got = control.readings(small_config(), small_traffic(), seed, ["fp8"])
    assert got["fp8"]["gnorm_gap"] > 3 * sound["gnorm_gap"]["value"], \
        (got, sound)
    # limits set as a cell's are, from the sound run at these widths
    limits = {k: 4 * v["value"] for k, v in sound.items()}
    assert control.verdict(CELL, seed, "sound", {k: v["value"] for k, v in
                                                 sound.items()},
                           limits)["correct"] is True
    line = control.verdict(CELL, seed, "fp8", got["fp8"], limits)
    assert line["correct"] is False, line
    assert set(line["checks"]) == set(sound)
