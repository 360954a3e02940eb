"""``chip_smoke.py`` on the CPU: its phases at reduced widths, with the
fused kernels in interpret mode, so the script the chip runs cannot rot;
and its refusal to run where there is no TPU or no checkout."""
import importlib.util
import math
import os
import shutil
import subprocess
import sys

from .helpers import REPO, subprocess_test


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_reduced():
    cs = _chip_smoke()
    ok, (a, b) = cs.one_chip(reduced=True, kernel_impl="bam_interpret")
    assert ok
    steps = cs.WARMUP_STEPS + cs.MEASURED_STEPS
    for phase in (a, b):
        assert len(phase["losses"]) == steps
        assert phase["finite"] and len(phase["step_seconds"]) == steps
        assert math.isfinite(phase["compile_s"])
        assert set(phase["params_on"]) == {"encoders/vision", "llm"}
    assert (a["attn_impl"], b["attn_impl"]) == ("xla", "bam_interpret")
    assert cs.loss_gap(a["losses"], b["losses"]) <= cs.LOSS_RTOL


@subprocess_test(4)
def test_four_chip_phases_reduced():
    cs = _chip_smoke()
    ok, (replay, spmd) = cs.spmd_vs_replay(reduced=True)
    assert ok and spmd["spmd"] and not replay["spmd"]
    assert (replay["attn_impl"], spmd["attn_impl"]) == ("xla", "auto")
    ok, rows = cs.cp_vs_single(reduced=True, kernel_impl="bam_interpret")
    assert ok
    assert {(r["mode"], r["method"]) for r in rows} == {
        (m, k) for m in ("ep", "ee", "mp") for k in ("allgather", "ring")}


def test_main_refuses_without_tpu(capsys):
    assert _chip_smoke().main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_refuses_outside_checkout(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
