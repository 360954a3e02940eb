"""``BENCHMARK.json`` and the files it names: every cell finds its
configuration, traffic and limits; every per-layer metric its reader;
names and units keep to the allowed characters; and ``run.py`` refuses
to run without a TPU, printing no result."""
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import compare
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _json(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def test_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["chipbench"]
    assert manifest["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_cells_name_files_that_parse(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    pairs = set()
    for cell in manifest["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        pairs.add((cell["config"], cell["traffic"]))
        entry = configs[cell["config"]]
        cfg = _json(ROOT, entry["file"])
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        tr = _json(BENCH, "traffic", cell["traffic"] + ".json")
        assert tr["mode"] in ("replay", "spmd")
        assert 0 <= tr["image_at"] <= tr["text_len"]
        limits = _json(BENCH, "limits", cell["name"] + ".json")["limits"]
        assert set(limits) <= set(compare.NUMBERS) and limits
    assert len(pairs) == len(manifest["workloads"])
    used = {c["config"] for c in manifest["workloads"]}
    assert used == set(configs)


def test_metrics_have_readers_and_are_reported(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert {"tokens_per_s", "peak_hbm_gib", "setup_s"} <= set(e2e)
    cells = [c["name"] for c in manifest["workloads"]]
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("m", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read({"trace": None}) is None
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "vlm-qwen3-1.7b.align-1600", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_refuses_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "TPU" in proc.stderr


def test_run_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
