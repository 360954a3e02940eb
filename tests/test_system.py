"""End-to-end behaviour tests for the full system (deliverable c,
integration level): the Cornstarch MLLM training loop converges with
frozen masking, the serving path is self-consistent, and the dry-run
machinery (specs -> shardings -> HLO analysis) holds together."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs.base import SHAPES, get_config
from repro.data.synthetic import MultimodalDataset
from repro.models import api
from repro.models.mllm import build_paper_mllm
from repro.optim import optimizer as opt
from repro.training import steps


def test_mllm_projector_training_converges():
    """The paper's core training scenario: frozen encoders + frozen LLM,
    train the projectors on a fixed batch -> loss decreases."""
    mllm = build_paper_mllm("vlm", reduced=True)
    params = mllm.init(jax.random.PRNGKey(0))
    ocfg = opt.AdamWConfig(lr=3e-3, warmup_steps=3, total_steps=100,
                           weight_decay=0.0)
    fmask = mllm.frozen_mask(params)
    state = opt.init(ocfg, params, fmask)
    step, _ = steps.make_mllm_train_step(mllm, ocfg)
    step = jax.jit(step)
    ds = iter(MultimodalDataset(
        vocab_size=mllm.llm_cfg.vocab_size, text_len=32, batch_size=2,
        encoder_dims={"vision": mllm.encoders["vision"].cfg.d_model},
        encoder_tokens={"vision": 16}, modality_ids={"vision": 1}))
    batch = next(ds)   # fixed batch: memorization
    losses = []
    for i in range(60):
        params, state, m = step(params, state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    assert np.isfinite(losses).all()


def test_serving_prefill_decode_consistency():
    """Greedy decode continuation equals argmax of the parallel
    forward at each position (system-level serving correctness)."""
    cfg = get_config("qwen3-1.7b", reduced=True)
    params = api.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n = 10
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, n)), jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    logits, _ = api.forward(params, cfg, {"tokens": toks, "positions": pos})
    want = np.asarray(jnp.argmax(logits, axis=-1))[0]

    serve = jax.jit(steps.make_serve_step(cfg))
    cache = api.init_cache(cfg, 1, n)
    got = []
    for i in range(n):
        batch = {"tokens": toks[:, i:i + 1],
                 "positions": jnp.full((1, 1), i, jnp.int32)}
        tok, cache = serve(params, cache, batch)
        got.append(int(tok[0]))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_dryrun_machinery_host_scale():
    """The exact dry-run pipeline (specs -> shardings -> jit -> lower ->
    compile -> static profile) at host scale (1 device, reduced cfg)."""
    from repro.launch import hlo_analysis as H
    from repro.launch import sharding as shd
    from repro.launch import specs as S

    cfg = get_config("qwen3-1.7b", reduced=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    rules = shd.Rules(seq_parallel=False)
    shd.set_rules(rules)
    shd.set_mesh(mesh)
    try:
        p_spec = S.param_specs(cfg)
        b = {
            "tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32),
            "labels": jax.ShapeDtypeStruct((2, 16), jnp.int32),
            "positions": jax.ShapeDtypeStruct((2, 16), jnp.int32),
        }
        o_spec = S.opt_state_specs(cfg, p_spec)
        fn = steps.make_train_step(cfg)
        with mesh:
            lowered = jax.jit(fn).lower(p_spec, o_spec, b)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        assert int(mem.temp_size_in_bytes) > 0
        prof = H.analyze(compiled.as_text())
        # trip-count-aware flops must cover >= L x the per-layer matmuls
        L, d, T = cfg.num_layers, cfg.d_model, 16
        min_flops = L * 2 * 2 * T * d * cfg.q_dim  # fwd+bwd q-proj alone
        assert prof["flops"] > min_flops
    finally:
        shd.set_rules(None)
        shd.set_mesh(None)


def test_multidataset_modes_produce_valid_bam():
    from repro.core import bam
    for mode, docs in (("ep", 1), ("ee", 1), ("mp", 4)):
        ds = MultimodalDataset(
            vocab_size=128, text_len=64, batch_size=2,
            encoder_dims={"vision": 16, "audio": 16},
            encoder_tokens={"vision": 8, "audio": 8},
            modality_ids={"vision": 1, "audio": 2},
            mask_mode=mode, docs_per_row=docs)
        bits, pos = ds.merged_bits()
        W = bam.token_workload(bits, pos)
        nonpad = bits != 0
        assert (W[nonpad] >= 1).all()   # every real token attends itself
        if docs > 1:
            assert len(np.unique(bam.instance_id(
                bits[nonpad].astype(np.uint32)))) == docs


def test_dryrun_preserves_user_xla_flags():
    """Regression: importing repro.launch.dryrun used to CLOBBER any
    user-set XLA_FLAGS with its 512-device override. It must append
    the device-count flag only when the user has not already chosen
    one, and never drop unrelated flags."""
    import os
    import subprocess
    import sys

    from .helpers import REPO

    code = ("import os, repro.launch.dryrun, jax\n"
            "print(os.environ['XLA_FLAGS'])\n"
            "print(jax.device_count())")

    def run(xla_flags):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        if xla_flags is not None:
            env["XLA_FLAGS"] = xla_flags
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True,
                              timeout=600, cwd=REPO)
        assert proc.returncode == 0, proc.stderr
        flags, devices = proc.stdout.strip().rsplit("\n", 1)
        return flags, int(devices)

    # an explicit device-count choice wins — kept verbatim, honored
    flags, devices = run("--xla_force_host_platform_device_count=4")
    assert flags == "--xla_force_host_platform_device_count=4"
    assert devices == 4
    # unrelated user flags survive alongside the appended default
    flags, devices = run("--xla_cpu_enable_fast_math=false")
    assert "--xla_cpu_enable_fast_math=false" in flags
    assert "--xla_force_host_platform_device_count=512" in flags
    assert devices == 512
    # no user flags: the dry-run's 512-device default applies
    flags, devices = run(None)
    assert flags == "--xla_force_host_platform_device_count=512"
    assert devices == 512


def test_compilation_cache_placement(tmp_path):
    """``launch.train.enable_compilation_cache``: compiled entries land
    where ``JAX_COMPILATION_CACHE_DIR`` says when it is set; otherwise
    the cache is the fixed ``<repo>/.jax_cache``. Importing the module
    sets nothing."""
    import os
    import subprocess
    import sys

    from .helpers import REPO

    code = ("import sys, jax, jax.numpy as jnp\n"
            "from repro.launch import train\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(train.enable_compilation_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "if sys.argv[1] == 'compile':\n"
            "    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8))"
            ".block_until_ready()\n")

    def run(cache_dir):
        # only the run with its own cache directory compiles: the other
        # would write into the checkout
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   PYTHONPATH=os.path.join(REPO, "src"))
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if cache_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        proc = subprocess.run(
            [sys.executable, "-c", code,
             "compile" if cache_dir else "no-compile"],
            env=env, capture_output=True, text=True, timeout=600,
            cwd=tmp_path)
        assert proc.returncode == 0, proc.stderr
        return proc.stdout.split()

    where = str(tmp_path / "cache")
    assert run(where) == [where] * 3
    assert os.listdir(where)                 # the jit's entry landed
    default = os.path.join(REPO, ".jax_cache")
    before, returned, after = run(None)
    assert (returned, after) == (default, default)
    assert before in ("None", "")            # the import set nothing
