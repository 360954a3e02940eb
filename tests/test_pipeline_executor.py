"""shard_map pipeline executor + modality islands (subprocess,
multi-device) — the SPMD realizations of the paper's schedules."""
import pytest

from .helpers import run_with_devices


def test_pipeline_forward_and_grads_4_stages():
    code = """
import jax, jax.numpy as jnp
from repro.core import modality_parallel as mp
from jax.sharding import AxisType
mesh = jax.make_mesh((4,), ("stage",),
                     axis_types=(AxisType.Auto,))
key = jax.random.PRNGKey(0)
d = 32
per_stage = [{"w": jax.random.normal(jax.random.fold_in(key, s),
                                     (d, d)) * 0.1} for s in range(4)]
sp = mp.stack_stage_params(per_stage)
def stage_fn(lp, x):
    return x + jnp.tanh(x @ lp["w"])
mbs = jax.random.normal(jax.random.fold_in(key, 9), (6, 2, 8, d))
out = mp.pipeline_forward(mesh, "stage", stage_fn, sp, mbs, num_stages=4)
ref = mp.pipeline_reference(stage_fn, sp, mbs, num_stages=4)
assert float(jnp.abs(out - ref).max()) < 1e-5
def loss(sp):
    return jnp.mean(mp.pipeline_forward(mesh, "stage", stage_fn, sp, mbs,
                                        num_stages=4) ** 2)
def loss_ref(sp):
    return jnp.mean(mp.pipeline_reference(stage_fn, sp, mbs,
                                          num_stages=4) ** 2)
g1 = jax.grad(loss)(sp); g2 = jax.grad(loss_ref)(sp)
assert float(jnp.abs(g1["w"] - g2["w"]).max()) < 1e-6
print("OK")
"""
    assert "OK" in run_with_devices(code, 4)


def test_pipeline_transformer_stages():
    """Real transformer blocks as pipeline stages (paper's LLM chain)."""
    code = """
import jax, jax.numpy as jnp
from repro.core import modality_parallel as mp
from repro.configs.base import get_config
from repro.models import transformer as T
from repro.models import layers as L
cfg = get_config("qwen3-1.7b", reduced=True).replace(num_layers=4)
from jax.sharding import AxisType
mesh = jax.make_mesh((4,), ("stage",),
                     axis_types=(AxisType.Auto,))
key = jax.random.PRNGKey(0)
full = T.init(key, cfg)
per_stage = [jax.tree.map(lambda a: a[s], full["layers"]) for s in range(4)]
sp = mp.stack_stage_params(per_stage)
B, T_ = 2, 16
pos = jnp.broadcast_to(jnp.arange(T_, dtype=jnp.int32)[None], (B, T_))
batch = {"positions": pos}
def stage_fn(lp, x):
    out, _, _ = T._block(cfg, lp, x, batch, jnp.int32(0), None)
    return out
mbs = jax.random.normal(jax.random.fold_in(key, 7), (4, B, T_, cfg.d_model))
out = mp.pipeline_forward(mesh, "stage", stage_fn, sp, mbs, num_stages=4)
ref = mp.pipeline_reference(stage_fn, sp, mbs, num_stages=4)
assert float(jnp.abs(out - ref).max()) < 1e-4
print("OK")
"""
    assert "OK" in run_with_devices(code, 4)


def test_modality_islands_match_monolithic():
    code = """
import jax, jax.numpy as jnp
from repro.core import modality_parallel as mp
from repro.models.mllm import build_paper_mllm
mllm = build_paper_mllm("valm", reduced=True)
params = mllm.init(jax.random.PRNGKey(0))
batch = {
    "text_tokens": jnp.ones((2, 64), jnp.int32),
    "vision_embeds": jax.random.normal(jax.random.PRNGKey(1), (2, 16, 128)),
    "audio_embeds": jax.random.normal(jax.random.PRNGKey(2), (2, 16, 128)),
}
split = mp.split_devices(mllm, jax.devices())
isl = mp.ModalityIslands(mllm, split)
logits, aux = isl.run(params, batch)
(ref_logits, _), _ = mllm.forward(params, batch)
assert float(jnp.abs(logits - ref_logits).max()) == 0.0
# encoders really live on disjoint devices
assert set(d.id for d in split["vision"]).isdisjoint(
    d.id for d in split["audio"])
print("OK")
"""
    assert "OK" in run_with_devices(code, 8)


def test_islands_device_split_respects_plan():
    code = """
import jax
from repro.core import modality_parallel as mp
from repro.models.mllm import build_paper_mllm
mllm = build_paper_mllm("valm", reduced=True)
split = mp.split_devices(mllm, jax.devices(), plan={"vision": 2, "audio": 1})
assert len(split["vision"]) == 2 and len(split["audio"]) == 1
assert len(split["llm"]) == 5
print("OK")
"""
    assert "OK" in run_with_devices(code, 8)


def test_shardmap_moe_dispatch_matches_gspmd():
    """Perf-A4 path: the shard_map expert-parallel dispatch must be
    numerically identical to the plain capacity dispatch."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config, MoEConfig
from repro.models import moe, api
from repro.launch import sharding as shd
cfg = get_config("qwen2-moe-a2.7b", reduced=True).replace(
    moe=MoEConfig(num_experts=4, top_k=2, num_shared_experts=1,
                  d_expert=128, backend="capacity", capacity_factor=4.0,
                  expert_pad_to=4))
params = api.init(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
B, T = 4, 16
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                               jnp.int32),
         "positions": jnp.broadcast_to(
             jnp.arange(T, dtype=jnp.int32)[None], (B, T))}
l_plain, _ = moe.forward(params, cfg, batch)
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
shd.set_rules(shd.Rules(seq_parallel=False))
shd.set_mesh(mesh)
try:
    with mesh:
        l_sm, _ = jax.jit(lambda p, b: moe.forward(p, cfg, b))(params, batch)
finally:
    shd.set_rules(None); shd.set_mesh(None)
d = float(jnp.abs(l_sm - l_plain).max())
assert d < 1e-5, d
print("OK", d)
"""
    assert "OK" in run_with_devices(code, 4)
