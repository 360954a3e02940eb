"""Sharding-rule tests: divisibility-aware candidate selection for
every assigned architecture against the production mesh geometry
(no devices needed — specs are pure functions of shapes), and the
expert-parallel MoE dispatch those rules select, run on a 2x2 host
mesh in a subprocess."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, get_config, list_archs
from repro.launch import sharding as shd
from repro.launch import specs as S

from .helpers import run_with_devices


class FakeMesh:
    """Geometry-only stand-in for the (16,16)/(2,16,16) meshes."""

    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.zeros(shape)


MESH_1POD = FakeMesh((16, 16), ("data", "model"))
MESH_2POD = FakeMesh((2, 16, 16), ("pod", "data", "model"))


def axis_size(mesh, entry):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for e in entry:
            n *= sizes[e]
        return n
    return sizes[entry]


def check_divisible(tree_specs, tree_shapes, mesh):
    flat_sp = jax.tree.leaves(tree_specs,
                              is_leaf=lambda x: isinstance(x, P))
    flat_sh = jax.tree.leaves(tree_shapes)
    assert len(flat_sp) == len(flat_sh)
    for spec, leaf in zip(flat_sp, flat_sh):
        for dim, entry in zip(leaf.shape[len(leaf.shape) - len(spec):],
                              spec):
            n = axis_size(mesh, entry)
            assert dim % n == 0, (spec, leaf.shape)


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD],
                         ids=["1pod", "2pod"])
def test_param_specs_divisible(arch, mesh):
    cfg = get_config(arch)
    p_spec = S.param_specs(cfg)
    specs = shd.param_pspecs(p_spec, mesh)
    check_divisible(specs, p_spec, mesh)


@pytest.mark.parametrize("arch", ["starcoder2-7b", "qwen2-moe-a2.7b",
                                  "zamba2-2.7b", "xlstm-125m"])
@pytest.mark.parametrize("shape", ["decode_32k"])
def test_cache_specs_divisible(arch, shape):
    cfg = get_config(arch)
    c_spec = S.cache_specs(cfg, SHAPES[shape])
    rules = shd.Rules(seq_parallel=False)
    specs = shd.cache_pspecs(rules, c_spec, MESH_1POD)
    check_divisible(specs, c_spec, MESH_1POD)


def test_moe_expert_fallback_to_tp():
    """60 unpadded experts % 16 != 0 -> falls back to TP-within-expert;
    the shipped config pads to 64 (expert-parallel, next test)."""
    from repro.configs.base import MoEConfig
    import dataclasses
    cfg = get_config("qwen2-moe-a2.7b")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, expert_pad_to=0))
    p_spec = S.param_specs(cfg)
    specs = shd.param_pspecs(p_spec, MESH_1POD)
    wg = specs["layers"]["mlp"]["w_gate"]     # [L, E, d, f]
    assert wg == P(None, None, None, "model")  # f=1408 sharded


def test_moe_expert_pad_enables_ep():
    """expert_pad_to=64 (shipped qwen2-moe config) -> expert-parallel."""
    cfg = get_config("qwen2-moe-a2.7b")
    assert cfg.moe.num_experts_padded == 64
    p_spec = S.param_specs(cfg)
    specs = shd.param_pspecs(p_spec, MESH_1POD)
    assert specs["layers"]["mlp"]["w_gate"] == P(None, "model", None, None)


def test_moe_expert_parallel_when_divisible():
    cfg = get_config("deepseek-moe-16b")      # 64 experts % 16 == 0
    p_spec = S.param_specs(cfg)
    specs = shd.param_pspecs(p_spec, MESH_1POD)
    wg = specs["layers"]["mlp"]["w_gate"]
    assert wg == P(None, "model", None, None)  # expert-parallel


def test_whisper_vocab_fallback():
    """51865 % 16 != 0 -> embedding shards d_model instead."""
    cfg = get_config("whisper-base")
    p_spec = S.param_specs(cfg)
    specs = shd.param_pspecs(p_spec, MESH_1POD)
    assert specs["embed"] == P(None, "model")  # d=512 sharded, not vocab


def test_kv_cache_seq_fallback_for_narrow_gqa():
    """kv heads 4 % 16 != 0 -> cache seq dim takes the model axis."""
    cfg = get_config("starcoder2-7b")
    c_spec = S.cache_specs(cfg, SHAPES["decode_32k"])
    specs = shd.cache_pspecs(shd.Rules(seq_parallel=False), c_spec,
                             MESH_1POD)
    assert specs["k"] == P(None, ("data",), "model", None, None)


def test_long500k_cache_seq_over_data():
    cfg = get_config("zamba2-2.7b")
    c_spec = S.cache_specs(cfg, SHAPES["long_500k"])
    rules = shd.Rules(seq_parallel=False, shard_cache_seq=True)
    specs = shd.cache_pspecs(rules, c_spec, MESH_1POD)
    assert specs["attn_k"][2] in ("data", ("data",))


def test_zero_opt_sharding():
    cfg = get_config("qwen3-1.7b")
    p_spec = S.param_specs(cfg)
    base = shd.opt_state_pspecs(shd.Rules(), p_spec, MESH_1POD)
    zero = shd.opt_state_pspecs(shd.Rules(zero_sharded_opt=True), p_spec,
                                MESH_1POD)
    # ZeRO shards the first replicated dim that divides (L=28 does not
    # divide 16, so the d_model dim takes the data axis)
    w = zero["layers"]["attn"]["wq"]
    assert any(e in ("data", ("data",)) for e in w)
    assert not any(e in ("data", ("data",))
                   for e in base["layers"]["attn"]["wq"])


def test_constrain_residual_noop_without_rules():
    x = jnp.ones((2, 4, 8))
    shd.set_rules(None)
    y = shd.constrain_residual(x)
    assert y.shape == x.shape


@pytest.mark.parametrize("arch", list_archs())
def test_input_specs_constructible(arch):
    cfg = get_config(arch)
    for name, sh in SHAPES.items():
        if sh.kind in ("train", "prefill"):
            b = S.train_input_specs(cfg, sh)
            assert b["tokens"].shape == (sh.global_batch, sh.seq_len)
        else:
            b = S.decode_input_specs(cfg, sh)
            assert b["tokens"].shape == (sh.global_batch, 1)


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
