"""Production mesh builders (TPU v5e pods).

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first
jax init; everything else sees the single real CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) — roofline terms (EXPERIMENTS.md)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_BW = 50e9                  # B/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Tiny mesh over the locally available devices (tests/examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
