"""AdamW with frozen-parameter masking + LR schedules.

Frozen masking is load-bearing for Cornstarch: frozen modules get NO
optimizer state and NO updates (their backward is already skipped by
stop_gradient in the forward; tests assert both). Implemented optax-free
(optax isn't in the container) as a pure pytree transformation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"    # cosine | constant


def lr_at(cfg: AdamWConfig, step):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    if cfg.schedule == "constant":
        return cfg.lr * warm
    frac = jnp.clip((step - cfg.warmup_steps) /
                    jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    return cfg.lr * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))


def _tree_zeros_like_masked(params, frozen_mask):
    """Frozen leaves get a zero-size placeholder (no optimizer memory)."""
    def z(p, frz):
        if frz:
            return jnp.zeros((0,), jnp.float32)
        return jnp.zeros_like(p, jnp.float32)
    return jax.tree.map(z, params, frozen_mask)


def global_norm(tree):
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def init(cfg: AdamWConfig, params, frozen_mask=None):
    if frozen_mask is None:
        frozen_mask = jax.tree.map(lambda _: False, params)
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": _tree_zeros_like_masked(params, frozen_mask),
        "v": _tree_zeros_like_masked(params, frozen_mask),
    }


@jax.named_scope("optimizer")
def update(cfg: AdamWConfig, grads, state, params, frozen_mask=None):
    """Returns (new_params, new_state, metrics)."""
    if frozen_mask is None:
        frozen_mask = jax.tree.map(lambda _: False, params)
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9)) \
        if cfg.grad_clip else 1.0
    step = state["step"] + 1
    lr = lr_at(cfg, step)
    bc1 = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v, frz):
        if frz:
            return p, m, v
        g = g.astype(jnp.float32) * clip
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * g * g
        mh = m / bc1
        vh = v / bc2
        delta = mh / (jnp.sqrt(vh) + cfg.eps) + \
            cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_m = tdef.flatten_up_to(state["m"])
    flat_v = tdef.flatten_up_to(state["v"])
    flat_f = tdef.flatten_up_to(frozen_mask)
    outs = [upd(p, g, m, v, frz)
            for p, g, m, v, frz in zip(flat_p, flat_g, flat_m, flat_v,
                                       flat_f)]
    new_p = tdef.unflatten([o[0] for o in outs])
    new_m = tdef.unflatten([o[1] for o in outs])
    new_v = tdef.unflatten([o[2] for o in outs])
    return new_p, {"step": step, "m": new_m, "v": new_v}, \
        {"grad_norm": gnorm, "lr": lr}
