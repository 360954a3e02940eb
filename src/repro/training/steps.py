"""Training / serving step builders.

``make_train_step(cfg)`` -> jit-able ``step(params, opt_state, batch)``
for any registered architecture; cross-entropy is computed **chunked
over the sequence** (``cfg.loss_chunk``) so the full [B,T,V] logits
tensor never materializes — essential for 150k-256k vocabularies at 4k
sequence (the memory-roofline lever recorded in EXPERIMENTS.md §Perf).

``make_serve_step(cfg)`` -> one-token decode against a KV/state cache
(the ``decode_32k`` / ``long_500k`` dry-run entry point).

``make_mllm_train_step(mllm)`` -> the Cornstarch path: frozen-aware
MLLM training (encoders + projectors + LLM with frozen masking).

``make_cp_train_step(cfg, layout, mesh)`` -> context-parallel training
(Cornstarch §4.3): the batch is permuted to a ``ContextPlan`` token
layout (``layout = plan.context.apply(seq_len)``), attention runs
through the differentiable CP bodies under ``mesh``, and loss + grads
come out identical to the unpermuted step (cross-entropy is
permutation-invariant, CP attention is exact).

``make_spmd_train_step(stage_fn, graph, sim)`` -> pipeline-parallel
training under the shard_map schedule executor
(``repro.parallel.spmd``): each step runs the plan's F/B/W timeline
distributed over the mesh's pipeline axis and feeds the stage-stacked
grads to the optimizer. The mesh may carry a ``cp`` axis alongside, so
one plan JSON drives PP x CP on a single device mesh.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import api
from repro.models import transformer as T
from repro.optim import optimizer as opt


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, valid=None):
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None],
                             axis=-1)[..., 0]
    nll = lse - ll
    if valid is None:
        return jnp.mean(nll)
    w = valid.astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def chunked_cross_entropy(h, params, cfg: ModelConfig, labels, valid=None,
                          chunk: Optional[int] = None):
    """h: [B,T,d] final hidden; computes CE scanning seq chunks so only
    [B,chunk,V] logits exist at a time (recomputed in backward)."""
    B, T_, d = h.shape
    c = chunk or cfg.loss_chunk
    if not c or T_ % c != 0:
        logits = T.unembed(params, cfg, h)
        return cross_entropy(logits, labels, valid)
    nc = T_ // c
    hs = jnp.moveaxis(h.reshape(B, nc, c, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nc, c), 1, 0)
    vs = None if valid is None else \
        jnp.moveaxis(valid.reshape(B, nc, c), 1, 0)

    def body(carry, xs):
        if vs is None:
            hc, lc = xs
            vc = jnp.ones(lc.shape, jnp.float32)
        else:
            hc, lc, vc = xs
            vc = vc.astype(jnp.float32)

        def f(hc):
            logits = T.unembed(params, cfg, hc).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
            return jnp.sum((lse - ll) * vc), jnp.sum(vc)
        s, n = jax.checkpoint(f)(hc)
        tot, cnt = carry
        return (tot + s, cnt + n), None

    xs = (hs, ls) if vs is None else (hs, ls, vs)
    (tot, cnt), _ = lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), xs)
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# LM train step (all assigned architectures)
# ---------------------------------------------------------------------------

def make_loss_fn(cfg: ModelConfig):
    mod = api.module_for(cfg)

    def loss_fn(params, batch):
        valid = batch.get("valid")
        if cfg.loss_chunk and hasattr(mod, "hidden"):
            h, aux = mod.hidden(params, cfg, batch)
            loss = chunked_cross_entropy(h, params, cfg, batch["labels"],
                                         valid)
        else:
            logits, aux = mod.forward(params, cfg, batch)
            loss = cross_entropy(logits, batch["labels"], valid)
        return loss + aux.get("aux_loss", 0.0), \
            {"ce": loss, **{k: v for k, v in aux.items()}}

    return loss_fn


def make_train_step(cfg: ModelConfig, ocfg: Optional[opt.AdamWConfig] = None,
                    frozen_mask=None):
    ocfg = ocfg or opt.AdamWConfig()
    loss_fn = make_loss_fn(cfg)

    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        params, opt_state, om = opt.update(ocfg, grads, opt_state, params,
                                           frozen_mask)
        return params, opt_state, {"loss": loss, **metrics, **om}

    return step


# ---------------------------------------------------------------------------
# Context-parallel train step (Cornstarch §4.3: train THROUGH the CP
# bodies — attention gradients cross ranks via the combining-aware
# custom_vjps in core.context_parallel)
# ---------------------------------------------------------------------------

#: batch keys whose token axis follows the CP permutation -> token axis
#: (pos3 is [3, B, T] — M-RoPE position ids travel with their tokens)
_CP_TOKEN_KEYS = {"tokens": 1, "labels": 1, "positions": 1, "bits": 1,
                  "valid": 1, "inputs_embeds": 1, "embed_mask": 1,
                  "pos3": 2}


def make_cp_train_step(cfg: ModelConfig, layout, mesh,
                       ocfg: Optional[opt.AdamWConfig] = None, *,
                       axis_name: str = "cp", method: str = "allgather",
                       frozen_mask=None):
    """Context-parallel LM train step.

    ``layout`` is ``ContextPlan.apply(seq_len)``'s dict (``perm``,
    ``inv_perm``, ``num_ranks``): the step permutes every token-axis
    batch array into plan layout, then runs the ordinary loss with
    ``cfg`` rewired so attention dispatches through
    ``core.context_parallel.cp_attention`` over ``mesh``'s
    ``axis_name`` axis (per-step math from
    ``models.layers.resolve_attn_impl``; ``method`` picks allgather vs
    ring). Because the permutation rides every
    per-token tensor and CP attention is exact, loss and grads match
    ``make_train_step`` on the unpermuted batch.
    """
    ocfg = ocfg or opt.AdamWConfig()
    perm = jnp.asarray(layout["perm"])
    n_dev = mesh.shape[axis_name]
    if len(layout["perm"]) % n_dev != 0:
        raise ValueError(
            f"seq_len {len(layout['perm'])} is not divisible by the "
            f"{n_dev}-device {axis_name!r} mesh axis; pad the sequence "
            f"to a rank multiple before planning")
    if layout["num_ranks"] != n_dev:
        # math stays exact on any mesh size (shard_map just re-slices
        # the permuted axis), but the plan's workload balance only
        # holds when rank slices align with devices — say so
        import warnings
        warnings.warn(
            f"ContextPlan was balanced for {layout['num_ranks']} ranks "
            f"but the {axis_name!r} mesh axis has {n_dev} devices; "
            f"results are exact but the planned load balance is lost",
            stacklevel=2)
    cp_cfg = cfg.replace(cp_mesh=mesh, cp_axis=axis_name,
                         cp_method=method, attn_q_chunk=0)
    loss_inner = make_loss_fn(cp_cfg)

    def loss_fn(params, batch):
        if batch.get("bits") is None:
            # without bits run_attention cannot dispatch to
            # cp_attention — every device would replicate the full
            # dense attention and nothing would be context-parallel
            raise ValueError(
                "make_cp_train_step needs batch['bits'] (BAM "
                "bitfields); use bam.causal_bits for pure-text batches")
        pb = dict(batch)
        for key, axis in _CP_TOKEN_KEYS.items():
            if pb.get(key) is not None:
                pb[key] = jnp.take(pb[key], perm, axis=axis)
        return loss_inner(params, pb)

    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        params, opt_state, om = opt.update(ocfg, grads, opt_state, params,
                                           frozen_mask)
        return params, opt_state, {"loss": loss, **metrics, **om}

    return step


# ---------------------------------------------------------------------------
# SPMD pipeline train step (schedule executor under shard_map)
# ---------------------------------------------------------------------------

def make_spmd_train_step(stage_fn, graph, sim,
                         ocfg: Optional[opt.AdamWConfig] = None, *,
                         mesh=None, axis_name: str = "pp",
                         microbatch_loss=None, frozen_mask=None,
                         trainable=None, grad_scale: float = 1.0,
                         program=None):
    """Pipeline-parallel train step driven by a simulated schedule
    timeline, executed distributed (``repro.parallel.spmd``).

    ``stage_fn`` / ``stage_params`` follow the ``execute_schedule``
    contract — a single homogeneous callable with stage-stacked params,
    or a real-model stage list (``models.stages.StageBundle``:
    per-stage 3-arg fns, list params, ``trainable`` flags);
    ``graph``/``sim`` come from the plan (``executor["sim_graph"]`` /
    ``executor["schedule"]`` of ``plan.apply(mllm, mode="spmd")``, pass
    ``program=executor["spmd_program"]`` to reuse its compile). The
    schedule program is compiled once; every ``step(stage_params,
    opt_state, microbatches)`` replays it under ``shard_map`` (the
    jitted core is cached across steps) and applies AdamW — list
    params flow through AdamW as a pytree, with ``frozen_mask``
    keeping optimizer state out of frozen slots. ``grad_scale``
    rescales the summed per-microbatch loss/grads to the full-batch
    mean (``1/num_microbatches`` for ``StageBundle.microbatch_loss``).
    Frozen stages contribute exactly-zero grads by construction."""
    from repro.parallel.spmd import build_spmd_runner
    ocfg = ocfg or opt.AdamWConfig()
    runner = build_spmd_runner(stage_fn, graph, sim, mesh=mesh,
                               axis_name=axis_name,
                               microbatch_loss=microbatch_loss,
                               trainable=trainable, program=program)

    def step(stage_params, opt_state, microbatches):
        res = runner(stage_params, microbatches)
        grads, loss = res["param_grads"], res["loss"]
        if grad_scale != 1.0:
            grads = jax.tree.map(lambda g: g * grad_scale, grads)
            loss = loss * grad_scale
        params, opt_state, om = opt.update(
            ocfg, grads, opt_state, stage_params, frozen_mask)
        return params, opt_state, {"loss": loss, **om}

    return step


# ---------------------------------------------------------------------------
# Serve step (decode shapes)
# ---------------------------------------------------------------------------

def make_serve_step(cfg: ModelConfig):
    def serve_step(params, cache, batch):
        logits, cache = api.decode_step(params, cfg, cache, batch)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, cache
    return serve_step


def make_prefill(cfg: ModelConfig):
    """prefill = full forward returning logits of the last position
    (prefill_32k dry-run entry point)."""
    mod = api.module_for(cfg)

    def prefill(params, batch):
        if cfg.loss_chunk and hasattr(mod, "hidden"):
            h, _ = mod.hidden(params, cfg, batch)
            return T.unembed(params, cfg, h[:, -1:, :])
        logits, _ = mod.forward(params, cfg, batch)
        return logits[:, -1:, :]
    return prefill


# ---------------------------------------------------------------------------
# Cornstarch MLLM train step (frozen-aware)
# ---------------------------------------------------------------------------

def make_mllm_train_step(mllm, ocfg: Optional[opt.AdamWConfig] = None):
    ocfg = ocfg or opt.AdamWConfig()

    def loss_fn(params, batch):
        (logits, aux), merged = mllm.forward(params, batch)
        # loss over text positions only (modality tokens carry no labels)
        is_text = (merged["bits"] != 0) & (~merged["embed_mask"])
        B, Tm = merged["tokens"].shape
        labels = jnp.zeros((B, Tm), jnp.int32)
        # labels provided for the original text token stream; scatter
        # them to text slots
        txt_idx = jnp.cumsum(is_text.astype(jnp.int32), axis=1) - 1
        lab_src = batch["labels"]
        gathered = jnp.take_along_axis(
            lab_src, jnp.clip(txt_idx, 0, lab_src.shape[1] - 1), axis=1)
        labels = jnp.where(is_text, gathered, 0)
        with jax.named_scope("lm_head"):
            loss = cross_entropy(logits, labels, valid=is_text)
        return loss + aux.get("aux_loss", 0.0), {"ce": loss}

    def step(params, opt_state, batch):
        # frozen mask is a *static* structure of python bools derived
        # from the module flags (not traced values)
        frozen_mask = mllm.frozen_mask(params)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        params, opt_state, om = opt.update(ocfg, grads, opt_state, params,
                                           frozen_mask)
        return params, opt_state, {"loss": loss, **metrics, **om}

    return step, loss_fn
