"""Step-health monitoring: in-jit metrics bundle + host-side classifier.

Two halves, split exactly at the device/host boundary:

* :func:`make_resilient_train_step` builds the **guarded** train step.
  Inside the jitted step it computes NaN/Inf flags, the global grad
  norm, and an EMA-based loss-spike z-score, fuses them into ONE small
  f32 vector (``BUNDLE_KEYS`` names its lanes), and — crucially —
  gates the optimizer update on step health *inside* the jit: a
  non-finite or over-norm step applies **no** update (params, optimizer
  moments, and the EMA state all keep their previous values via a
  ``jnp.where`` select), so a single NaN can never poison training
  state no matter what the host does with the verdict. The host reads
  one array per step — the same sync logging already paid for — and
  per-step *policy* knobs (grad-norm ceiling, retry clip scale, fault
  injection) are traced scalars, so changing them never retraces.

* :class:`HealthMonitor` is the host-side classifier: it maps a bundle
  to an ``ok | skip | rollback | abort`` verdict under a
  :class:`MonitorConfig` policy (consecutive-skip escalation, total
  rollback budget) and writes every decision to a structured JSONL
  :class:`EventLog` — the audit trail the fault-injection tests replay.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.optim import optimizer as opt

#: verdicts, in escalation order
OK, SKIP, ROLLBACK, ABORT = "ok", "skip", "rollback", "abort"
VERDICTS = (OK, SKIP, ROLLBACK, ABORT)

#: lanes of the fused health bundle the guarded step emits, in order:
#:   loss       — this step's loss (pre-gate; may be nan/inf)
#:   grad_norm  — global grad norm (pre-clip; may be nan/inf)
#:   spike      — |loss - EMA| / sqrt(EMA-variance) z-score (0 during
#:                EMA warmup — the host applies its own warmup gate too)
#:   nonfinite  — 1.0 iff loss or grad norm is NaN/Inf
#:   applied    — 1.0 iff the in-jit gate applied the update
BUNDLE_KEYS = ("loss", "grad_norm", "spike", "nonfinite", "applied")


def init_health() -> Dict[str, Any]:
    """The EMA state threaded through the guarded step (and bundled
    into every checkpoint, so resumes keep the spike baseline)."""
    return {"ema": jnp.float32(0.0), "var": jnp.float32(0.0),
            "count": jnp.int32(0)}


def default_controls() -> Dict[str, Any]:
    """Per-step policy scalars (traced — mutate freely, no retrace):
    ``max_grad_norm`` in-jit skip ceiling, ``clip_scale`` retry grad
    shrink (<1 after a rollback), ``inject_nan`` deterministic
    NaN-grad fault switch."""
    return {"max_grad_norm": jnp.float32(np.inf),
            "clip_scale": jnp.float32(1.0),
            "inject_nan": jnp.float32(0.0)}


def make_resilient_train_step(loss_fn, ocfg: opt.AdamWConfig,
                              frozen_mask=None, *,
                              ema_decay: float = 0.98,
                              value_and_grad_fn=None):
    """``step(params, opt_state, health, batch, controls) ->
    (params, opt_state, health, bundle)`` — ``make_train_step`` with
    the health bundle fused in and the update gated on step health.

    ``loss_fn(params, batch) -> (loss, aux)`` is the same callable the
    plain step builders consume (``steps.make_loss_fn`` or
    ``make_mllm_train_step``'s second return). The bundle is one f32
    ``[len(BUNDLE_KEYS)]`` vector — a single device->host transfer
    per step, no extra syncs.

    ``value_and_grad_fn(params, batch) -> ((loss, aux), grads)``
    overrides the default ``jax.value_and_grad(loss_fn)`` — this is
    how executors that compute grads themselves (the SPMD schedule
    runner, whose backward is the schedule's B/W items, not one
    autodiff sweep) plug into the same health gate. When set,
    ``loss_fn`` may be ``None``.
    """
    if value_and_grad_fn is None:
        value_and_grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(params, opt_state, health, batch, controls):
        (loss, _aux), grads = value_and_grad_fn(params, batch)
        with jax.named_scope("health"):
            # deterministic fault injection: a traced switch multiplies
            # every grad by NaN — exactly what a real overflow looks like
            # downstream, with none of the nondeterminism
            poison = jnp.where(controls["inject_nan"] > 0,
                               jnp.float32(np.nan), jnp.float32(1.0))
            grads = jax.tree.map(lambda g: g * poison.astype(g.dtype), grads)
            gnorm = opt.global_norm(grads)
            finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            ok = finite & (gnorm <= controls["max_grad_norm"])

            # EMA loss-spike score (computed BEFORE this step's loss is
            # folded in — a spike must not dilute its own baseline)
            warm = health["count"] > 0
            mean = jnp.where(warm, health["ema"], loss)
            dev = loss - mean
            spike = jnp.where(
                warm & finite,
                jnp.abs(dev) * jax.lax.rsqrt(health["var"] + 1e-8),
                jnp.float32(0.0))

            # the optimizer must never see non-finite grads (NaN would
            # poison the Adam moments even if params were later restored):
            # zero them, run the update, then select old vs new on `ok`
            safe_scale = jnp.where(ok, controls["clip_scale"],
                                   jnp.float32(0.0))
            safe = jax.tree.map(
                lambda g: (g.astype(jnp.float32) * safe_scale).astype(g.dtype),
                grads)
        new_p, new_s, _om = opt.update(ocfg, safe, opt_state, params,
                                       frozen_mask)
        with jax.named_scope("health"):
            sel = lambda a, b: jnp.where(ok, a, b)           # noqa: E731
            new_p = jax.tree.map(sel, new_p, params)
            new_s = jax.tree.map(sel, new_s, opt_state)

            upd = ok  # EMA tracks only applied steps: a skipped spike must
            #           not drag the baseline toward itself
            new_health = {
                "ema": jnp.where(upd, ema_decay * mean
                                 + (1 - ema_decay) * loss, health["ema"]),
                "var": jnp.where(upd, ema_decay * health["var"]
                                 + (1 - ema_decay) * dev * dev,
                                 health["var"]),
                "count": health["count"] + upd.astype(jnp.int32),
            }
            bundle = jnp.stack([
                loss.astype(jnp.float32), gnorm.astype(jnp.float32), spike,
                1.0 - finite.astype(jnp.float32), ok.astype(jnp.float32)])
        return new_p, new_s, new_health, bundle

    return step


def bundle_dict(bundle) -> Dict[str, float]:
    """One host sync: device bundle vector -> {key: float}."""
    vals = np.asarray(bundle, np.float32)
    return {k: float(v) for k, v in zip(BUNDLE_KEYS, vals)}


# ---------------------------------------------------------------------------
# Host side: event log + verdict classifier
# ---------------------------------------------------------------------------

class EventLog:
    """Structured JSONL event sink. Every event is one json object per
    line with at least ``{"step", "kind"}``; ``path=None`` keeps the
    log in memory only (tests). Appends are flushed per event so a
    crash cannot lose the decision trail."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.events: List[dict] = []
        if path:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)

    def emit(self, kind: str, step: int, **fields) -> dict:
        ev = {"kind": kind, "step": int(step), **fields}
        self.events.append(ev)
        if self.path:
            with open(self.path, "a", encoding="utf-8") as f:
                f.write(json.dumps(ev) + "\n")
                f.flush()
        return ev

    def of_kind(self, kind: str) -> List[dict]:
        return [e for e in self.events if e["kind"] == kind]


@dataclasses.dataclass(frozen=True)
class MonitorConfig:
    """Host-side verdict policy.

    spike_sigma: EMA z-score above which a finite loss is a spike.
    spike_warmup: applied steps before the z-score is trusted (the EMA
        variance estimate is garbage early).
    max_grad_norm: grad-norm ceiling; above it a step is skipped (the
        same value should be passed as the ``max_grad_norm`` control so
        the in-jit gate withholds the update).
    skip_limit: consecutive skips tolerated before escalating to
        rollback (0 = first bad step rolls back immediately).
    max_rollbacks: total rollbacks tolerated before abort.
    """
    spike_sigma: float = 8.0
    spike_warmup: int = 20
    max_grad_norm: float = math.inf
    skip_limit: int = 2
    max_rollbacks: int = 3


class HealthMonitor:
    """Maps health bundles to verdicts and logs every decision."""

    def __init__(self, cfg: Optional[MonitorConfig] = None,
                 log: Optional[EventLog] = None):
        self.cfg = cfg or MonitorConfig()
        self.log = log if log is not None else EventLog()
        self.consecutive_skips = 0
        self.rollbacks = 0
        self.applied_steps = 0

    def classify(self, step: int, bundle: Dict[str, float]) -> str:
        """One verdict per step. Escalation is stateful: skips in a row
        beyond ``skip_limit`` become a rollback; rollbacks beyond
        ``max_rollbacks`` become an abort."""
        cfg = self.cfg
        verdict, reason = OK, None
        if bundle["nonfinite"] >= 0.5:
            verdict, reason = SKIP, "nonfinite"
        elif bundle["grad_norm"] > cfg.max_grad_norm:
            verdict, reason = SKIP, "grad-norm"
        elif (self.applied_steps >= cfg.spike_warmup
              and bundle["spike"] > cfg.spike_sigma):
            verdict, reason = ROLLBACK, "loss-spike"

        if verdict == SKIP:
            self.consecutive_skips += 1
            if self.consecutive_skips > cfg.skip_limit:
                verdict = ROLLBACK
        else:
            self.consecutive_skips = 0
        if verdict == ROLLBACK:
            self.rollbacks += 1
            self.consecutive_skips = 0
            if self.rollbacks > cfg.max_rollbacks:
                verdict = ABORT
        if verdict == OK:
            self.applied_steps += 1
        if verdict != OK:
            self.log.emit("verdict", step, verdict=verdict, reason=reason,
                          **{k: bundle[k] for k in BUNDLE_KEYS})
        return verdict
