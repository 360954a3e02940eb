"""Pipeline execution helpers on TPU/JAX (Cornstarch §4.1).

``execute_schedule`` replays a simulated F/B/W item timeline with real
stage computations and real VJPs, holding every inter-stage activation
in an instrumented store. It is the measurement side of the
memory-validation harness (``core.schedule.memory``), which
cross-checks the simulator's per-device peak-activation claims against
execution, and the sequential reference that the distributed executor,
``repro.parallel.spmd.build_spmd_runner``, is held to schedule by
schedule. ``normalize_stage_fns`` gives both executors one stage-fn
contract, and ``split_devices`` hands out physical devices per module
for a plan.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp


def _accepts_microbatch(fn: Callable) -> bool:
    """Does ``fn`` implement the 3-arg StageFn contract
    ``fn(stage_params, x, microbatch)``?  Legacy 2-arg stage fns
    (``fn(stage_params, x)``) are still accepted everywhere."""
    import inspect
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    params = list(sig.parameters.values())
    if any(p.kind == p.VAR_POSITIONAL for p in params):
        return True
    pos = [p for p in params if p.kind in
           (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    return len(pos) >= 3


def normalize_stage_fns(stage_fn, num_stages: int) -> List[Callable]:
    """Normalize a stage-fn argument to a list of per-stage 3-arg
    callables (``models.stages.StageBundle.stage_fns`` passes a list;
    a single callable is replicated; 2-arg fns get the microbatch
    argument dropped)."""
    if isinstance(stage_fn, (list, tuple)):
        fns = list(stage_fn)
        if len(fns) != num_stages:
            raise ValueError(
                f"got {len(fns)} stage fns for {num_stages} stages")
    else:
        fns = [stage_fn] * num_stages
    return [f if _accepts_microbatch(f)
            else (lambda lp, x, mb, _f=f: _f(lp, x)) for f in fns]


def execute_schedule(stage_fn, stage_params, microbatches,
                     graph, sim: Dict[str, Any], *,
                     microbatch_loss: Optional[Callable] = None,
                     devices: Optional[Sequence[Any]] = None,
                     trainable: Optional[Sequence[bool]] = None
                     ) -> Dict[str, Any]:
    """Execute a simulated schedule's work-item timeline with REAL
    stage computations, instrumenting live activations per device.

    This is the executor side of the memory-validation harness
    (``core.schedule.memory``): the discrete-event simulator claims a
    per-device peak of live activations under its admission caps
    (``depth_from_end``); this function replays the exact item order
    the simulator emitted — F with a real forward, B with a real
    input-grad VJP, W with a real weight-grad VJP — while holding every
    inter-stage activation in an explicit store that is filled at F and
    drained at B. The store's peak occupancy per device is the
    measurement. Executing the timeline also *validates* it: an item
    order that violated data dependencies or freed an activation too
    early dies with a KeyError here rather than silently diverging.

    Contracts: ``stage_fn`` is one callable or a per-stage list, each
    ``fn(lp, x, microbatch) -> y`` (legacy ``fn(lp, x)`` accepted) with
    x/y of identical shape (the carrier contract — real MLLM stages
    come from ``models.stages``); ``stage_params`` stage-stacked with
    leading dim S, or a *list* of per-stage trees when stages are
    heterogeneous (param_grads then comes back as a matching list);
    ``trainable`` overrides which stages must produce weight grads —
    default ``bwd_w > 0`` per stage, but a frozen stage holding a
    trainable projector has no W cost in the schedule model yet still
    needs its grads glued at B (the paper's §6 configuration);
    ``microbatches`` [M, ...]; ``graph`` any stage DAG in topological
    order — source
    stages read the microbatch, fan-in stages consume the SUM of their
    predecessors' outputs (the modality-parallel merge: every encoder
    chain feeds the first LLM stage), fan-out stages accumulate the
    cotangents their successors send back, and the loss sums over sink
    stages. ``sim`` is any ``core.schedule`` simulation dict (``items``
    + ``device_of``), so folded placements — interleaved round-robin,
    ZB-V — execute on their simulated device map. When ``devices`` (one JAX device per
    pipeline rank) is given, each rank's params and activations are
    placed on its device; otherwise placement is logical.

    Memory accounting mirrors the simulator's model: an activation is
    live on stage s's device from the execution of F(s, m) until the
    execution of B(s, m). Two deliberate simplifications, kept
    symmetric on both sides so the comparison stays exact: (1) output
    cotangents and in-transit stage outputs are not counted (they hand
    over at the consumer's admission point, which is what the caps
    bound); (2) a trainable stage's deferred W pass moves its operands
    (input activation + output cotangent) to a separate W-residual
    store, reported as ``peak_w_residuals_per_device`` — the zero-
    bubble papers' memory-vs-bubble trade-off, measured rather than
    hidden.

    Returns dict: outputs [M, ...], loss, param_grads (stage-stacked,
    zero for stages the schedule assigns no W/B-glued weight work),
    peak_activations_per_device, peak_w_residuals_per_device.
    """
    from repro.core.schedule.simulator import item_id

    S = len(graph.stages)
    preds, succs = graph.preds, graph.succs
    M = int(microbatches.shape[0])
    items = sim["items"]
    device_of = sim["device_of"]
    D = int(sim["num_devices"])
    loss_fn = microbatch_loss or (lambda y: jnp.mean(y ** 2))
    has_w_items = any(kind == "W" for _, _, _, kind, _, _ in items)
    fns = normalize_stage_fns(stage_fn, S)
    hetero = isinstance(stage_params, (list, tuple))
    if trainable is None:
        trainable = [graph.stages[s].bwd_w > 0 for s in range(S)]
    trainable = [bool(t) for t in trainable]
    assert len(trainable) == S

    def rank_param(s):
        lp = stage_params[s] if hetero \
            else jax.tree.map(lambda a: a[s], stage_params)
        if devices is not None:
            lp = jax.device_put(lp, devices[device_of[s]])
        return lp

    params = [rank_param(s) for s in range(S)]
    grads = [jax.tree.map(jnp.zeros_like, p) for p in params]
    store: Dict[tuple, Any] = {}        # (s, m) -> input activation
    w_store: Dict[tuple, Any] = {}      # (s, m) -> (x, output cotangent)
    transit: Dict[tuple, Any] = {}      # produced, not yet admitted
    cot: Dict[tuple, Any] = {}          # (s, m) -> output cotangent
    outputs: List[Any] = [None] * M

    def accumulate(d: Dict[tuple, Any], key: tuple, val: Any) -> None:
        # fan-in merge: a consumer stage with several predecessors (or
        # a fan-out stage with several successors in the backward)
        # sums what arrives, in timeline order
        d[key] = val if key not in d else jax.tree.map(
            jnp.add, d[key], val)

    peak = [0] * D
    w_peak = [0] * D
    loss = 0.0
    # per-item measurement: (item_id, device, live activations on that
    # device AFTER the item ran) — the ids are
    # ``core.schedule.simulator.item_id`` strings, shared with
    # schedlint findings and MemoryModelMismatch diffs
    trace: List[tuple] = []
    act_nbytes = 0

    def store_count(d):
        # measure the CONTAINER, not a parallel counter: the peak is
        # however many entries the store truly holds for device d
        return sum(1 for (s_, _m) in store if device_of[s_] == d)

    for item in items:
        start, _end, dev, kind, s, m = item
        st = graph.stages[s]
        if kind == "F":
            x = transit.pop((s, m)) if preds[s] else microbatches[m]
            if devices is not None:
                x = jax.device_put(x, devices[dev])
            store[(s, m)] = x
            act_nbytes = max(act_nbytes, int(getattr(x, "nbytes", 0)))
            peak[dev] = max(peak[dev], store_count(dev))
            y = fns[s](params[s], x, microbatches[m])
            if not succs[s]:                     # sink: loss + cotangent
                outputs[m] = y if outputs[m] is None \
                    else outputs[m] + y
                loss = loss + loss_fn(y)
                accumulate(cot, (s, m), jax.grad(loss_fn)(y))
            else:
                for q in succs[s]:
                    accumulate(transit, (q, m), y)
        elif kind == "B":
            x = store.pop((s, m))
            # frozen stages with nothing trainable upstream (bwd_b = 0)
            # receive no cotangent — their B item only frees memory
            g = cot.pop((s, m), None)
            assert g is not None or (st.bwd_b == 0 and st.bwd_w == 0
                                     and not trainable[s]), \
                f"missing cotangent for B({s}, {m})"
            if st.bwd_b > 0 and preds[s]:
                _, vjp_x = jax.vjp(
                    lambda xx: fns[s](params[s], xx, microbatches[m]), x)
                (dx,) = vjp_x(g)
                for p in preds[s]:
                    accumulate(cot, (p, m), dx)
            if trainable[s]:
                # park for a deferred W item only if the schedule
                # emitted one (bwd_w > 0); a trainable stage the cost
                # model sees as weight-free glues its grads here
                if has_w_items and st.bwd_w > 0:
                    w_store[(s, m)] = (x, g)
                    w_peak[dev] = max(w_peak[dev], sum(
                        1 for (s_, _m) in w_store
                        if device_of[s_] == dev))
                else:                        # glued: weight grads now
                    _, vjp_p = jax.vjp(
                        lambda pp: fns[s](pp, x, microbatches[m]),
                        params[s])
                    (gp,) = vjp_p(g)
                    grads[s] = jax.tree.map(jnp.add, grads[s], gp)
        else:                                # W
            parked = w_store.pop((s, m), None)
            if parked is not None:           # else: trainable=False
                x, g = parked                # override — W is a no-op
                _, vjp_p = jax.vjp(
                    lambda pp: fns[s](pp, x, microbatches[m]), params[s])
                (gp,) = vjp_p(g)
                grads[s] = jax.tree.map(jnp.add, grads[s], gp)
        trace.append((item_id(item), dev, store_count(dev)))

    assert not store and not w_store and not transit, \
        "schedule left live activations behind (incomplete timeline)"
    assert all(y is not None for y in outputs)
    return {
        "outputs": jnp.stack(outputs),
        "loss": loss,
        "param_grads": grads if hetero
        else jax.tree.map(lambda *xs: jnp.stack(xs), *grads),
        "peak_activations_per_device": peak,
        "peak_w_residuals_per_device": w_peak,
        "activation_trace": trace,
        "activation_nbytes": act_nbytes,
    }


def _is_typed_plan(plan: Any) -> bool:
    from repro.parallel.plan import MLLMParallelPlan
    return isinstance(plan, MLLMParallelPlan)


def split_devices(mllm, devices: Sequence[Any],
                  plan: Any = None) -> Dict[str, list]:
    """Assign device counts per module (default: 1 per encoder, rest to
    the LLM). ``plan`` is an ``MLLMParallelPlan`` (its
    ``stage_counts_by_name()``), a plain {encoder_name: count} dict, or
    None. The winning schedule travels on the typed plan
    (``plan.schedule``); this dict stays purely {module: device list}."""
    devices = list(devices)
    if _is_typed_plan(plan):
        plan = plan.stage_counts_by_name()
    plan = plan or {name: 1 for name in mllm.encoders}
    out: Dict[str, list] = {}
    i = 0
    for name in sorted(mllm.encoders):
        n = plan.get(name, 1)
        out[name] = devices[i:i + n]
        i += n
    out["llm"] = devices[i:]
    assert out["llm"], "no devices left for the LLM"
    return out
