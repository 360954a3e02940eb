"""Modality parallelism + pipeline execution on TPU/JAX (Cornstarch §4.1).

Two complementary realizations of the paper's MPMD schedule in JAX's
SPMD world (DESIGN.md §2):

1. **Circular pipeline executor** (``pipeline_forward``): a single chain
   of homogeneous stages mapped onto a ``stage`` mesh axis via
   ``shard_map``. Microbatch ``m`` occupies stage ``s`` at tick
   ``t = m + s``; activations advance with ``lax.ppermute`` inside a
   ``lax.scan`` over ticks (the standard GPipe-on-TPU construction —
   1F1B's memory policy is a scheduling refinement that SPMD ticks
   subsume; bubble accounting for 1F1B / interleaved-1F1B / ZB-H1 /
   ZB-V lives in core/schedule's simulator, and ``split_devices``
   threads the schedule picked by Algorithm 1 through to the executor
   plan). Autodiff through the scan gives the backward pipeline for
   free.

2. **Modality islands** (``ModalityIslands``): the paper's modality
   parallelism proper — each encoder is jitted onto a *disjoint device
   subset*; JAX's async dispatch overlaps their execution exactly
   because the execution DAG has no edge between them (paper C1). The
   LLM island consumes their outputs. On a real multi-pod TPU each
   island is one pjit program over its submesh.

3. **Schedule-driven executor** (``execute_schedule``): replays a
   simulated F/B/W item timeline with real stage computations and real
   VJPs, holding every inter-stage activation in an instrumented store
   — the measurement side of the memory-validation harness
   (``core.schedule.memory``), which cross-checks the simulator's
   per-device peak-activation claims against execution.

All are exercised by tests (subprocess, forced host device count) and
by the Fig. 9/10-style benchmarks; the production dry-run proves the
shard_map executor lowers on the (16, 16) mesh.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# 1. Circular pipeline executor (homogeneous stages, shard_map + ppermute)
# ---------------------------------------------------------------------------

def stack_stage_params(per_stage_params: Sequence[Any]):
    """List of per-stage pytrees (identical structure) -> stage-stacked
    pytree with leading S dim (shard P("stage") over it)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def pipeline_forward(mesh: Mesh, axis_name: str, stage_fn: Callable,
                     stage_params, microbatches, *, num_stages: int):
    """Run ``y_m = stage_{S-1}(... stage_0(x_m))`` for every microbatch.

    stage_fn(local_stage_params, x) -> y, with x/y of identical shape
    (the residual-stream contract all our blocks obey).
    stage_params: stage-stacked pytree (leading dim S).
    microbatches: [M, ...] (replicated; stage 0 slices its tick's mb).
    Returns [M, ...] outputs (gathered from the last stage).
    """
    M = microbatches.shape[0]
    S = num_stages
    ticks = M + S - 1

    def body(local_params, mbs):
        # local_params: leading dim 1 (this device's stage)
        lp = jax.tree.map(lambda a: a[0], local_params)
        sid = lax.axis_index(axis_name)
        x0 = jnp.zeros_like(mbs[0])
        out_buf = jnp.zeros_like(mbs)

        def tick(carry, t):
            x, out_buf = carry
            mb_in_idx = jnp.clip(t, 0, M - 1)
            fresh = lax.dynamic_index_in_dim(mbs, mb_in_idx, 0,
                                             keepdims=False)
            x = jnp.where(sid == 0, fresh, x)
            y = stage_fn(lp, x)
            # last stage writes finished microbatch t-(S-1) to the buffer
            done_idx = jnp.clip(t - (S - 1), 0, M - 1)
            write = (sid == S - 1) & (t >= S - 1)
            cur = lax.dynamic_index_in_dim(out_buf, done_idx, 0,
                                           keepdims=False)
            out_buf = lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(write, y, cur), done_idx, 0)
            perm = [(i, (i + 1) % S) for i in range(S)]
            x = lax.ppermute(y, axis_name, perm)
            return (x, out_buf), None

        (x, out_buf), _ = lax.scan(tick, (x0, out_buf), jnp.arange(ticks))
        # collect the filled buffer from the last stage on all devices
        out_all = lax.all_gather(out_buf, axis_name)        # [S, M, ...]
        return out_all[S - 1]

    spec_params = jax.tree.map(
        lambda a: P(axis_name, *([None] * (a.ndim - 1))), stage_params)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_params, P(*([None] * microbatches.ndim))),
        out_specs=P(*([None] * microbatches.ndim)),
        check_vma=False,
    )(stage_params, microbatches)


def pipeline_reference(stage_fn: Callable, stage_params, microbatches, *,
                       num_stages: int):
    """Oracle: same math, no pipeline."""
    def run_one(x):
        for s in range(num_stages):
            lp = jax.tree.map(lambda a: a[s], stage_params)
            x = stage_fn(lp, x)
        return x
    return jax.vmap(run_one)(microbatches)


# ---------------------------------------------------------------------------
# 2. Modality islands: encoders on disjoint device subsets
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Island:
    name: str
    devices: List[Any]               # jax devices owned by this island
    fn: Callable                     # jitted on this island's devices
    mesh: Optional[Mesh] = None


class ModalityIslands:
    """Place each encoder on its own device subset; the LLM on the rest.

    ``run(params, batch)`` dispatches every encoder island asynchronously
    (no dependency between them — the execution DAG guarantees it), then
    feeds their outputs to the LLM island. With JAX async dispatch the
    encoder computations overlap on real hardware; on CPU this verifies
    correctness + device placement.
    """

    def __init__(self, mllm, device_split: Dict[str, List[Any]]):
        from repro.models import mllm as M
        self.mllm = mllm
        self.islands: Dict[str, Island] = {}
        for name, enc in mllm.encoders.items():
            devs = device_split[name]
            sh = NamedSharding(Mesh(np.array(devs), ("d",)), P())

            def enc_fn(params, batch, enc=enc, sh=sh):
                params = jax.device_put(params, sh)
                return enc.forward(params, batch)

            self.islands[name] = Island(name, devs,
                                        jax.jit(enc_fn, static_argnums=()))
        devs = device_split["llm"]
        self.llm_sharding = NamedSharding(Mesh(np.array(devs), ("d",)), P())

        def llm_fn(params, merged, mllm=mllm):
            from repro.models import transformer as T
            return T.forward(params, mllm.llm_cfg, merged)

        self.llm_fn = jax.jit(llm_fn)

    def run(self, params, batch):
        # dispatch all encoder islands first — async, overlapping
        futures = {}
        for name, isl in self.islands.items():
            futures[name] = isl.fn(params["encoders"][name], batch)
        # cross-island transfer (the paper's encoder->LLM P2P send)
        futures = {name: jax.device_put(out, self.llm_sharding)
                   for name, out in futures.items()}
        merged = self.mllm.build_merge(
            jax.device_put(batch["text_tokens"], self.llm_sharding), futures)
        llm_p = jax.device_put(params["llm"], self.llm_sharding)
        return self.llm_fn(llm_p, merged)


# ---------------------------------------------------------------------------
# 3. Schedule-driven executor: replay a simulated item timeline with real
#    stage computations (the memory-validation target)
# ---------------------------------------------------------------------------

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


def _dict_schedule_name(plan: Dict[str, Any]) -> Optional[str]:
    """The schedule name a legacy plan dict carries, if any:
    ``auto_parallelize`` results keep it under "schedule",
    ``MultimodalParallelSpec.apply`` plans keep the sim dict there and
    the name under "schedule_name"."""
    name = plan.get("schedule")
    if not isinstance(name, str):
        name = plan.get("schedule_name")
    return name if isinstance(name, str) else None


def schedule_from_plan(plan: Any) -> str:
    """DEPRECATED shim — read ``plan.schedule.name`` off an
    ``MLLMParallelPlan`` instead. Accepts the typed plan, the two
    legacy dict flavors (``auto_parallelize`` result /
    ``MultimodalParallelSpec.apply``), or None (no plan -> classic
    1F1B). A dict that carries no recognizable schedule, or a name
    outside ``core.schedule.SCHEDULES``, raises ``ValueError`` — the
    silent-1F1B default masked genuinely malformed plans."""
    import warnings
    warnings.warn(
        "schedule_from_plan is deprecated; use "
        "repro.parallel.MLLMParallelPlan and plan.schedule.name",
        DeprecationWarning, stacklevel=2)
    from repro.core.schedule import SCHEDULES
    if plan is None:
        return "1f1b"
    if _is_typed_plan(plan):
        return plan.schedule.name
    if isinstance(plan, dict):
        name = _dict_schedule_name(plan)
        if name in SCHEDULES:
            return name
        raise ValueError(
            f"plan carries no recognizable schedule (got {name!r}, "
            f"valid: {SCHEDULES}); pass an MLLMParallelPlan, an "
            "auto_parallelize result, or a MultimodalParallelSpec."
            "apply dict")
    raise ValueError(f"not a plan: {type(plan).__name__!r}")


def virtual_chunks_from_plan(plan: Any) -> int:
    """DEPRECATED shim — read ``plan.schedule.virtual_chunks`` off an
    ``MLLMParallelPlan`` instead. Same accepted flavors as
    ``schedule_from_plan``; a recognized plan without the tag (both
    legacy flavors always carry it) defaults to 1, anything malformed
    raises ``ValueError``."""
    import warnings
    warnings.warn(
        "virtual_chunks_from_plan is deprecated; use "
        "repro.parallel.MLLMParallelPlan and "
        "plan.schedule.virtual_chunks",
        DeprecationWarning, stacklevel=2)
    from repro.core.schedule import SCHEDULES
    if plan is None:
        return 1
    if _is_typed_plan(plan):
        return plan.schedule.virtual_chunks
    if isinstance(plan, dict):
        v = plan.get("virtual_chunks")
        if isinstance(v, int) and v >= 1:
            return v
        if v is None and _dict_schedule_name(plan) in SCHEDULES:
            return 1
        raise ValueError(f"plan carries no usable virtual_chunks "
                         f"(got {v!r})")
    raise ValueError(f"not a plan: {type(plan).__name__!r}")


def split_devices(mllm, devices: Sequence[Any],
                  plan: Any = None) -> Dict[str, list]:
    """Assign device counts per module (default: 1 per encoder, rest to
    the LLM). ``plan`` is an ``MLLMParallelPlan`` (the typed API), a
    plain {encoder_name: count} dict, or the legacy result dict of
    ``core.pipeline.auto_parallelize``, whose per-encoder stage counts
    are matched by the "encoder_names" it carries. The winning schedule
    travels on the typed plan (``plan.schedule``); this dict stays
    purely {module: device list}."""
    devices = list(devices)
    if _is_typed_plan(plan):
        plan = plan.stage_counts_by_name()
    elif plan and "encoder_stages" in plan:   # auto_parallelize result
        names = plan.get("encoder_names") or sorted(mllm.encoders)
        plan = dict(zip(names, plan["encoder_stages"]))
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
