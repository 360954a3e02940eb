"""SPMD schedule executor: run a simulated F/B/W timeline on a real
device mesh under ``shard_map``.

``core.modality_parallel.execute_schedule`` replays a schedule's item
timeline sequentially in one process — real stage computations, real
VJPs, an instrumented activation store — but never crosses a device
boundary. This module is the distributed counterpart: the same
timeline, compiled to a static SPMD program and executed under
``shard_map`` on a named mesh, with every stage handoff (forward
activation, backward cotangent) carried between devices at a wave
boundary.

Compilation (``compile_spmd_program``) turns the timeline into
**waves**: a wave holds at most one work item per device (devices
whose next item is not yet dependency-ready sit the wave out — that
is the pipeline bubble, now visible as an idle branch), and each wave
boundary carries the activations/cotangents the wave just produced as
one or more comm **rounds** (a round is a partial permutation:
distinct sources, distinct destinations; fan-in DAGs that route two
encoder outputs to the same LLM device in one boundary simply take two
rounds). The compiled program is plain data — ``repro.analysis.
schedlint.lint_spmd_program`` statically checks the *emitted* rounds
(freshness, delivery-before-use, permutation validity) rather than the
timeline model.

Execution (``run_schedule_spmd`` / ``build_spmd_runner``) keeps a
fixed-shape local state per device — an ``[L, M]``-slot activation
store with a boolean occupancy mask (the *measured* container, exactly
like ``execute_schedule``'s dict store), an inbox accumulating fan-in
partial sums, a cotangent accumulator for fan-out stages, W-residual
slots for deferred weight-grad passes — and steps through the waves
with one rolled loop: a ``lax.fori_loop`` over a compacted
instruction table dispatching one ``lax.switch`` over *distinct*
``(kind, stage)`` branches, so compile time scales with the number of
distinct instructions rather than timeline length; each round is one
``all_gather`` from which every receiver takes its source's buffer.
The switch carries one microbatch's slices, never the stores (a
conditional copies every large operand it is handed): the wave reads
its slots before it and updates the stores in place after it.
``execute_schedule`` is the reference the tests hold it to, schedule
by schedule. Stage fns may be real-model per-stage callables
(``models.stages.build_mllm_stages`` — heterogeneous params travel as
a replicated list; only the leaves a trainable stage's cotangent
reaches hold, accumulate and psum-reduce a weight gradient, the rest
come back as zeros) or a single homogeneous callable. Each work item
runs under ``jax.named_scope`` ``stage{s}`` > ``F``/``B``/``W``, the
store reads and updates under ``store``, each
wave boundary's comm rounds under ``handoff`` and the closing
reductions under ``pipeline_reduce``. Loss and outputs are
``psum``-reduced over the pipeline axis; per-item occupancy is written
into a trace buffer and reassembled host-side into the same
``activation_trace`` format ``execute_schedule`` returns, so
``core.schedule.memory.validate_schedule_memory`` (and
``MemoryModelMismatch.first_divergence``) work unchanged on the
distributed path.

The mesh may carry extra axes (``cp``, ``dp``): every spec here names
only the pipeline axis, so the program replicates over the others and
composes with ``repro.training.steps.make_cp_train_step`` on a single
``("pp", "cp")`` (or ``("pp", "cp", "dp")``) mesh — one plan JSON
drives PP x CP x DP end to end.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend.core import Literal
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.schedule.graph import PipelineGraph
from repro.core.schedule.simulator import Item, item_id


# ---------------------------------------------------------------------------
# Compiled program data model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Transfer:
    """One cross-device handoff: the value item (kind', src_stage, m)
    just produced, shipped src_dev -> dst_dev for stage ``dst_stage``.
    ``kind`` is "fwd" (activation, F -> consumer F) or "bwd"
    (cotangent, B -> predecessor B)."""
    kind: str
    src_dev: int
    dst_dev: int
    src_stage: int
    dst_stage: int
    microbatch: int


@dataclasses.dataclass
class CommRound:
    """One comm round at a wave boundary, run as one ``all_gather``.
    Sources and destinations are distinct within a round (a partial
    permutation: each device sends and receives at most one buffer)."""
    kind: str                        # "fwd" | "bwd"
    transfers: List[Transfer]

    @property
    def pairs(self) -> List[Tuple[int, int]]:
        return [(t.src_dev, t.dst_dev) for t in self.transfers]


@dataclasses.dataclass
class Wave:
    """At most one work item per device, then the boundary's comm
    rounds. ``compute`` maps device -> (item_index, kind, stage,
    local_chunk, microbatch)."""
    compute: Dict[int, Tuple[int, str, int, int, int]]
    rounds: List[CommRound]


@dataclasses.dataclass
class SPMDProgram:
    """A timeline compiled for ``shard_map`` execution (plain data —
    what ``schedlint.lint_spmd_program`` validates)."""
    graph: PipelineGraph
    items: List[Item]
    device_of: List[int]
    num_devices: int
    hosted: List[List[int]]          # device -> hosted stages (asc)
    chunk_of: List[int]              # stage -> local chunk slot
    max_chunks: int                  # L: store slots per device
    waves: List[Wave]
    has_w_items: bool

    def counts(self) -> Dict[str, int]:
        return {"waves": len(self.waves),
                "rounds": sum(len(w.rounds) for w in self.waves),
                "items": len(self.items),
                "devices": self.num_devices}


# ---------------------------------------------------------------------------
# Compilation: timeline -> waves + comm rounds
# ---------------------------------------------------------------------------

def compile_spmd_program(graph: PipelineGraph,
                         sim: Dict[str, Any]) -> SPMDProgram:
    """Compile a simulation dict (``items`` + ``device_of``) into an
    :class:`SPMDProgram`.

    Wave placement is the earliest level consistent with (a) one item
    per device per wave and (b) every dependency — producer F for a
    consumer F, consumer B (and own F) for a producer B, own B for a W
    — sitting in a strictly earlier wave, so its boundary transfer has
    already been delivered. Items are walked in timeline order, which
    the simulator guarantees is dependency-respecting; a malformed
    timeline (tested deliberately) still compiles and is caught by
    ``lint_spmd_program`` or by the executor's measured trace.
    """
    items = list(sim["items"])
    device_of = list(sim["device_of"])
    S = len(graph.stages)
    D = int(sim["num_devices"])
    preds, succs = graph.preds, graph.succs

    hosted = [[s for s in range(S) if device_of[s] == d] for d in range(D)]
    chunk_of = [hosted[device_of[s]].index(s) for s in range(S)]
    L = max(1, max((len(h) for h in hosted), default=1))

    # a stage that needs a cotangent must get one: from being a sink,
    # or from at least one successor that computes input grads — the
    # same invariant execute_schedule asserts per item, checked once
    for s in range(S):
        st = graph.stages[s]
        if st.bwd_b <= 0 and st.bwd_w <= 0:
            continue
        if succs[s] and not any(graph.stages[q].bwd_b > 0
                                for q in succs[s]):
            raise ValueError(
                f"stage {s} has backward work (bwd_b={st.bwd_b}, "
                f"bwd_w={st.bwd_w}) but no successor produces its "
                f"cotangent (all succs have bwd_b == 0)")

    waves: List[Wave] = []
    placed: Dict[Tuple[str, int, int], int] = {}
    last_wave = [-1] * D
    has_w = any(it[3] == "W" for it in items)

    def wave_at(w: int) -> Wave:
        while len(waves) <= w:
            waves.append(Wave(compute={}, rounds=[]))
        return waves[w]

    def add_transfer(w: int, t: Transfer) -> None:
        for r in wave_at(w).rounds:
            if r.kind != t.kind:
                continue
            if t.src_dev in (x.src_dev for x in r.transfers):
                continue
            if t.dst_dev in (x.dst_dev for x in r.transfers):
                continue
            r.transfers.append(t)
            return
        wave_at(w).rounds.append(CommRound(kind=t.kind, transfers=[t]))

    for i, it in enumerate(items):
        _s0, _e0, dev, kind, s, m = it
        if kind == "F":
            deps = [("F", p, m) for p in preds[s]]
        elif kind == "B":
            deps = [("F", s, m)] + [("B", q, m) for q in succs[s]]
        else:
            deps = [("B", s, m)]
        w = 1 + max([last_wave[dev]]
                    + [placed.get(k, -1) for k in deps])
        wave_at(w).compute[dev] = (i, kind, s, chunk_of[s], m)
        placed[(kind, s, m)] = w
        last_wave[dev] = w
        if kind == "F":
            for q in succs[s]:
                if device_of[q] != dev:
                    add_transfer(w, Transfer("fwd", dev, device_of[q],
                                             s, q, m))
        elif kind == "B" and graph.stages[s].bwd_b > 0:
            for p in preds[s]:
                if device_of[p] != dev:
                    add_transfer(w, Transfer("bwd", dev, device_of[p],
                                             s, p, m))

    return SPMDProgram(graph=graph, items=items, device_of=device_of,
                       num_devices=D, hosted=hosted, chunk_of=chunk_of,
                       max_chunks=L, waves=waves, has_w_items=has_w)


# ---------------------------------------------------------------------------
# Execution under shard_map
# ---------------------------------------------------------------------------

def default_mesh(num_devices: int, axis_name: str = "pp",
                 devices: Optional[Sequence[Any]] = None) -> Mesh:
    """A 1-D mesh over the first ``num_devices`` host devices. Raises
    with the XLA_FLAGS hint when the process has too few."""
    devs = list(devices if devices is not None else jax.devices())
    if len(devs) < num_devices:
        hint = "run on a larger mesh"
        if devs and devs[0].platform == "cpu":
            hint = (f"set XLA_FLAGS=--xla_force_host_platform_"
                    f"device_count={num_devices} (before importing jax) "
                    f"or " + hint)
        raise ValueError(
            f"SPMD program needs {num_devices} devices but the process "
            f"has {len(devs)}; {hint}")
    return Mesh(np.array(devs[:num_devices]), (axis_name,))


def mesh_from_plan(plan: Any, mllm: Any, num_devices: int,
                   axis_name: str = "pp") -> Mesh:
    """Build the pipeline mesh for a plan using ``split_devices`` for
    stage -> device placement: physical devices are handed out per
    module (encoders in sorted order, then the LLM), and the mesh takes
    them in that order — so mesh position d is exactly the device the
    plan's stage/device split assigned to pipeline rank d."""
    from repro.core.modality_parallel import split_devices
    split = split_devices(mllm, jax.devices(), plan)
    flat = [d for name in sorted(mllm.encoders) for d in split[name]]
    flat += list(split["llm"])
    return default_mesh(num_devices, axis_name, devices=flat)


def toy_stage_model(num_stages: int, d_model: int, seed: int = 0):
    """The residual toy stage the memory-validation harness uses
    (``x + tanh(x W)``, one weight per stage) — same seeding, so SPMD
    runs are directly comparable against ``validate_schedule_memory``
    and ``execute_schedule`` fixtures."""
    key = jax.random.PRNGKey(seed)
    stage_params = {"w": jax.random.normal(
        key, (num_stages, d_model, d_model)) * 0.1}

    def stage_fn(lp, x):
        return x + jnp.tanh(x @ lp["w"])

    return stage_fn, stage_params


def _stack_local(program: SPMDProgram, stage_params: Any) -> Any:
    """Stage-stacked [S, ...] params -> device/chunk-stacked
    [D, L, ...] (devices hosting fewer than L chunks get zero pads that
    no branch ever touches)."""
    def one(a):
        rows = []
        for d in range(program.num_devices):
            row = [a[s] for s in program.hosted[d]]
            row += [jnp.zeros_like(a[0])] * (program.max_chunks - len(row))
            rows.append(jnp.stack(row))
        return jnp.stack(rows)
    return jax.tree.map(one, stage_params)


def _unstack_grads(program: SPMDProgram, grads_dl: Any) -> Any:
    """[D, L, ...] per-device grads back to stage-stacked [S, ...]."""
    S = len(program.graph.stages)

    def one(a):
        return jnp.stack([a[program.device_of[s], program.chunk_of[s]]
                          for s in range(S)])
    return jax.tree.map(one, grads_dl)


def _rolled_tables(prog: SPMDProgram):
    """Compact the wave timeline into per-wave instruction tables.

    Distinct instructions are ``(kind, stage)`` pairs — the device and
    chunk are static per stage, the microbatch and item index are
    traced table lookups — so the rolled dispatch loop traces each
    stage branch ONCE regardless of timeline length."""
    D, W = prog.num_devices, len(prog.waves)
    keys: List[Tuple[str, int]] = []
    key_of: Dict[Tuple[str, int], int] = {}
    instr = np.zeros((W, D), np.int32)       # 0 = idle
    m_tab = np.zeros((W, D), np.int32)
    item_tab = np.zeros((W, D), np.int32)
    for w, wave in enumerate(prog.waves):
        for d, (i, kind, s, _c, m) in wave.compute.items():
            k = (kind, s)
            if k not in key_of:
                key_of[k] = len(keys) + 1
                keys.append(k)
            instr[w, d] = key_of[k]
            m_tab[w, d] = m
            item_tab[w, d] = i
    R = max((len(wv.rounds) for wv in prog.waves), default=0)
    comm = None
    if R:
        on = np.zeros((W, R, D), bool)
        src = np.zeros((W, R, D), np.int32)
        c_tab = np.zeros((W, R, D), np.int32)
        m2 = np.zeros((W, R, D), np.int32)
        isb = np.zeros((W, R), bool)
        for w, wave in enumerate(prog.waves):
            for r, rnd in enumerate(wave.rounds):
                isb[w, r] = rnd.kind == "bwd"
                for t in rnd.transfers:
                    on[w, r, t.dst_dev] = True
                    src[w, r, t.dst_dev] = t.src_dev
                    c_tab[w, r, t.dst_dev] = prog.chunk_of[t.dst_stage]
                    m2[w, r, t.dst_dev] = t.microbatch
        comm = (on, src, c_tab, m2, isb)
    return keys, instr, m_tab, item_tab, R, comm


def _store_tables(prog: SPMDProgram, keys: Sequence[Tuple[str, int]],
                  trainable: Sequence[bool]) -> Dict[str, np.ndarray]:
    """What each instruction does to the device's stores, indexed like
    the ``lax.switch`` branches (row 0 is the idle branch, which does
    nothing): its kind, its chunk, whether it reads the microbatch
    (source F), feeds the loss (sink F), hands ``y`` to the wave's fwd
    (``fy``) or bwd (``bg``) rounds, or parks its residuals for a W; and
    up to K local successors (F) or predecessors (B) whose inbox or
    cotangent slot takes ``y`` — fan-in plans put several on one
    device."""
    graph, dev_of, chunk_of = prog.graph, prog.device_of, prog.chunk_of

    def local(ss, s):
        return [chunk_of[q] for q in ss if dev_of[q] == dev_of[s]]
    to: List[List[int]] = [[]]
    t: Dict[str, List[Any]] = {n: [False] for n in (
        "F", "B", "W", "src", "sink", "fy", "bg", "park")}
    t["c"] = [0]
    for kind, s in keys:
        stg = graph.stages[s]
        prs, sucs = graph.preds[s], graph.succs[s]
        bwd_b = kind == "B" and stg.bwd_b > 0 and bool(prs)
        row = {"F": kind == "F", "B": kind == "B", "W": kind == "W",
               "src": kind == "F" and not prs,
               "sink": kind == "F" and not sucs,
               "fy": kind == "F" and bool(sucs), "bg": bwd_b,
               "park": (kind == "B" and trainable[s] and prog.has_w_items
                        and stg.bwd_w > 0),
               "c": chunk_of[s]}
        for n, v in row.items():
            t[n].append(v)
        to.append(local(sucs, s) if kind == "F" else
                  local(prs, s) if bwd_b else [])
    K = max(1, max(len(r) for r in to))
    out = {n: np.asarray(v, np.int32 if n == "c" else bool)
           for n, v in t.items()}
    out["to_c"] = np.asarray([r + [0] * (K - len(r)) for r in to],
                             np.int32)
    out["to_on"] = np.asarray([[k < len(r) for k in range(K)]
                               for r in to], bool)
    return out


def _grad_support(fn: Callable, lp: Any, x: Any, mb: Any) -> List[int]:
    """Indices of the leaves of ``lp`` (a stage's params) whose weight
    gradient can be nonzero. A VJP is linear in its cotangent, so a
    leaf whose gradient does not read the cotangent is identically zero:
    a subtree the stage fn holds under ``stop_gradient`` (a frozen
    encoder beside its trainable projector). Read off the VJP's jaxpr
    at the given shapes; nothing runs."""
    leaves, tdef = jax.tree.flatten(lp)

    def grads(leaves, x, mb, g):
        _, vjp = jax.vjp(lambda *ls: fn(tdef.unflatten(ls), x, mb),
                         *leaves)
        return vjp(g)

    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    y = jax.eval_shape(fn, lp, x, mb)
    jaxpr = jax.make_jaxpr(grads)(*jax.tree.map(
        shape, (leaves, x, mb, y))).jaxpr
    live = set(jaxpr.invars[-len(jax.tree.leaves(y)):])
    for eqn in jaxpr.eqns:
        if any(v in live for v in eqn.invars
               if not isinstance(v, Literal)):
            live.update(eqn.outvars)
    return [i for i, v in enumerate(jaxpr.outvars)
            if not isinstance(v, Literal) and v in live]


def _with_zeros(params: Any, held: Sequence[int], grads: Sequence[Any]):
    """``params``' tree with ``grads`` at the leaves ``held`` indexes
    and zeros everywhere else."""
    leaves, tdef = jax.tree.flatten(params)
    out = [jnp.zeros(a.shape, a.dtype) for a in leaves]
    for i, g in zip(held, grads):
        out[i] = g
    return tdef.unflatten(out)


def build_spmd_runner(stage_fn, graph: PipelineGraph,
                      sim: Dict[str, Any], *,
                      mesh: Optional[Mesh] = None,
                      axis_name: str = "pp",
                      microbatch_loss: Optional[Callable] = None,
                      program: Optional[SPMDProgram] = None,
                      jit: bool = True,
                      trainable: Optional[Sequence[bool]] = None
                      ) -> Callable:
    """Compile the schedule once and return
    ``runner(stage_params, microbatches) -> result dict`` with the same
    contract as ``execute_schedule`` (outputs, loss, param_grads,
    per-device peaks, activation_trace). The shard_map core is jitted
    (cached across calls) — this is what ``make_spmd_train_step``
    builds per training run.

    ``stage_fn`` follows ``execute_schedule``'s contract: one callable
    or a per-stage list, 2-arg ``fn(lp, x)`` or 3-arg
    ``fn(lp, x, microbatch)`` (``models.stages.StageBundle.stage_fns``).
    ``stage_params`` may be stage-stacked (homogeneous stages, sharded
    ``[D, L, ...]`` per device) or a list of per-stage trees
    (heterogeneous real-model stages; replicated, the grads of the
    leaves that can have one psum-reduced, zeros elsewhere —
    ``param_grads`` then comes back as a matching list). ``trainable``
    has ``execute_schedule``'s semantics (stages that must produce
    weight grads even with ``bwd_w == 0``).

    The waves run as a ``lax.fori_loop`` indexing a compacted
    instruction table (``_rolled_tables``): one ``lax.switch`` over the
    *distinct* ``(kind, stage)`` branches, then the wave's comm rounds,
    each an ``all_gather`` of the producers' buffers from which every
    receiver picks its source by table lookup. Compile time scales with
    the number of distinct instructions, not with timeline length. The
    branches take this device's slots for the wave and return a result
    of one shape; the per-item store updates are ``execute_schedule``'s,
    in its order, applied in place after the switch (``_store_tables``).
    """
    from repro.core.modality_parallel import normalize_stage_fns
    prog = program if program is not None else \
        compile_spmd_program(graph, sim)
    if mesh is None:
        mesh = default_mesh(prog.num_devices, axis_name)
    if mesh.shape[axis_name] != prog.num_devices:
        raise ValueError(
            f"mesh axis {axis_name!r} has {mesh.shape[axis_name]} "
            f"devices but the program was compiled for "
            f"{prog.num_devices}")
    loss_fn = microbatch_loss or (lambda y: jnp.mean(y ** 2))
    S = len(graph.stages)
    D, L = prog.num_devices, prog.max_chunks
    device_of, chunk_of = prog.device_of, prog.chunk_of
    preds, succs = graph.preds, graph.succs
    n_items = len(prog.items)
    has_w = prog.has_w_items
    fns = normalize_stage_fns(stage_fn, S)
    if trainable is None:
        trainable = [graph.stages[s].bwd_w > 0 for s in range(S)]
    trainable = [bool(t) for t in trainable]
    for s in range(S):
        # same reachability invariant compile_spmd_program checks for
        # bwd-costed stages, extended to the trainable override: a
        # trainable stage must receive a cotangent from somewhere
        if trainable[s] and succs[s] and not any(
                graph.stages[q].bwd_b > 0 for q in succs[s]):
            raise ValueError(
                f"stage {s} is trainable but no successor produces its "
                f"cotangent (all succs have bwd_b == 0)")

    def core(local_params, mbs, hetero=False):
        M = mbs.shape[0]
        xshape, xdtype = mbs.shape[1:], mbs.dtype
        loss_dtype = jax.eval_shape(
            loss_fn, jax.ShapeDtypeStruct(xshape, xdtype)).dtype
        if hetero:
            # the leaves that take weight grads: of trainable stages only,
            # and only those the cotangent reaches, so a frozen stage (or
            # a frozen tower beside its projector) holds, accumulates and
            # reduces no gradient buffer at all
            xs = jax.ShapeDtypeStruct(xshape, xdtype)
            held = [_grad_support(fns[s], local_params[s], xs, xs)
                    if trainable[s] else [] for s in range(S)]

        def body(local_params, mbs):
            if hetero:
                params_t = local_params          # tuple of stage trees
            else:
                lp = jax.tree.map(lambda a: a[0], local_params)  # [L,...]
            idx = lax.axis_index(axis_name)
            if hetero:
                zgrads = tuple(
                    tuple(jnp.zeros_like(jax.tree.leaves(params_t[s])[i])
                          for i in held[s]) for s in range(S))
            else:
                zgrads = jax.tree.map(jnp.zeros_like, lp)
            state = {
                "x": jnp.zeros((L, M) + xshape, xdtype),
                "used": jnp.zeros((L, M), jnp.bool_),
                "inbox": jnp.zeros((L, M) + xshape, xdtype),
                "cot": jnp.zeros((L, M) + xshape, xdtype),
                "grads": zgrads,
                "loss": jnp.zeros((), loss_dtype),
                "out": jnp.zeros((M,) + xshape, xdtype),
                "fy": jnp.zeros(xshape, xdtype),
                "bg": jnp.zeros(xshape, xdtype),
                "occ": jnp.zeros((n_items,), jnp.int32),
                "wocc": jnp.zeros((n_items,), jnp.int32),
            }
            if has_w:
                state["wx"] = jnp.zeros((L, M) + xshape, xdtype)
                state["wg"] = jnp.zeros((L, M) + xshape, xdtype)
                state["wused"] = jnp.zeros((L, M), jnp.bool_)

            def zeros_out():
                """The result every branch returns: ``y`` (a stage
                output or input cotangent), the loss and weight-grad
                increments; zeros where the branch produces none (the
                idle branch, all of it)."""
                if hetero:
                    dg = tuple(tuple(jnp.zeros_like(g) for g in gs)
                               for gs in zgrads)
                else:
                    dg = jax.tree.map(lambda a: jnp.zeros_like(a[0]), lp)
                return (jnp.zeros(xshape, xdtype),
                        jnp.zeros((), loss_dtype), dg)

            def idle(a, b, m):
                return zeros_out()

            def weight_grads(s, lpc, x, mb, g):
                """The stage's weight VJP: over the held leaves only
                (hetero), the rest closed over as constants."""
                if not hetero:
                    _, vjp_p = jax.vjp(lambda pw: fns[s](pw, x, mb), lpc)
                    return vjp_p(g)[0]
                leaves, tdef = jax.tree.flatten(lpc)

                def f(*hl):
                    ls = list(leaves)
                    for i, h in zip(held[s], hl):
                        ls[i] = h
                    return fns[s](tdef.unflatten(ls), x, mb)
                _, vjp_p = jax.vjp(f, *[leaves[i] for i in held[s]])
                return vjp_p(g)

            def make_branch(kind, s, park):
                # a branch sees one microbatch's slices, never the
                # stores: a conditional copies every large operand it
                # is handed. ``a`` is F's input, or B's and W's stored
                # input; ``b`` is B's and W's cotangent. Each operand
                # and result is live while the branch runs, so the
                # microbatch is read in here and the sink's loss
                # cotangent is made after the switch
                c = chunk_of[s]
                stg = graph.stages[s]
                prs, sucs = preds[s], succs[s]

                @jax.named_scope(f"stage{s}")
                @jax.named_scope(kind)
                def br(a, b, m):
                    y, dloss, dg = zeros_out()
                    mb = mbs[m]
                    if hetero:
                        lpc = params_t[s]
                    else:
                        lpc = jax.tree.map(lambda t: t[c], lp)
                    glued = trainable[s] and (kind == "W" or (
                        kind == "B" and not park))
                    if kind == "F":
                        y = fns[s](lpc, a, mb)
                        if not sucs:             # sink: the loss
                            dloss = loss_fn(y)
                    elif kind == "B" and stg.bwd_b > 0 and prs:
                        _, vjp_x = jax.vjp(
                            lambda xx: fns[s](lpc, xx, mb), a)
                        (y,) = vjp_x(b)
                    if glued:                    # weight grads now
                        gp = weight_grads(s, lpc, a, mb, b)
                        if hetero:
                            dg = tuple(tuple(gp) if q == s else dg[q]
                                       for q in range(S))
                        else:
                            dg = gp
                    return y, dloss, dg
                return br

            keys, instr, m_tab, item_tab, R, comm = _rolled_tables(prog)
            ops = _store_tables(prog, keys, trainable)
            branches = [idle] + [make_branch(kind, s, bool(park))
                                 for (kind, s), park in
                                 zip(keys, ops["park"][1:])]
            tab = {n: jnp.asarray(v) for n, v in ops.items()}
            instr_a = jnp.asarray(instr)
            m_a = jnp.asarray(m_tab)
            item_a = jnp.asarray(item_tab)
            if R:
                on_t, src_t, c_t, m2_t, isb_t = comm
                on_a, src_a = jnp.asarray(on_t), jnp.asarray(src_t)
                c_a, m2_a = jnp.asarray(c_t), jnp.asarray(m2_t)
                isb_a = jnp.asarray(isb_t)

            @jax.named_scope("handoff")
            def comm_rounds(w, st):
                def round_body(r, st):
                    st = dict(st)
                    isb = isb_a[w, r]
                    buf = jnp.where(isb, st["bg"], st["fy"])
                    gathered = lax.all_gather(buf, axis_name)
                    recv = gathered[src_a[w, r, idx]]
                    onv = on_a[w, r, idx]
                    cc, mm = c_a[w, r, idx], m2_a[w, r, idx]
                    delta = jnp.where(onv, recv, jnp.zeros_like(recv))
                    zero = jnp.zeros_like(delta)
                    st["inbox"] = st["inbox"].at[cc, mm].add(
                        jnp.where(isb, zero, delta))
                    st["cot"] = st["cot"].at[cc, mm].add(
                        jnp.where(isb, delta, zero))
                    return st
                return lax.fori_loop(0, R, round_body, st)

            def wave_body(w, st):
                st = dict(st)
                k, m, i = instr_a[w, idx], m_a[w, idx], item_a[w, idx]
                op = {n: v[k] for n, v in tab.items()}
                c = op["c"]

                def put(name, at, on, v):
                    # one slot of a store, in place: ``v`` where ``on``
                    st[name] = st[name].at[at].set(
                        jnp.where(on, v, st[name][at]))

                with jax.named_scope("store"):
                    a = jnp.where(op["F"],
                                  jnp.where(op["src"], mbs[m],
                                            st["inbox"][c, m]),
                                  st["x"][c, m])
                    b = st["cot"][c, m]
                    if has_w:
                        a = jnp.where(op["W"], st["wx"][c, m], a)
                        b = jnp.where(op["W"], st["wg"][c, m], b)
                y, dloss, dg = lax.switch(k, branches, a, b, m)
                # execute_schedule's per-item updates, in its order
                zy = jnp.zeros_like(y)
                with jax.named_scope("store"):
                    put("x", (c, m), op["F"], a)
                    put("used", (c, m), op["F"] | op["B"], op["F"])
                    st["out"] = st["out"].at[m].add(
                        jnp.where(op["sink"], y, zy))
                    st["loss"] = st["loss"] + dloss
                    gy = jnp.where(op["sink"], jax.grad(loss_fn)(y), zy)
                    st["cot"] = st["cot"].at[c, m].set(
                        jnp.where(op["B"], zy, st["cot"][c, m] + gy))
                    st["fy"] = jnp.where(op["fy"], y, st["fy"])
                    st["bg"] = jnp.where(op["bg"], y, st["bg"])
                    for j in range(ops["to_c"].shape[1]):
                        on, cj = op["to_on"][j], op["to_c"][j]
                        st["inbox"] = st["inbox"].at[cj, m].add(
                            jnp.where(on & op["F"], y, zy))
                        st["cot"] = st["cot"].at[cj, m].add(
                            jnp.where(on & op["B"], y, zy))
                    if has_w:
                        put("wx", (c, m), op["park"], a)
                        put("wg", (c, m), op["park"], b)
                        put("wused", (c, m), op["park"] | op["W"],
                            op["park"])
                if hetero:
                    st["grads"] = jax.tree.map(jnp.add, st["grads"], dg)
                else:
                    st["grads"] = jax.tree.map(
                        lambda G, d: G.at[c].add(d), st["grads"], dg)
                live = op["F"] | op["B"] | op["W"]
                put("occ", i, live, jnp.sum(st["used"]).astype(jnp.int32))
                if has_w:
                    put("wocc", i, live,
                        jnp.sum(st["wused"]).astype(jnp.int32))
                if R:
                    st = comm_rounds(w, st)
                return st

            state = lax.fori_loop(0, len(prog.waves), wave_body, state)

            with jax.named_scope("pipeline_reduce"):
                outputs = lax.psum(state["out"], axis_name)
                loss = lax.psum(state["loss"], axis_name)
                if hetero:
                    grads = lax.psum(state["grads"], axis_name)
                else:
                    grads = jax.tree.map(lambda a: a[None], state["grads"])
            return (outputs, loss, grads,
                    state["occ"][None], state["wocc"][None])

        if hetero:
            spec_p = jax.tree.map(
                lambda a: P(*([None] * a.ndim)), local_params)
            grads_spec = tuple(tuple(P() for _ in h) for h in held)
        else:
            spec_p = jax.tree.map(
                lambda a: P(axis_name, *([None] * (a.ndim - 1))),
                local_params)
            grads_spec = spec_p
        outputs, loss, grads, occ, wocc = jax.shard_map(
            body, mesh=mesh,
            in_specs=(spec_p, P(*([None] * mbs.ndim))),
            out_specs=(P(*([None] * mbs.ndim)), P(), grads_spec,
                       P(axis_name, None), P(axis_name, None)),
            check_vma=False,
        )(local_params, mbs)
        if hetero:
            # every stage's full tree again: zeros where no grad is held
            grads = tuple(_with_zeros(p, h, g)
                          for p, h, g in zip(local_params, held, grads))
        return outputs, loss, grads, occ, wocc

    core_fn = jax.jit(core, static_argnames=("hetero",)) if jit else core

    def prepare(stage_params):
        """Raw stage params -> the representation ``core`` consumes
        (list of trees pass through; stacked trees go device-local)."""
        if isinstance(stage_params, (list, tuple)):
            return tuple(stage_params)
        return _stack_local(prog, stage_params)

    def finish_grads(grads_repr):
        """``core``'s grads output -> ``execute_schedule``'s
        ``param_grads`` shape (list for hetero, stage-stacked else)."""
        if isinstance(grads_repr, tuple):
            return list(grads_repr)
        return _unstack_grads(prog, grads_repr)

    def runner(stage_params, microbatches):
        hetero = isinstance(stage_params, (list, tuple))
        local = prepare(stage_params)
        outputs, loss, grads_repr, occ, wocc = core_fn(
            local, microbatches, hetero=hetero)
        occ_np = np.asarray(occ)
        wocc_np = np.asarray(wocc)
        trace = [(item_id(it), it[2], int(occ_np[it[2], i]))
                 for i, it in enumerate(prog.items)]
        peak = [0] * D
        w_peak = [0] * D
        for i, it in enumerate(prog.items):
            dev = it[2]
            peak[dev] = max(peak[dev], int(occ_np[dev, i]))
            w_peak[dev] = max(w_peak[dev], int(wocc_np[dev, i]))
        nbytes = int(np.prod(microbatches.shape[1:])
                     * microbatches.dtype.itemsize)
        return {
            "outputs": outputs,
            "loss": loss,
            "param_grads": finish_grads(grads_repr),
            "peak_activations_per_device": peak,
            "peak_w_residuals_per_device": w_peak,
            "activation_trace": trace,
            "activation_nbytes": nbytes,
            "program": prog,
        }

    # expose the pieces make_resilient_train_step's value_and_grad hook
    # needs to keep everything inside one outer jit
    runner.program = prog
    runner.core = core_fn
    runner.prepare = prepare
    runner.finish_grads = finish_grads
    return runner


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def run_schedule_spmd(*args: Any, mesh: Optional[Mesh] = None,
                      axis_name: str = "pp",
                      microbatch_loss: Optional[Callable] = None,
                      program: Optional[SPMDProgram] = None,
                      stage_fn: Any = None,
                      stage_params: Any = None,
                      trainable: Optional[Sequence[bool]] = None,
                      seed: int = 0) -> Dict[str, Any]:
    """Execute a schedule timeline distributed under ``shard_map``.

    Two call forms, mirroring ``execute_schedule``'s contract:

    * ``run_schedule_spmd(stage_fn, stage_params, microbatches, graph,
      sim)`` — the core form: explicit stage callables and a simulation
      dict (``items`` + ``device_of``).
    * ``run_schedule_spmd(plan, mllm, microbatches)`` — the plan form:
      an :class:`~repro.parallel.plan.MLLMParallelPlan` is applied to
      ``mllm`` in SPMD mode (``plan.apply(mllm, mode="spmd")``), the
      mesh is derived from ``split_devices`` placement. ``stage_fn``
      selects what runs the timeline: real stage callables (e.g.
      ``models.stages`` bundle fns, with matching ``stage_params``), or
      the explicit sentinel ``stage_fn="toy"`` for the toy residual
      stage model sized to the microbatches' feature dim (the model the
      memory-validation harness uses — module profiles are cost models,
      not callables). Passing ``stage_fn=None`` still falls back to the
      toy model but warns: real-model callers must opt in explicitly so
      they cannot accidentally verify the wrong model.

    Returns the ``execute_schedule`` result dict (outputs, loss,
    param_grads, per-device peaks, activation_trace) plus the compiled
    ``program``.
    """
    from repro.core.modality_parallel import _is_typed_plan
    if _is_typed_plan(args[0]):
        plan, mllm, microbatches = args
        executor = plan.apply(mllm, mode="spmd")
        graph = executor["sim_graph"]
        sim = executor["schedule"]
        prog = program if program is not None \
            else executor.get("spmd_program")
        if mesh is None:
            mesh = mesh_from_plan(plan, mllm, int(sim["num_devices"]),
                                  axis_name)
        if stage_fn is None or stage_fn == "toy":
            if stage_fn is None:
                import warnings
                warnings.warn(
                    "run_schedule_spmd(plan, mllm, ...) got no "
                    "stage_fn and will run the TOY stage model, not "
                    "the MLLM; pass stage_fn=\"toy\" to silence this, "
                    "or real stage fns (models.stages.build_mllm_"
                    "stages) to execute the model", stacklevel=2)
            stage_fn, stage_params = toy_stage_model(
                len(graph.stages), int(microbatches.shape[-1]),
                seed=seed)
    else:
        stage_fn, stage_params, microbatches, graph, sim = args
        prog = program
    runner = build_spmd_runner(stage_fn, graph, sim, mesh=mesh,
                               axis_name=axis_name,
                               microbatch_loss=microbatch_loss,
                               program=prog, trainable=trainable)
    return runner(stage_params, microbatches)


def spmd_parity_report(executor: Dict[str, Any], *, d_model: int = 16,
                       seq: int = 4, seed: int = 0,
                       mesh: Optional[Mesh] = None,
                       axis_name: str = "pp") -> Dict[str, Any]:
    """Run one executor contract's timeline on BOTH executors — the
    distributed shard_map program and the sequential replay — with the
    toy residual stage model, and report the parity: losses, the max
    elementwise grad difference, whether the measured per-device peaks
    and activation traces agree. The cheap end-to-end proof that a
    plan's compiled SPMD program computes what its timeline claims
    (the memory-validation harness and tests use it; ``launch/train
    --spmd`` itself trains the real partitioned model)."""
    from repro.core.modality_parallel import execute_schedule
    graph = executor["sim_graph"]
    sim = executor["schedule"]
    prog = executor.get("spmd_program")
    stage_fn, stage_params = toy_stage_model(
        len(graph.stages), d_model, seed=seed)
    M = max(int(it[5]) for it in sim["items"]) + 1
    microbatches = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), 1),
        (M, 1, seq, d_model))
    got = run_schedule_spmd(stage_fn, stage_params, microbatches,
                            graph, sim, mesh=mesh, axis_name=axis_name,
                            program=prog)
    ref = execute_schedule(stage_fn, stage_params, microbatches,
                           graph, sim)
    diffs = jax.tree.leaves(jax.tree.map(
        lambda a, b: jnp.max(jnp.abs(a - b)),
        got["param_grads"], ref["param_grads"]))
    return {
        "loss_spmd": float(got["loss"]),
        "loss_replay": float(ref["loss"]),
        "max_grad_diff": max(float(d) for d in diffs),
        "peaks_match": (got["peak_activations_per_device"]
                        == ref["peak_activations_per_device"]),
        "trace_match": (got["activation_trace"]
                        == ref["activation_trace"]),
        "program": got["program"].counts(),
    }


def reference_dag_loss(stage_fn: Callable, stage_params: Any,
                       microbatches: Any, graph: PipelineGraph, *,
                       microbatch_loss: Optional[Callable] = None
                       ) -> Tuple[Any, Any]:
    """Single-device autodiff oracle for any stage DAG: compose the
    stages in topological order (sources read the microbatch, fan-in
    sums predecessor outputs, the loss sums over sinks), take
    ``jax.value_and_grad`` — the ``make_train_step``-equivalent both
    executors must match. Returns (loss, stage-stacked grads)."""
    loss_fn = microbatch_loss or (lambda y: jnp.mean(y ** 2))
    S = len(graph.stages)
    preds, succs = graph.preds, graph.succs

    def total_loss(params):
        loss = jnp.zeros((), jnp.float32)
        for m in range(microbatches.shape[0]):
            ys: Dict[int, Any] = {}
            for s in range(S):                   # stages are topo-ordered
                lp = jax.tree.map(lambda a: a[s], params)
                x = microbatches[m] if not preds[s] else \
                    sum(ys[p] for p in preds[s])
                ys[s] = stage_fn(lp, x)
            for s in range(S):
                if not succs[s]:
                    loss = loss + loss_fn(ys[s])
        return loss

    # stop_gradient semantics of frozen stages: the schedule encodes
    # them as bwd_w == 0, which the executors honor by never running a
    # weight-grad VJP; the oracle masks the autodiff grads to match
    loss, grads = jax.value_and_grad(total_loss)(stage_params)
    mask = jnp.asarray([graph.stages[s].bwd_w > 0 for s in range(S)])
    grads = jax.tree.map(
        lambda g: jnp.where(
            mask.reshape((S,) + (1,) * (g.ndim - 1)), g,
            jnp.zeros_like(g)), grads)
    return loss, grads
