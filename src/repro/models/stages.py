"""Typed StageFn contract: partition a real MLLM into per-stage callables.

The pipeline executors (the sequential replay in
``core.modality_parallel.execute_schedule`` and the distributed
``parallel.spmd.build_spmd_runner``) move ONE activation tensor per
stage handoff.  A real MLLM has heterogeneous stage boundaries — an
encoder's hidden state is [B, T_m, d_m], the LLM's is [B, T_c, d_llm],
and the LLM additionally needs the text tokens and labels that no
upstream activation carries.  ``build_mllm_stages`` closes that gap
with a *carrier* encoding plus a typed 3-argument stage function:

    stage_fn(stage_params, x, microbatch) -> y

* The carrier is a single float32 array [B, T_c, d_c] over the merged
  sequence (T_c = ``mllm.merged_length(text_len)``, d_c = max of the
  LLM and encoder widths).  Encoder stages read/write their modality's
  rows in channels [:d_m]; the last encoder stage writes the projected
  output in channels [:d_llm].  Text rows of the *microbatch* carrier
  hold the text token id in channel 0 and the label in channel 1
  (exact in float32: vocab sizes here are far below 2**24).  Because
  modality rows carry raw embeddings in those same channels, token and
  label reads are always masked by the static text mask.
* Stage partitioning follows the executor's simulated graph
  (``executor["sim_graph"]``): stages grouped by ``Stage.module``
  (encoder name or ``"llm"``), validated to tile each module's layers
  contiguously.  Boundary stages own the boundary params — final_ln +
  projector on the last encoder stage, embedding on the first LLM
  stage, final_ln + unembed on the last.
* Frozen flags are preserved: frozen subtrees run under stop_gradient
  inside the stage fn (backward truly skips them), ``frozen_masks``
  mirrors them for AdamW, and ``trainable`` tells the executors which
  stages must produce weight grads even when the cost model assigned
  them no W work (the paper's frozen-encoder + trainable-projector
  configuration).

The sink stage emits per-token NLL in carrier channel 0;
``microbatch_loss`` reduces it so that summing over microbatches and
dividing by their count reproduces ``make_mllm_train_step``'s
cross-entropy exactly (same masked-label construction, same float32
reduction).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bam
from repro.models import layers as L
from repro.models import transformer as T


def _stop(tree):
    return jax.tree.map(lax.stop_gradient, tree)


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One pipeline stage of the partitioned MLLM (host-side, static)."""
    kind: str            # "encoder" | "llm"
    module: str          # encoder name, or "llm"
    lo: int              # module-local first layer (inclusive)
    hi: int              # module-local last layer (exclusive)
    first: bool          # first stage of its module chain
    last: bool           # last stage of its module chain
    trainable: bool      # does this stage hold any trainable params?


@dataclasses.dataclass
class StageBundle:
    """Everything the executors need to run a real MLLM: per-stage
    callables + typed per-stage params + the carrier codec."""
    mllm: Any
    specs: List[StageSpec]
    stage_fns: List[Callable]
    text_len: int
    merged_len: int
    d_carrier: int
    # static merge geometry (host numpy)
    bits_np: Any
    pos_np: Any
    emask_np: Any
    is_text_np: Any
    text_pos_np: Any
    slots: Dict[str, Tuple[int, int, int]]   # name -> (offset, n, d_m)

    # -- carrier codec ------------------------------------------------------
    @property
    def n_text(self) -> int:
        return int(self.is_text_np.sum())

    @property
    def trainable(self) -> Tuple[bool, ...]:
        return tuple(s.trainable for s in self.specs)

    def encode_microbatches(self, batch, num_microbatches: int):
        """batch: {"text_tokens" [B,T], "labels" [B,T],
        f"{name}_embeds" [B,n,d_m]} -> carrier [M, B/M, T_c, d_c]."""
        toks = batch["text_tokens"]
        B = toks.shape[0]
        M = int(num_microbatches)
        if B % M != 0:
            raise ValueError(
                f"batch size {B} not divisible by {M} microbatches")
        car = jnp.zeros((B, self.merged_len, self.d_carrier), jnp.float32)
        tpos = jnp.asarray(self.text_pos_np)
        car = car.at[:, tpos, 0].set(toks.astype(jnp.float32))
        car = car.at[:, tpos, 1].set(batch["labels"].astype(jnp.float32))
        for name, (off, n, dm) in sorted(self.slots.items()):
            car = car.at[:, off:off + n, :dm].set(
                batch[f"{name}_embeds"].astype(jnp.float32))
        return car.reshape(M, B // M, self.merged_len, self.d_carrier)

    def microbatch_loss(self, y):
        """Sink-stage output -> scalar.  Summed over the M microbatches
        this equals M x the full-batch reference cross-entropy (the
        text count per sample is static), so callers scale by 1/M."""
        n = max(self.n_text, 1)
        return jnp.sum(y[..., 0].astype(jnp.float32)) / (y.shape[0] * n)

    # -- params -------------------------------------------------------------
    def partition(self, params) -> List[Any]:
        """Full MLLM param tree -> per-stage param trees (plan order)."""
        out = []
        for sp in self.specs:
            if sp.kind == "encoder":
                src = params["encoders"][sp.module]
                st = {"layers": jax.tree.map(
                    lambda a, sp=sp: a[sp.lo:sp.hi], src["module"]["layers"])}
                if sp.last:
                    st["final_ln"] = src["module"]["final_ln"]
                    st["projector"] = src["projector"]
            else:
                src = params["llm"]
                st = {"layers": jax.tree.map(
                    lambda a, sp=sp: a[sp.lo:sp.hi], src["layers"])}
                if sp.first:
                    st["embed"] = src["embed"]
                if sp.last:
                    st["final_ln"] = src["final_ln"]
                    if not self.mllm.llm_cfg.tie_embeddings:
                        st["unembed"] = src["unembed"]
            out.append(st)
        return out

    def unpartition(self, stage_params: Sequence[Any]):
        """Exact inverse of ``partition`` (stage layer slices tile each
        module, so concatenation reconstructs the stacked layers)."""
        by_module: Dict[str, List[Tuple[StageSpec, Any]]] = {}
        for sp, st in zip(self.specs, stage_params):
            by_module.setdefault(sp.module, []).append((sp, st))
        params: Dict[str, Any] = {"encoders": {}}
        for module, parts in by_module.items():
            parts = sorted(parts, key=lambda p: p[0].lo)
            layers = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0),
                *[st["layers"] for _, st in parts])
            last = parts[-1][1]
            if module == "llm":
                llm = {"embed": parts[0][1]["embed"], "layers": layers,
                       "final_ln": last["final_ln"]}
                if not self.mllm.llm_cfg.tie_embeddings:
                    llm["unembed"] = last["unembed"]
                params["llm"] = llm
            else:
                params["encoders"][module] = {
                    "module": {"layers": layers,
                               "final_ln": last["final_ln"]},
                    "projector": last["projector"],
                }
        return params

    def frozen_masks(self, stage_params: Sequence[Any]) -> List[Any]:
        """Per-stage bool trees (True = frozen) mirroring the frozen
        flags — feed straight into AdamW's frozen masking."""
        out = []
        for sp, st in zip(self.specs, stage_params):
            if sp.kind == "encoder":
                enc = self.mllm.encoders[sp.module]
                mask = {"layers": jax.tree.map(
                    lambda _: enc.frozen_module, st["layers"])}
                if sp.last:
                    mask["final_ln"] = jax.tree.map(
                        lambda _: enc.frozen_module, st["final_ln"])
                    mask["projector"] = jax.tree.map(
                        lambda _: enc.frozen_projector, st["projector"])
            else:
                mask = jax.tree.map(lambda _: self.mllm.frozen_llm, st)
            out.append(mask)
        return out

    # -- checkpoint manifest metadata ---------------------------------------
    @property
    def layout_meta(self) -> Dict[str, Any]:
        """JSON-able stage layout recorded in checkpoint manifests so
        ``--resume`` can verify it is adopting a compatible layout."""
        return {
            "text_len": self.text_len,
            "merged_len": self.merged_len,
            "d_carrier": self.d_carrier,
            "stages": [dataclasses.asdict(s) for s in self.specs],
        }


# ---------------------------------------------------------------------------
# Stage grouping from the simulated graph
# ---------------------------------------------------------------------------

def _group_stages(mllm, graph) -> List[StageSpec]:
    per_module: Dict[str, List[int]] = {}
    for i, st in enumerate(graph.stages):
        per_module.setdefault(st.module, []).append(i)
    specs: List[StageSpec] = [None] * len(graph.stages)   # type: ignore
    for module, idxs in per_module.items():
        if module == "llm":
            n_layers = mllm.llm_cfg.num_layers
        elif module in mllm.encoders:
            n_layers = mllm.encoders[module].cfg.num_layers
        else:
            raise ValueError(
                f"graph stage module {module!r} is not an encoder of this "
                f"MLLM (encoders: {sorted(mllm.encoders)}) nor 'llm'")
        idxs = sorted(idxs, key=lambda i: graph.stages[i].layer_range[0])
        want = 0
        for k, i in enumerate(idxs):
            lo, hi = graph.stages[i].layer_range
            if lo != want or hi < lo:
                raise ValueError(
                    f"stages of module {module!r} do not tile its layers "
                    f"contiguously: got range ({lo}, {hi}) expecting "
                    f"lo={want}")
            want = hi
            first, last = (k == 0), (k == len(idxs) - 1)
            if module == "llm":
                trainable = not mllm.frozen_llm
            else:
                enc = mllm.encoders[module]
                trainable = (not enc.frozen_module) or \
                    (last and not enc.frozen_projector)
            specs[i] = StageSpec(
                kind="llm" if module == "llm" else "encoder",
                module=module, lo=lo, hi=hi, first=first, last=last,
                trainable=trainable)
        if want != n_layers:
            raise ValueError(
                f"stages of module {module!r} cover layers [0, {want}) "
                f"but the module has {n_layers}")
    return specs


# ---------------------------------------------------------------------------
# Builder
# ---------------------------------------------------------------------------

def build_mllm_stages(mllm, executor: Dict[str, Any], *,
                      text_len: int) -> StageBundle:
    """Partition ``mllm`` per the executor contract's simulated graph
    into a :class:`StageBundle` whose ``stage_fns``/``partition`` feed
    both ``execute_schedule`` and ``build_spmd_runner``."""
    graph = executor["sim_graph"]
    specs = _group_stages(mllm, graph)
    llm_cfg = mllm.llm_cfg
    if llm_cfg.tie_embeddings and \
            sum(1 for s in specs if s.kind == "llm") > 1:
        raise ValueError(
            "tie_embeddings requires the LLM to be a single pipeline "
            "stage (embedding and head live on different stages)")

    # static merge geometry — constructed exactly as build_merge does
    layout = mllm.layout or mllm.default_layout(text_len)
    total = mllm.merged_length(text_len)
    segs, t_used = [], 0
    for seg in layout:
        if seg[0] == "text":
            segs.append(("text", 0, seg[1]))
            t_used += seg[1]
        else:
            enc = mllm.encoders[seg[0]]
            segs.append(("mod", enc.modality_id, enc.num_tokens))
    if t_used != text_len:
        raise ValueError(f"layout text length {t_used} != {text_len}")
    bits_np, pos_np = bam.build_sample_bits(segs, total)
    emask_np = np.zeros((total,), bool)
    slots: Dict[str, Tuple[int, int, int]] = {}
    off = 0
    for seg in layout:
        if seg[0] == "text":
            off += seg[1]
        else:
            enc = mllm.encoders[seg[0]]
            slots[seg[0]] = (off, enc.num_tokens, enc.cfg.d_model)
            emask_np[off:off + enc.num_tokens] = True
            off += enc.num_tokens
    is_text_np = (np.asarray(bits_np) != 0) & (~emask_np)
    text_pos_np = np.where(is_text_np)[0]
    d_llm = llm_cfg.d_model
    d_carrier = max([d_llm] + [e.cfg.d_model
                               for e in mllm.encoders.values()])

    bits_c = jnp.asarray(bits_np)
    pos_c = jnp.asarray(pos_np)
    emask_c = jnp.asarray(emask_np)
    is_text_c = jnp.asarray(is_text_np)

    def make_encoder_fn(sp: StageSpec):
        enc = mllm.encoders[sp.module]
        cfg = enc.cfg
        off, n, dm = slots[sp.module]

        def fn(lp, x, mb):
            h = x[:, off:off + n, :dm].astype(jnp.dtype(cfg.dtype))
            B = h.shape[0]
            pos = jnp.broadcast_to(
                jnp.arange(n, dtype=jnp.int32)[None], (B, n))
            full = jnp.ones((B, 1, n, n), bool)
            layers = _stop(lp["layers"]) if enc.frozen_module \
                else lp["layers"]

            def body(h, lyr):
                def blk(h):
                    hh = L.apply_norm(cfg, lyr["ln1"], h)
                    a, _ = L.run_attention(lyr["attn"], cfg, hh,
                                           q_pos=pos, mask=full,
                                           rope=False)
                    h = h + a
                    hh = L.apply_norm(cfg, lyr["ln2"], h)
                    return h + L.run_mlp(lyr["mlp"], hh, "gelu")
                if cfg.remat:
                    blk = jax.checkpoint(blk)
                return blk(h), None

            with jax.named_scope("encoder"):
                h, _ = lax.scan(body, h, layers)
                if sp.last:
                    fl = _stop(lp["final_ln"]) if enc.frozen_module \
                        else lp["final_ln"]
                    h = L.apply_norm(cfg, fl, h)
            if not sp.last:
                return jnp.zeros_like(x).at[:, off:off + n, :dm].set(
                    h.astype(x.dtype))
            proj = _stop(lp["projector"]) if enc.frozen_projector \
                else lp["projector"]
            with jax.named_scope("projector"):
                out = h @ proj["w1"]
                if "w2" in proj:
                    out = jax.nn.gelu(out) @ proj["w2"]
            return jnp.zeros_like(x).at[:, off:off + n, :d_llm].set(
                out.astype(x.dtype))
        return fn

    def make_llm_fn(sp: StageSpec):
        cfg = llm_cfg
        lo, hi = sp.lo, sp.hi

        def fn(lp, x, mb):
            if mllm.frozen_llm:
                lp = _stop(lp)
            h = hidden(lp, x, mb)
            if not sp.last:
                return jnp.zeros_like(x).at[:, :, :cfg.d_model].set(
                    h.astype(x.dtype))
            return head(lp, x, mb, h)

        @jax.named_scope("llm")
        def hidden(lp, x, mb):
            """The stage's share of ``transformer.hidden``: the embedding
            on the first stage, its layers, the final norm on the last."""
            B = x.shape[0]
            Tc = x.shape[1]
            batch = {
                "positions": jnp.broadcast_to(pos_c[None], (B, Tc)),
                "bits": jnp.broadcast_to(bits_c[None], (B, Tc)),
            }
            if sp.first:
                # mod rows of the carrier hold raw embeddings in
                # channel 0 — the token read must stay masked
                tokens = jnp.where(is_text_c[None], mb[..., 0],
                                   0.0).astype(jnp.int32)
                h = lp["embed"][tokens]
                if cfg.embed_scale:
                    h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
                h = jnp.where(emask_c[None, :, None],
                              x[:, :, :cfg.d_model].astype(h.dtype), h)
            else:
                h = x[:, :, :cfg.d_model].astype(jnp.dtype(cfg.dtype))

            def body(h, xs):
                lyr, i = xs

                def blk(h):
                    out, _, _ = T._block(cfg, lyr, h, batch, i, None)
                    return out
                if cfg.remat:
                    blk = jax.checkpoint(blk)
                return blk(h), None

            h, _ = lax.scan(body, h,
                            (lp["layers"], jnp.arange(lo, hi)))
            if sp.last:
                h = L.apply_norm(cfg, lp["final_ln"], h)
            return h

        @jax.named_scope("lm_head")
        def head(lp, x, mb, h):
            """The head and each text position's negative
            log-likelihood, in carrier channel 0."""
            w = lp["embed"].T if cfg.tie_embeddings else lp["unembed"]
            logits = h @ w
            if cfg.final_softcap:
                logits = jnp.tanh(logits / cfg.final_softcap) \
                    * cfg.final_softcap
            logits = logits.astype(jnp.float32)
            labels = jnp.where(is_text_c[None], mb[..., 1],
                               0.0).astype(jnp.int32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(
                logits, labels[..., None], axis=-1)[..., 0]
            nll = (lse - ll) * is_text_c[None].astype(jnp.float32)
            return jnp.zeros_like(x).at[:, :, 0].set(
                nll.astype(x.dtype))
        return fn

    fns = [make_encoder_fn(sp) if sp.kind == "encoder" else make_llm_fn(sp)
           for sp in specs]
    return StageBundle(
        mllm=mllm, specs=specs, stage_fns=fns, text_len=text_len,
        merged_len=total, d_carrier=d_carrier, bits_np=np.asarray(bits_np),
        pos_np=np.asarray(pos_np), emask_np=emask_np,
        is_text_np=is_text_np, text_pos_np=text_pos_np, slots=slots)
