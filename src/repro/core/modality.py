"""Modular MLLM construction (Cornstarch §3.2): ModalityModule,
MultimodalModule, ParallelSpec, execution DAG, callback interface.

JAX adaptation of the paper's programming model (Listing 1/2):

    vis   = ModalityModule("vision", vis_cfg, modality_id=1, proj="mlp")
    audio = ModalityModule("audio", audio_cfg, modality_id=2)
    mllm  = MultimodalModule(encoders={...}, llm=llm_cfg)
    mllm.freeze("vision", module=True, projector=False)
    params = mllm.init(key)
    logits, aux = mllm.forward(params, batch)          # single-program
    spec  = MultimodalParallelSpec(encoder_specs=..., llm_spec=...)
    plan  = spec.apply(mllm)                           # -> pipeline plan

The execution graph is explicit (networkx DiGraph) and is constructed
only from true data flow — no false dependencies between encoders
(paper C1). The frozen flags feed the frozen-aware partitioner
(core/pipeline.py) and the gradient masking in optim/.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import networkx as nx

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import bam, pipeline as pp
from repro.models import layers as Lyr
from repro.models import transformer as T

Callback = Callable[..., Any]


# ---------------------------------------------------------------------------
# ModalityModule
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ModalityModule:
    """One unimodal model + its projector into the LLM embedding space.

    The modality *frontend* (conv codec / ViT patcher) is stubbed per
    DESIGN.md — the module consumes precomputed frame/patch embeddings
    and runs the transformer backbone + projector.
    """
    name: str
    cfg: ModelConfig
    modality_id: int                      # BAM bit (1..15; 0 = text)
    projector: str = "linear"             # linear | mlp
    num_tokens: int = 0                   # tokens this encoder emits
    frozen_module: bool = True
    frozen_projector: bool = False
    preprocess_callback: Optional[Callback] = None
    postprocess_module_callback: Optional[Callback] = None
    postprocess_projector_callback: Optional[Callback] = None

    # -- params ------------------------------------------------------------
    def init(self, key, llm_d_model: int):
        from repro.models import mllm as M
        k1, k2 = jax.random.split(key)
        dtype = jnp.dtype(self.cfg.dtype)
        p = {"module": M.encoder_init(k1, self.cfg)}
        d = self.cfg.d_model
        if self.projector == "mlp":
            p["projector"] = {
                "w1": Lyr.dense_init(k2, d, llm_d_model, dtype),
                "w2": Lyr.dense_init(jax.random.fold_in(k2, 1),
                                     llm_d_model, llm_d_model, dtype),
            }
        else:
            p["projector"] = {
                "w1": Lyr.dense_init(k2, d, llm_d_model, dtype)}
        return p

    # -- forward -----------------------------------------------------------
    def forward(self, params, inputs):
        """inputs: dict with f"{name}_embeds" [B, T_m, d_m]. Applies the
        call order of Listing 2: cb_before -> module -> cb_after ->
        projector -> cb_after_proj. Frozen parts run under
        stop_gradient so backward truly skips them (paper §4.2)."""
        from repro.models import mllm as M
        if self.preprocess_callback:
            inputs = self.preprocess_callback(inputs)
        embeds = inputs[f"{self.name}_embeds"]
        mod_p = params["module"]
        if self.frozen_module:
            mod_p = jax.tree.map(jax.lax.stop_gradient, mod_p)
        with jax.named_scope("encoder"):
            out = M.encoder_forward(mod_p, self.cfg, embeds)
        if self.postprocess_module_callback:
            out = self.postprocess_module_callback(inputs, out)
        proj_p = params["projector"]
        if self.frozen_projector:
            proj_p = jax.tree.map(jax.lax.stop_gradient, proj_p)
        with jax.named_scope("projector"):
            out = out @ proj_p["w1"]
            if "w2" in proj_p:
                out = jax.nn.gelu(out) @ proj_p["w2"]
        if self.postprocess_projector_callback:
            out = self.postprocess_projector_callback(inputs, out)
        return out

    # -- cost profile for the partitioner -----------------------------------
    def profile(self, seq_tokens: int, batch: int = 1,
                recompute: bool = False) -> pp.ModuleProfile:
        prof = pp.profile_from_config(
            self.cfg, seq_tokens or self.num_tokens, batch=batch,
            frozen=self.frozen_module, recompute=recompute, name=self.name)
        return prof


# ---------------------------------------------------------------------------
# MultimodalModule
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MultimodalModule:
    encoders: Dict[str, ModalityModule]
    llm_cfg: ModelConfig
    frozen_llm: bool = True
    # merge policy: list of segments ("text", n) | (encoder_name,)
    layout: Optional[List[Tuple]] = None
    preprocess_callback: Optional[Callback] = None   # cb_before_llm

    def __post_init__(self):
        ids = [e.modality_id for e in self.encoders.values()]
        assert len(set(ids)) == len(ids) and 0 not in ids, \
            "modality ids must be unique and nonzero"

    # -- execution DAG (paper §3.2) -----------------------------------------
    def execution_graph(self) -> nx.DiGraph:
        g = nx.DiGraph()
        for name in self.encoders:
            g.add_node(name, kind="encoder")
        g.add_node("llm", kind="llm")
        for name in self.encoders:
            g.add_edge(name, "llm")   # only true data flow — no false deps
        assert nx.is_directed_acyclic_graph(g)
        return g

    def independent_sets(self) -> List[List[str]]:
        """Antichains of the DAG = groups executable in parallel
        (modality parallelism, §4.1)."""
        g = self.execution_graph()
        order = list(nx.topological_generations(g))
        return [sorted(gen) for gen in order]

    # -- freezing ------------------------------------------------------------
    def freeze(self, name: str, *, module: Optional[bool] = None,
               projector: Optional[bool] = None):
        if name == "llm":
            assert module is not None
            self.frozen_llm = module
            return
        e = self.encoders[name]
        if module is not None:
            e.frozen_module = module
        if projector is not None:
            e.frozen_projector = projector

    # -- params ---------------------------------------------------------------
    def init(self, key):
        keys = jax.random.split(key, len(self.encoders) + 1)
        params = {"encoders": {}}
        for k, (name, enc) in zip(keys, sorted(self.encoders.items())):
            params["encoders"][name] = enc.init(k, self.llm_cfg.d_model)
        params["llm"] = T.init(keys[-1], self.llm_cfg)
        return params

    def frozen_mask(self, params):
        """Pytree of bools: True = frozen (no optimizer update)."""
        mask = {"encoders": {}}
        for name, enc in self.encoders.items():
            mask["encoders"][name] = {
                "module": jax.tree.map(lambda _: enc.frozen_module,
                                       params["encoders"][name]["module"]),
                "projector": jax.tree.map(
                    lambda _: enc.frozen_projector,
                    params["encoders"][name]["projector"]),
            }
        mask["llm"] = jax.tree.map(lambda _: self.frozen_llm, params["llm"])
        return mask

    # -- batch merge (cb_before_llm default policy) ---------------------------
    def default_layout(self, text_len: int) -> List[Tuple]:
        """EE-style: text prefix, then each encoder stream, then the
        remaining text (encoder outputs embedded, Fig. 11b)."""
        n_enc = len(self.encoders)
        pre = max(text_len // (n_enc + 1), 1)
        lay: List[Tuple] = [("text", pre)]
        rest = text_len - pre
        for name in sorted(self.encoders):
            lay.append((name,))
            seg = max(rest // n_enc, 0)
            lay.append(("text", seg))
        used = sum(s[1] for s in lay if s[0] == "text")
        if used < text_len:
            lay.append(("text", text_len - used))
        return lay

    def merged_length(self, text_len: int) -> int:
        return text_len + sum(e.num_tokens for e in self.encoders.values())

    @jax.named_scope("merge")
    def build_merge(self, text_tokens, enc_outputs: Dict[str, Any],
                    layout: Optional[List[Tuple]] = None):
        """Merge text tokens + projected encoder outputs into one
        sequence; returns a transformer batch (inputs_embeds path) with
        BAM bits and positions. Pure host logic for segment offsets
        (static layout), jnp for tensors."""
        import numpy as np
        B, Tt = text_tokens.shape
        layout = layout or self.layout or self.default_layout(Tt)
        total = self.merged_length(Tt)
        d = self.llm_cfg.d_model

        segs = []
        t_used = 0
        for seg in layout:
            if seg[0] == "text":
                segs.append(("text", 0, seg[1]))
                t_used += seg[1]
            else:
                enc = self.encoders[seg[0]]
                segs.append(("mod", enc.modality_id, enc.num_tokens))
        assert t_used == Tt, (t_used, Tt)
        bits_np, pos_np = bam.build_sample_bits(segs, total)
        bits = jnp.broadcast_to(jnp.asarray(bits_np)[None], (B, total))
        positions = jnp.broadcast_to(jnp.asarray(pos_np)[None], (B, total))

        # scatter maps
        tok_full = jnp.zeros((B, total), text_tokens.dtype)
        embeds = jnp.zeros((B, total, d),
                           jnp.dtype(self.llm_cfg.dtype))
        emask_np = np.zeros((total,), bool)
        off, t_off = 0, 0
        for seg in layout:
            if seg[0] == "text":
                n = seg[1]
                tok_full = jax.lax.dynamic_update_slice(
                    tok_full, jax.lax.dynamic_slice(
                        text_tokens, (0, t_off), (B, n)), (0, off))
                t_off += n
            else:
                enc = self.encoders[seg[0]]
                n = enc.num_tokens
                embeds = jax.lax.dynamic_update_slice(
                    embeds, enc_outputs[seg[0]].astype(embeds.dtype),
                    (0, off, 0))
                emask_np[off:off + n] = True
            off += n
        embed_mask = jnp.broadcast_to(jnp.asarray(emask_np)[None],
                                      (B, total))
        return {"tokens": tok_full, "positions": positions, "bits": bits,
                "inputs_embeds": embeds, "embed_mask": embed_mask}

    # -- single-program forward (reference; pipelined execution lives in
    #    parallel/spmd.py) --------------------------------------------
    def forward(self, params, batch):
        enc_out = {}
        for name, enc in sorted(self.encoders.items()):
            enc_out[name] = enc.forward(params["encoders"][name], batch)
        merged = self.build_merge(batch["text_tokens"], enc_out)
        if self.preprocess_callback:
            merged = self.preprocess_callback(enc_out, merged)
        llm_p = params["llm"]
        if self.frozen_llm:
            llm_p = jax.tree.map(jax.lax.stop_gradient, llm_p)
        return T.forward(llm_p, self.llm_cfg, merged), merged

    # -- profiles for the partitioner ----------------------------------------
    def profiles(self, text_len: int, batch: int = 1,
                 recompute: bool = False):
        encs = []
        for name, enc in sorted(self.encoders.items()):
            encs.append(enc.profile(enc.num_tokens, batch, recompute))
        merged = self.merged_length(text_len)
        llm = pp.profile_from_config(self.llm_cfg, merged, batch=batch,
                                     frozen=self.frozen_llm,
                                     recompute=recompute, name="llm")
        # forward-order chain: encoders (parallel) then llm; a trainable
        # projector after encoder => llm must compute input grads
        any_trainable_proj = any(not e.frozen_projector
                                 for e in self.encoders.values())
        for e, enc in zip(encs, sorted(self.encoders.values(),
                                       key=lambda x: x.name)):
            e.trainable_upstream = False
        llm.trainable_upstream = any_trainable_proj or \
            any(not e.frozen_module for e in self.encoders.values())
        return encs, llm


# ---------------------------------------------------------------------------
# Parallelism specs (paper §3.2)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParallelSpec:
    tp_size: int = 1
    cp_size: int = 1
    pp_size: int = 1

    @property
    def devices(self) -> int:
        return self.tp_size * self.cp_size * self.pp_size


@dataclasses.dataclass
class MultimodalParallelSpec:
    encoder_specs: Dict[str, ParallelSpec]
    llm_spec: ParallelSpec
    num_microbatches: int = 8
    microbatch_size: int = 1
    frozen_aware: bool = True
    schedule: str = "1f1b"   # "1f1b" | "interleaved" | "zb-h1" | "zb-v"
    # interleaved's virtual-chunk search: an int ceiling (try v..1) or
    # an explicit candidate tuple; zb-v always searches {2, 1}
    virtual_chunks: Any = 2

    def apply(self, mllm: MultimodalModule, text_len: int = 1024) -> dict:
        """Build the pipeline plan: per-module stage partitions (using
        the frozen-aware rule) + the modality-parallel graph + its
        simulated schedule (any core.schedule scheduler). The executor
        contract's plan["graph"] always has one stage per simulated
        device — chunked schedules keep their v-times finer simulation
        for bubble accounting but fold the executor graph back to the
        planned partition.

        Superseded by ``repro.parallel``: ``parallelize()`` searches
        the allocation instead of taking it as given, and
        ``MLLMParallelPlan.apply`` replays a recorded plan — both
        share this method's fold-back construction
        (``repro.parallel.build_executor_plan``)."""
        from repro.parallel.plan import build_executor_plan
        assert set(self.encoder_specs) == set(mllm.encoders)
        encs, llm = mllm.profiles(text_len, batch=self.microbatch_size)
        enc_counts = [self.encoder_specs[e.name].pp_size for e in encs]
        out = build_executor_plan(
            encs, llm, enc_counts, self.llm_spec.pp_size,
            self.num_microbatches, schedule=self.schedule,
            virtual_chunks=self.virtual_chunks,
            frozen_aware=self.frozen_aware)
        # legacy accounting: tp x cp x pp of every spec, not just the
        # simulated pipeline ranks
        out["devices"] = sum(s.devices
                             for s in self.encoder_specs.values()) \
            + self.llm_spec.devices
        return out
