"""Mixture-of-Experts decoder family (qwen2-moe-a2.7b, deepseek-moe-16b).

Fine-grained MoE with shared experts (DeepSeekMoE, arXiv:2401.06066;
Qwen1.5-MoE): each layer = GQA attention + [shared experts (always-on
dense MLP) + routed experts (top-k)].

Two dispatch backends:

* ``capacity`` (production, expert-parallel): GShard-style fixed-capacity
  scatter. Tokens are assigned slot positions inside their expert's
  buffer via a cumulative count; overflow beyond
  ``C = ceil(T*K/E * capacity_factor)`` is dropped (standard TPU MoE).
  Expert weights and buffers shard over the ``model`` mesh axis (expert
  parallelism); compute is ``E × C × d × d_e`` batched matmuls on the
  MXU. HLO FLOPs ≈ active-expert FLOPs × capacity_factor — this is what
  the roofline's MODEL_FLOPS/HLO_FLOPs ratio measures for MoE.
* ``dense`` (exact, for tests/smoke): every expert computes every token,
  combined with routing weights — O(E/K) more FLOPs, bitwise-checkable
  against the router math.

Router aux loss: Switch-style load-balance loss
``E * Σ_e f_e · p_e`` (f = fraction of tokens routed to e, p = mean
router prob of e).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _moe_ffn_init(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    ks = jax.random.split(key, 5)
    d, de = cfg.d_model, m.d_expert
    ep = m.num_experts_padded   # dummy tail experts: routed-to never
    p = {
        "router": L.dense_init(ks[0], d, m.num_experts, dtype),
        # stacked expert weights [E_pad, d, de] / [E_pad, de, d]
        "w_gate": (jax.random.normal(ks[1], (ep, d, de)) * 0.02
                   ).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (ep, d, de)) * 0.02
                 ).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (ep, de, d)) * 0.02
                   ).astype(dtype),
    }
    if m.num_shared_experts:
        p["shared"] = L.mlp_init(ks[4], d, de * m.num_shared_experts, dtype,
                                 gated=True)
    return p


def init(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.dtype)
    m = cfg.moe
    k_dense, k_moe = jax.random.split(key)
    # deepseek-moe: leading dense layer(s) kept out of the homogeneous scan
    moe_cfg = cfg.replace(num_layers=cfg.num_layers - m.first_dense_layers)
    params = T.init(k_moe, moe_cfg,
                    ffn_init=lambda k: _moe_ffn_init(k, cfg, dtype))
    if m.first_dense_layers:
        params["dense_layers"] = L.stacked_init(
            lambda k: T._layer_init(k, cfg, dtype), k_dense,
            m.first_dense_layers)
    return params


# ---------------------------------------------------------------------------
# Routed-expert dispatch
# ---------------------------------------------------------------------------

def router_probs(lp, h, cfg: ModelConfig):
    m = cfg.moe
    logits = (h @ lp["router"]).astype(jnp.float32)       # [B,T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, m.top_k)                    # [B,T,K]
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-9)   # renormalize
    return probs, w, idx


def aux_loss(probs, idx, cfg: ModelConfig):
    m = cfg.moe
    E = m.num_experts
    # scatter-add histogram instead of a [B,T,K,E] one-hot (memory!)
    n = idx.size // m.top_k
    f = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / n
    p = jnp.mean(probs.reshape(-1, E), axis=0)            # mean prob
    return E * jnp.sum(f * p) * m.router_aux_coef


def _dense_dispatch(lp, h, w, idx, cfg: ModelConfig):
    """Exact reference: all experts on all tokens, weighted combine."""
    m = cfg.moe
    # [E,B,T,de]
    g = jnp.einsum("btd,edf->ebtf", h, lp["w_gate"])
    u = jnp.einsum("btd,edf->ebtf", h, lp["w_up"])
    act = jax.nn.silu(g) * u
    out_e = jnp.einsum("ebtf,efd->ebtd", act, lp["w_down"])  # [E,B,T,d]
    onehot = jax.nn.one_hot(idx, m.num_experts, dtype=h.dtype)  # [B,T,K,E]
    weight = jnp.einsum("btke,btk->ebt", onehot, w.astype(h.dtype))
    return jnp.einsum("ebt,ebtd->btd", weight, out_e)


def _capacity_dispatch(lp, h, w, idx, cfg: ModelConfig):
    """GShard-style fixed-capacity scatter dispatch, **row-local**:
    slot assignment / scatter / gather happen within each batch row, so
    every buffer keeps the (data-sharded) batch dimension — no global
    [B·T·K, ·] tensors that GSPMD would have to replicate. This was
    §Perf iteration 1 for qwen2-moe train_4k: the original global
    dispatch cost 280 GB/device and 6.5 s of collective time; row-local
    dispatch shards cleanly (see EXPERIMENTS.md)."""
    m = cfg.moe
    B, T, d = h.shape
    K, E = m.top_k, m.num_experts_padded
    cap = int((T * K / m.num_experts) * m.capacity_factor) + 1

    idx_f = idx.reshape(B, T * K)               # expert id per (token,k)
    w_f = w.reshape(B, T * K)
    tok_f = jnp.broadcast_to(
        jnp.repeat(jnp.arange(T), K)[None], (B, T * K))

    onehot = jax.nn.one_hot(idx_f, E, dtype=jnp.int32)      # [B, T*K, E]
    pos_in_expert = jnp.cumsum(onehot, axis=1) * onehot     # 1-based
    slot = jnp.sum(pos_in_expert, axis=-1) - 1              # [B, T*K]
    keep = (slot >= 0) & (slot < cap)
    slot_c = jnp.clip(slot, 0, cap - 1)

    def scatter_row(hrow, idx_r, slot_r, keep_r, tok_r):
        src = jnp.where(keep_r[:, None], hrow[tok_r], 0).astype(hrow.dtype)
        return jnp.zeros((E, cap, d), hrow.dtype).at[idx_r, slot_r].add(src)

    buf = jax.vmap(scatter_row)(h, idx_f, slot_c, keep, tok_f)  # [B,E,c,d]
    # §Perf iteration 2 (qwen2-moe train_4k): GSPMD replicates the
    # vmapped scatter-add without an explicit constraint (43 GB
    # all-gathers + 86 GB backward all-reduces per layer at the
    # production mesh). Pin the dispatch buffers to the data axis.
    from repro.launch import sharding as shd
    # E-and-B 2-D sharding: batch over data, experts over model (true
    # expert parallelism when E_pad % model == 0; §Perf iteration 3)
    buf = shd.constrain(buf, "dp", "model", None, None)

    # expert FFN as batched matmul on the stacked expert dim
    g = jnp.einsum("becd,edf->becf", buf, lp["w_gate"])
    u = jnp.einsum("becd,edf->becf", buf, lp["w_up"])
    act = jax.nn.silu(g) * u
    out_buf = jnp.einsum("becf,efd->becd", act, lp["w_down"])
    out_buf = shd.constrain(out_buf, "dp", "model", None, None)

    def gather_row(ob, idx_r, slot_r, keep_r, tok_r, w_r):
        g = ob[idx_r, slot_r]                               # [T*K, d]
        g = jnp.where(keep_r[:, None], g, 0)
        return jnp.zeros((T, d), ob.dtype).at[tok_r].add(
            g * w_r[:, None].astype(ob.dtype))

    combined = jax.vmap(gather_row)(out_buf, idx_f, slot_c, keep, tok_f,
                                    w_f)
    combined = shd.constrain(combined, "dp", None, None)
    return combined


def _shardmap_dispatch(lp, h, w, idx, cfg: ModelConfig, mesh, dp_axes):
    """Perf iteration A4: expert-parallel dispatch as an explicit
    shard_map — scatter/gather run *locally* per device (GSPMD's
    scatter partitioner, which replicated the buffers, never sees
    them). Each model-axis rank owns E_pad/model experts and computes
    only tokens routed to them from its (model-replicated) activation
    shard; one psum over ``model`` combines the outputs. Per-layer
    collective traffic drops to one [B/dp, T, d] all-reduce."""
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, T, d = h.shape
    K, E = m.top_k, m.num_experts_padded
    cap = int((T * K / m.num_experts) * m.capacity_factor) + 1

    # slot assignment is deterministic and model-replicated: compute it
    # once outside so every rank agrees
    idx_f = idx.reshape(B, T * K)
    w_f = w.reshape(B, T * K).astype(h.dtype)
    tok_f = jnp.broadcast_to(jnp.repeat(jnp.arange(T), K)[None], (B, T * K))
    onehot = jax.nn.one_hot(idx_f, E, dtype=jnp.int32)
    pos_in_expert = jnp.cumsum(onehot, axis=1) * onehot
    slot = jnp.sum(pos_in_expert, axis=-1) - 1
    keep_cap = (slot >= 0) & (slot < cap)
    slot_c = jnp.clip(slot, 0, cap - 1)

    def body(h_l, wg_l, wu_l, wd_l, idx_l, slot_l, keep_l, tok_l, wf_l):
        e_local = wg_l.shape[0]
        j = jax.lax.axis_index("model")
        idx_rel = idx_l - j * e_local
        mine = keep_l & (idx_rel >= 0) & (idx_rel < e_local)
        idx_rel = jnp.clip(idx_rel, 0, e_local - 1)

        def scatter_row(hrow, ir, sr, kr, tr):
            src = jnp.where(kr[:, None], hrow[tr], 0).astype(hrow.dtype)
            return jnp.zeros((e_local, cap, hrow.shape[-1]),
                             hrow.dtype).at[ir, sr].add(src)

        buf = jax.vmap(scatter_row)(h_l, idx_rel, slot_l, mine, tok_l)
        g = jnp.einsum("becd,edf->becf", buf, wg_l)
        u = jnp.einsum("becd,edf->becf", buf, wu_l)
        act = jax.nn.silu(g) * u
        ob = jnp.einsum("becf,efd->becd", act, wd_l)

        def gather_row(ob_r, ir, sr, kr, tr, wr):
            gbuf = ob_r[ir, sr]
            gbuf = jnp.where(kr[:, None], gbuf, 0)
            return jnp.zeros((T, ob_r.shape[-1]), ob_r.dtype).at[tr].add(
                gbuf * wr[:, None])

        out_l = jax.vmap(gather_row)(ob, idx_rel, slot_l, mine, tok_l,
                                     wf_l)
        return jax.lax.psum(out_l, "model")

    dp = P(dp_axes, None, None)
    ep = P("model", None, None)
    tk = P(dp_axes, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(dp, ep, ep, ep, tk, tk, tk, tk, tk),
        out_specs=dp, check_vma=False,
    )(h, lp["w_gate"], lp["w_up"], lp["w_down"], idx_f, slot_c, keep_cap,
      tok_f, w_f)


def _pick_dispatch(lp, h, w, idx, cfg: ModelConfig):
    m = cfg.moe
    if m.backend == "dense":
        return _dense_dispatch(lp, h, w, idx, cfg)
    from repro.launch import sharding as shd
    mesh = shd._CURRENT_MESH
    rules = shd.active()
    if mesh is not None and rules is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        msize = sizes.get("model", 1)
        dpsize = 1
        for a in rules.dp:
            dpsize *= sizes.get(a, 1)
        if msize > 1 and m.num_experts_padded % msize == 0 and \
                h.shape[0] % dpsize == 0:
            return _shardmap_dispatch(lp, h, w, idx, cfg, mesh, rules.dp)
    return _capacity_dispatch(lp, h, w, idx, cfg)


def moe_ffn(lp, h, cfg: ModelConfig):
    """Full MoE FFN: shared experts + routed top-k. Returns (out, aux)."""
    m = cfg.moe
    probs, w, idx = router_probs(lp, h, cfg)
    routed = _pick_dispatch(lp, h, w, idx, cfg)
    out = routed
    if m.num_shared_experts:
        out = out + L.run_mlp(lp["shared"], h, cfg.act)
    return out, aux_loss(probs, idx, cfg)


# ---------------------------------------------------------------------------
# Model API
# ---------------------------------------------------------------------------

def _ffn_hook(cfg: ModelConfig):
    def ffn(lp, h, layer_idx):
        return moe_ffn(lp["mlp"], h, cfg)
    return ffn


def _run_dense_prefix(params, cfg: ModelConfig, x, batch):
    """Leading dense layers (deepseek-moe style), outside the MoE scan."""
    m = cfg.moe
    if not m.first_dense_layers:
        return x

    def body(x, xs):
        lp, i = xs

        def blk(x):
            out, _, _ = T._block(cfg, lp, x, batch, i, None)
            return out
        if cfg.remat:
            blk = jax.checkpoint(blk)
        return blk(x), None

    x, _ = lax.scan(body, x,
                    (params["dense_layers"], jnp.arange(m.first_dense_layers)))
    return x


def hidden(params, cfg: ModelConfig, batch):
    m = cfg.moe
    x = T.embed_tokens(params, cfg, batch)
    x = _run_dense_prefix(params, cfg, x, batch)
    moe_cfg = cfg.replace(num_layers=cfg.num_layers - m.first_dense_layers)
    ffn = _ffn_hook(cfg)

    def body(carry, xs):
        x, aux = carry
        lp, i = xs

        def blk(x):
            return T._block(moe_cfg, lp, x, batch, i, ffn)
        if cfg.remat:
            blk = jax.checkpoint(blk)
        x, a, _ = blk(x)
        return (x, aux + a), None

    (x, aux), _ = lax.scan(
        body, (x, jnp.float32(0.0)),
        (params["layers"], jnp.arange(moe_cfg.num_layers)))
    return L.apply_norm(cfg, params["final_ln"], x), {"aux_loss": aux}


def forward(params, cfg: ModelConfig, batch):
    h, aux = hidden(params, cfg, batch)
    return T.unembed(params, cfg, h), aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = jnp.dtype(cfg.dtype) if dtype is None else dtype
    m = cfg.moe
    c = L.init_kv_cache(cfg, batch, max_len, dtype,
                        num_layers=cfg.num_layers - m.first_dense_layers)
    c["bits"] = jnp.zeros((batch, max_len), jnp.uint32)
    if m.first_dense_layers:
        c["dense"] = L.init_kv_cache(cfg, batch, max_len, dtype,
                                     num_layers=m.first_dense_layers)
    return c


def decode_step(params, cfg: ModelConfig, cache, batch):
    m = cfg.moe
    moe_cfg = cfg.replace(num_layers=cfg.num_layers - m.first_dense_layers)
    if not m.first_dense_layers:
        return T.decode_step(params, moe_cfg, cache, batch,
                             ffn=_ffn_hook(cfg))

    # run dense prefix layers with their own cache slice, then the MoE scan
    B = batch["tokens"].shape[0]
    x = T.embed_tokens(params, cfg, batch)
    Tmax = cache["k"].shape[2]
    cur = batch["positions"][:, 0]
    kv_pos = jnp.broadcast_to(jnp.arange(Tmax, dtype=jnp.int32)[None],
                              (B, Tmax))
    from repro.core import bam
    q_bits = batch.get("bits")
    if q_bits is None:
        q_bits = jnp.full((B, 1), bam.text_token(), jnp.uint32)
    cache_bits = jnp.where(
        kv_pos < cur[:, None], cache["bits"],
        jnp.where(kv_pos == cur[:, None],
                  jnp.broadcast_to(q_bits, kv_pos.shape), jnp.uint32(0)))
    idx = cur[0]
    mask = bam.allowed_mask(q_bits, cache_bits, batch["positions"],
                            kv_pos)[:, None]

    def dense_body(x, xs):
        lp, ck, cv = xs
        store = {}

        def kv_override(k, v):
            nk, nv = L.cache_update(ck, cv, k, v, idx)
            store["k"], store["v"] = nk, nv
            return nk, nv

        h = L.apply_norm(cfg, lp["ln1"], x)
        attn_out, _ = L.run_attention(
            lp["attn"], cfg, h, q_pos=batch["positions"], kv_pos=kv_pos,
            mask=mask, kv_override=kv_override)
        x = x + attn_out
        h = L.apply_norm(cfg, lp["ln2"], x)
        out, _ = T._default_ffn(lp, h, cfg)
        return x + out, (store["k"], store["v"])

    x, (dk, dv) = lax.scan(
        dense_body, x,
        (params["dense_layers"], cache["dense"]["k"], cache["dense"]["v"]))

    sub = {"embed": params["embed"], "layers": params["layers"],
           "final_ln": params["final_ln"]}
    if "unembed" in params:
        sub["unembed"] = params["unembed"]
    # moe scan consumes pre-embedded hidden: pass via inputs_embeds override
    moe_batch = dict(batch)
    moe_batch["inputs_embeds"] = x
    moe_batch["embed_mask"] = jnp.ones(batch["tokens"].shape, bool)
    moe_cache = {"k": cache["k"], "v": cache["v"], "bits": cache["bits"]}
    logits, new_moe_cache = T.decode_step(sub, moe_cfg, moe_cache, moe_batch,
                                          ffn=_ffn_hook(cfg))
    new_cache = dict(new_moe_cache)
    new_cache["dense"] = {"k": dk, "v": dv}
    return logits, new_cache
