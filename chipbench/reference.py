"""Plain float32 reference of the benchmark's frozen-encoder VLM step.

The model, written from its published description (EVA-CLIP ViT tower,
linear projector, Qwen-family decoder) in straightforward ``jax.numpy``
at ``precision=HIGHEST``, one batch row at a time and one layer at a
time, so that it fits beside nothing else on one chip. It imports
nothing of the program under test: weights come from :func:`init_params`
(the benchmark's own generator, which also makes the program's weights),
batches from ``traffic.batches``.

What it computes, for the first ``steps`` training steps of a cell:

- the frozen encoder forward over each row's image embeddings;
- the linear projector, the merge into the text stream at the layout's
  image slot, and the LLM forward with the multimodal mask (text rows
  causal over everything before them, image rows bidirectional within
  their own image and blind to text);
- cross-entropy over text positions, mean over the batch's text tokens;
- the backward through the frozen LLM into the projector, and AdamW on
  the projector, its result stored back in the parameters' dtype
  (bfloat16), as the configuration states.

``numerics="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor scale, the step down from the bfloat16
that the configuration states. ``fault="half_batch"`` takes the loss
over the first half of each batch only.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: float8 e4m3's largest finite value: the per-tensor scale maps amax here
_E4M3_MAX = 448.0
#: cross-entropy is taken over chunks of this many text positions, so a
#: row's [T, vocab] logits never exist at once
CE_CHUNK = 512


#: the choices this model (and the program as the benchmark builds it)
#: computes, by configuration key; a file that states another is refused
#: rather than run as this one
SUPPORTED = {
    ("hidden_act",): "silu",
    ("vision", "hidden_act"): "gelu_tanh",
    ("projector", "type"): "linear",
    ("trainable",): {"vision": False, "projector": True, "llm": False},
}


def check_config(cfg: Dict[str, Any]) -> None:
    """Raise ``ValueError`` where the configuration states a choice the
    benchmark does not build: another activation, projector or set of
    trained modules, or a projector whose sizes do not join the tower to
    the LLM."""
    for path, want in SUPPORTED.items():
        got = cfg
        for k in path:
            got = got.get(k) if isinstance(got, dict) else None
        if got != want:
            raise ValueError(f"{cfg.get('name')}: {'.'.join(path)} is "
                             f"{got!r}; the benchmark builds {want!r}")
    p = cfg["projector"]
    if (p.get("in_features"), p.get("out_features")) != (
            cfg["vision"]["hidden_size"], cfg["hidden_size"]):
        raise ValueError(f"{cfg.get('name')}: the projector maps "
                         f"{p.get('in_features')} -> {p.get('out_features')}"
                         f", not the tower's width to the LLM's")


@dataclasses.dataclass(frozen=True)
class Spec:
    """The sizes the reference needs, read from a configuration file."""
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    qk_norm: bool
    qkv_bias: bool
    tied: bool
    vd: int
    vlayers: int
    vheads: int
    vhead_dim: int
    vff: int
    v_tokens: int
    ln_eps: float

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "Spec":
        check_config(cfg)
        v = cfg["vision"]
        return cls(
            d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            qk_norm=bool(cfg.get("qk_norm", False)),
            qkv_bias=bool(cfg.get("attention_bias", False)),
            tied=bool(cfg["tie_word_embeddings"]),
            vd=v["hidden_size"], vlayers=v["num_hidden_layers"],
            vheads=v["num_attention_heads"], vhead_dim=v["head_dim"],
            vff=v["intermediate_size"], v_tokens=v["num_tokens"],
            ln_eps=float(v["layer_norm_eps"]))


@dataclasses.dataclass(frozen=True)
class Optim:
    """AdamW as the cell states it (lr schedule: linear warm-up, then
    cosine to zero at ``total_steps``; global-norm clipping)."""
    lr: float
    warmup_steps: int
    total_steps: int
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0

    @classmethod
    def from_traffic(cls, traffic: Dict[str, Any]) -> "Optim":
        return cls(**traffic["optimizer"])

    def lr_at(self, t: int) -> float:
        warm = min(t / max(self.warmup_steps, 1), 1.0)
        frac = min(max((t - self.warmup_steps)
                       / max(self.total_steps - self.warmup_steps, 1),
                       0.0), 1.0)
        return self.lr * warm * 0.5 * (1.0 + math.cos(math.pi * frac))


# ---------------------------------------------------------------------------
# Weights from the seed (the benchmark's own generator)
# ---------------------------------------------------------------------------

def base_key(seed: int):
    """A PRNG key from a seed of any size up to 62 bits."""
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def param_layout(spec: Spec) -> Dict[str, Any]:
    """Nested dict of ``(shape, kind)`` in the layout the program keeps
    its parameters in. ``kind``: ``dense`` (N(0, 0.02)), ``gain``
    (1 + N(0, 0.02), a LayerNorm gain), ``offset`` (N(0, 0.02): an
    RMSNorm gain stored as its offset from 1, a bias)."""
    L, d, Lv, dv = spec.layers, spec.d, spec.vlayers, spec.vd
    q, kv = spec.heads * spec.head_dim, spec.kv_heads * spec.head_dim
    vq = spec.vheads * spec.vhead_dim
    vis = {
        "module": {
            "layers": {
                "ln1": {"w": ((Lv, dv), "gain"), "b": ((Lv, dv), "offset")},
                "attn": {"wq": ((Lv, dv, vq), "dense"),
                         "wk": ((Lv, dv, vq), "dense"),
                         "wv": ((Lv, dv, vq), "dense"),
                         "wo": ((Lv, vq, dv), "dense")},
                "ln2": {"w": ((Lv, dv), "gain"), "b": ((Lv, dv), "offset")},
                "mlp": {"w_up": ((Lv, dv, spec.vff), "dense"),
                        "w_down": ((Lv, spec.vff, dv), "dense")},
            },
            "final_ln": {"w": ((dv,), "gain"), "b": ((dv,), "offset")},
        },
        "projector": {"w1": ((dv, d), "dense")},
    }
    attn = {"wq": ((L, d, q), "dense"), "wk": ((L, d, kv), "dense"),
            "wv": ((L, d, kv), "dense"), "wo": ((L, q, d), "dense")}
    if spec.qkv_bias:
        attn.update(bq=((L, q), "offset"), bk=((L, kv), "offset"),
                    bv=((L, kv), "offset"))
    if spec.qk_norm:
        attn.update(qnorm=((L, spec.head_dim), "offset"),
                    knorm=((L, spec.head_dim), "offset"))
    llm = {
        "embed": ((spec.vocab, d), "dense"),
        "layers": {
            "ln1": {"w": ((L, d), "offset")}, "attn": attn,
            "ln2": {"w": ((L, d), "offset")},
            "mlp": {"w_up": ((L, d, spec.ff), "dense"),
                    "w_down": ((L, spec.ff, d), "dense"),
                    "w_gate": ((L, d, spec.ff), "dense")},
        },
        "final_ln": {"w": ((d,), "offset")},
    }
    if not spec.tied:
        llm["unembed"] = ((d, spec.vocab), "dense")
    return {"encoders": {"vision": vis}, "llm": llm}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_params(key, spec: Spec, dtype=jnp.bfloat16):
    """Every weight from ``key`` and its path; jit it with ``spec`` and
    ``dtype`` static to make the whole tree on the device in one call."""
    def leaf(path, sk):
        (shape, kind) = sk
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        z = jax.random.normal(k, shape, jnp.float32) * 0.02
        return (1.0 + z if kind == "gain" else z).astype(dtype)
    return jax.tree_util.tree_map_with_path(leaf, param_layout(spec),
                                            is_leaf=_is_leaf)


# ---------------------------------------------------------------------------
# Numerics: f32 at HIGHEST, or the fp8 control
# ---------------------------------------------------------------------------

def _round8(x):
    """Round to float8 e4m3 with a per-tensor scale (amax -> 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _q8(x):
    """A matmul operand in float8: rounded on the way forward, and its
    gradient rounded the same way on the way back (a cast's own
    gradient would carry unscaled cotangents through float8, where
    they underflow)."""
    return _round8(x)


_q8.defvjp(lambda x: (_round8(x), None), lambda _, g: (_round8(g),))


def _mm(numerics: str, eq: str, a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if numerics == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, H, hd]; rotate-half RoPE, frequencies theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _attend(numerics, q, k, v, allowed):
    """q [T, H, hd], k/v [T, H, hd], allowed [T, T] bool."""
    hd = q.shape[-1]
    s = _mm(numerics, "qhd,khd->hqk", q, k) / math.sqrt(hd)
    s = jnp.where(allowed[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return _mm(numerics, "hqk,khd->qhd", p, v)


# ---------------------------------------------------------------------------
# The model, one row at a time
# ---------------------------------------------------------------------------

def encoder_forward(spec: Spec, numerics: str, vp, emb):
    """Frozen EVA-CLIP tower: emb [n, vd] -> [n, vd] (f32)."""
    n = emb.shape[0]
    full = jnp.ones((n, n), bool)

    def layer(x, lp):
        h = _ln(x, lp["ln1"]["w"], lp["ln1"]["b"], spec.ln_eps)
        a = lp["attn"]
        q = _mm(numerics, "td,de->te", h, a["wq"]).reshape(
            n, spec.vheads, spec.vhead_dim)
        k = _mm(numerics, "td,de->te", h, a["wk"]).reshape(
            n, spec.vheads, spec.vhead_dim)
        v = _mm(numerics, "td,de->te", h, a["wv"]).reshape(
            n, spec.vheads, spec.vhead_dim)
        o = _attend(numerics, q, k, v, full).reshape(n, -1)
        x = x + _mm(numerics, "te,ed->td", o, a["wo"])
        h = _ln(x, lp["ln2"]["w"], lp["ln2"]["b"], spec.ln_eps)
        u = jax.nn.gelu(_mm(numerics, "td,df->tf", h, lp["mlp"]["w_up"]),
                        approximate=True)
        return x + _mm(numerics, "tf,fd->td", u, lp["mlp"]["w_down"]), None

    x, _ = jax.lax.scan(layer, emb.astype(jnp.float32),
                        vp["module"]["layers"])
    fl = vp["module"]["final_ln"]
    return _ln(x, fl["w"], fl["b"], spec.ln_eps)


def merge_geometry(text_len: int, image_at: int, n_img: int):
    """Static geometry of one merged row: text[:image_at], the image,
    text[image_at:]. Returns (text slots, image offset, allowed mask)."""
    T = text_len + n_img
    idx = np.arange(T)
    is_img = (idx >= image_at) & (idx < image_at + n_img)
    text_slots = idx[~is_img]
    # text rows: causal over every earlier position (text or image);
    # image rows: every position of the same image, nothing else
    causal = idx[None, :] <= idx[:, None]
    allowed = np.where(is_img[:, None], is_img[None, :], causal)
    return text_slots, image_at, allowed


def llm_row_nll(spec: Spec, numerics: str, lp, img, tokens, labels,
                geometry):
    """Sum of the text positions' negative log-likelihoods for one row.
    img [n_img, d] projected image tokens; tokens/labels [text_len]."""
    text_slots, img_off, allowed = geometry
    n_img = img.shape[0]
    T = len(text_slots) + n_img
    emb = lp["embed"]
    x = jnp.zeros((T, spec.d), jnp.float32)
    x = x.at[jnp.asarray(text_slots)].set(emb[tokens].astype(jnp.float32))
    x = jax.lax.dynamic_update_slice(x, img.astype(jnp.float32),
                                     (img_off, 0))
    pos = jnp.arange(T)
    allowed = jnp.asarray(allowed)
    rep = spec.heads // spec.kv_heads

    def layer(x, p):
        h = _rms(x, p["ln1"]["w"], spec.rms_eps)
        a = p["attn"]
        q = _mm(numerics, "td,de->te", h, a["wq"])
        k = _mm(numerics, "td,de->te", h, a["wk"])
        v = _mm(numerics, "td,de->te", h, a["wv"])
        if spec.qkv_bias:
            q = q + a["bq"].astype(jnp.float32)
            k = k + a["bk"].astype(jnp.float32)
            v = v + a["bv"].astype(jnp.float32)
        q = q.reshape(T, spec.heads, spec.head_dim)
        k = k.reshape(T, spec.kv_heads, spec.head_dim)
        v = v.reshape(T, spec.kv_heads, spec.head_dim)
        if spec.qk_norm:
            q = _rms(q, a["qnorm"], spec.rms_eps)
            k = _rms(k, a["knorm"], spec.rms_eps)
        q = _rope(q, pos, spec.rope_theta)
        k = _rope(k, pos, spec.rope_theta)
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        o = _attend(numerics, q, k, v, allowed).reshape(T, -1)
        x = x + _mm(numerics, "te,ed->td", o, a["wo"])
        h = _rms(x, p["ln2"]["w"], spec.rms_eps)
        m = p["mlp"]
        g = jax.nn.silu(_mm(numerics, "td,df->tf", h, m["w_gate"]))
        u = _mm(numerics, "td,df->tf", h, m["w_up"])
        return x + _mm(numerics, "tf,fd->td", g * u, m["w_down"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, lp["layers"])
    h = _rms(x, lp["final_ln"]["w"], spec.rms_eps)
    ht = h[jnp.asarray(text_slots)]
    head = emb.T if spec.tied else lp["unembed"]
    n = ht.shape[0]
    c = CE_CHUNK if n % CE_CHUNK == 0 else n

    def ce(tot, xs):
        hc, lc = xs
        logits = _mm(numerics, "td,dv->tv", hc, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return tot + jnp.sum(lse - ll), None

    tot, _ = jax.lax.scan(jax.checkpoint(ce), jnp.float32(0.0),
                          (ht.reshape(n // c, c, -1),
                           labels.reshape(n // c, c)))
    return tot


def _row_fns(spec: Spec, numerics: str, geometry):
    @jax.jit
    def encode(vp, emb):
        return encoder_forward(spec, numerics, vp, emb)

    @jax.jit
    def row_grad(w, lp, enc, tokens, labels):
        def f(w):
            img = _mm(numerics, "td,de->te", enc, w)
            return llm_row_nll(spec, numerics, lp, img, tokens, labels,
                               geometry)
        return jax.value_and_grad(f)(w)
    return encode, row_grad


def train_steps(spec: Spec, optim: Optim, params, batches: List[dict], *,
                image_at: int, numerics: str = "f32",
                fault: Optional[str] = None) -> Dict[str, Any]:
    """Run the first ``len(batches)`` steps. ``params`` is the weight
    tree of :func:`init_params` (bfloat16 values). Returns the losses,
    the first step's gradient as the optimizer takes it (after
    clipping) and the projector's change over all steps, as float64
    numpy arrays keyed by leaf path."""
    enc_p = params["encoders"]["vision"]
    lp = params["llm"]
    w0 = np.asarray(enc_p["projector"]["w1"].astype(jnp.float32))
    w = jnp.asarray(w0)
    text_len = batches[0]["text_tokens"].shape[1]
    geometry = merge_geometry(text_len, image_at, spec.v_tokens)
    encode, row_grad = _row_fns(spec, numerics, geometry)
    m = jnp.zeros_like(w)
    v = jnp.zeros_like(w)
    losses, g_first, gnorm_first = [], None, None
    dtype = enc_p["projector"]["w1"].dtype
    for t, batch in enumerate(batches, start=1):
        rows = batch["text_tokens"].shape[0]
        if fault == "half_batch":
            rows = max(rows // 2, 1)
        tot, grad = 0.0, jnp.zeros_like(w)
        count = rows * text_len
        for r in range(rows):
            enc = encode(enc_p, jnp.asarray(batch["vision_embeds"][r]))
            s, g = row_grad(w, lp, enc,
                            jnp.asarray(batch["text_tokens"][r]),
                            jnp.asarray(batch["labels"][r]))
            tot += float(s)
            grad = grad + g
        loss = tot / count
        grad = grad / count
        gnorm = float(jnp.sqrt(jnp.sum(jnp.square(grad))))
        clip = min(1.0, optim.grad_clip / max(gnorm, 1e-9)) \
            if optim.grad_clip else 1.0
        g = grad * clip
        if g_first is None:
            g_first, gnorm_first = np.asarray(g, np.float64), gnorm
        m = optim.b1 * m + (1 - optim.b1) * g
        v = optim.b2 * v + (1 - optim.b2) * g * g
        mh = m / (1 - optim.b1 ** t)
        vh = v / (1 - optim.b2 ** t)
        delta = mh / (jnp.sqrt(vh) + optim.eps) + optim.weight_decay * w
        # the configuration keeps its parameters in bfloat16: the update
        # is computed in float32 and stored back in the parameters' dtype
        w = (w - optim.lr_at(t) * delta).astype(dtype).astype(jnp.float32)
        losses.append(loss)
    key = "encoders/vision/projector/w1"
    return {"losses": losses, "gnorm": gnorm_first, "grad": {key: g_first},
            "change": {key: np.asarray(w, np.float64) - w0}}
