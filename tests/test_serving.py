"""Serving subsystem tests: paged flash-decode kernel parity vs the XLA
references (GQA x softcap x window x multimodal bitfields), masked-page
grid compaction, the continuous batching engine's determinism, the
ContextPlan prefill handoff, and the ragged dense decode_step fix."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import bam
from repro.models import api
from repro.models import transformer as T
from repro.parallel import plan_context
from repro.serving import (NULL_PAGE, PageTable, ServingEngine,
                           build_decode_grid, decode_grid_bucket,
                           init_paged_cache)
from repro.kernels.paged_decode import (paged_decode_attention,
                                        paged_decode_ref)


def tiny_cfg(**kw):
    base = dict(name="tiny-serve", family="dense", num_layers=2, d_model=32,
                num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                dtype="float32", remat=False, seq_shard_activations=False)
    base.update(kw)
    return ModelConfig(**base)


# ---------------------------------------------------------------------------
# Kernel parity: paged_decode_attention (interpret) vs paged_decode_ref
# ---------------------------------------------------------------------------

def _paged_fixture(page_size, Hkv, hd, layouts, seed=0):
    """Build a page pool holding one request per multimodal layout.
    Returns (table, k_pages, v_pages, rids). ``layouts`` are
    build_sample_bits segment lists."""
    rng = np.random.default_rng(seed)
    total_pages = 1 + sum(
        -(-sum(s[2] for s in segs) // page_size) for segs in layouts)
    table = PageTable(total_pages + 2, page_size)
    for rid, segs in enumerate(layouts):
        n = sum(s[2] for s in segs)
        bits, pos = bam.build_sample_bits(segs, n)
        table.alloc(rid, n)
        table.write(rid, np.arange(n), bits, pos)
    P = table.num_pages
    k_pages = jnp.asarray(rng.normal(size=(P, Hkv, page_size, hd)),
                          jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(P, Hkv, page_size, hd)),
                          jnp.float32)
    return table, k_pages, v_pages, list(range(len(layouts)))


LAYOUTS = [
    [("text", 0, 5), ("mod", 1, 8), ("text", 0, 6)],
    [("text", 0, 9), ("newdoc", 0, 0), ("text", 0, 4)],
]


@pytest.mark.parametrize("H,Hkv", [(4, 4), (4, 2), (8, 2)])
@pytest.mark.parametrize("softcap", [0.0, 20.0])
@pytest.mark.parametrize("window", [0, 4])
def test_kernel_parity(H, Hkv, softcap, window):
    page_size, hd = 8, 16
    table, k_pages, v_pages, rids = _paged_fixture(
        page_size, Hkv, hd, LAYOUTS)
    rng = np.random.default_rng(1)
    B = len(rids) + 1                      # + one empty batch row
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    # text queries continuing each request (window semantics only
    # constrain text queries, so text is the apples-to-apples case);
    # request 0's query also attends its modality-1 stream; request 1's
    # continues its second document (instance 1, positions restart)
    q_bits = np.array([bam.text_token((1,)), bam.text_token(instance=1), 0],
                      np.uint32)[:, None]
    q_pos = np.array([[19], [4], [0]], np.int32)

    grid = build_decode_grid(table, rids + [None], q_bits[:, 0],
                             q_pos[:, 0], window=window,
                             pad_to=decode_grid_bucket(16))
    kv_bits = jnp.asarray(table.bits)
    kv_pos = jnp.asarray(table.pos)
    out_k = paged_decode_attention(
        q, k_pages, v_pages, jnp.asarray(q_bits), jnp.asarray(q_pos),
        kv_bits, kv_pos, grid.arrays(), softcap=softcap, window=window,
        interpret=True)
    mp = max(len(table.pages_of(r)) for r in rids)
    pt = np.stack([table.page_table_row(r, mp) for r in rids]
                  + [np.full(mp, NULL_PAGE, np.int32)])
    out_r = paged_decode_ref(
        q, k_pages, v_pages, jnp.asarray(q_bits), jnp.asarray(q_pos),
        kv_bits, kv_pos, jnp.asarray(pt), softcap=softcap, window=window)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5)
    assert np.asarray(out_k[0]).any()               # row 0 nonzero
    assert np.asarray(out_k[2] == 0).all()          # empty row exactly 0


def test_masked_pages_skipped():
    """A text query that does not attend the modality stream must not
    visit the image-only pages: the grid provably drops those steps and
    the kernel still matches the dense-gather reference."""
    page_size, Hkv, hd = 8, 2, 16
    # one image-heavy request: 8 text + 16 image + 8 text = 2 pure
    # image pages out of 4
    table, k_pages, v_pages, rids = _paged_fixture(
        page_size, Hkv, hd,
        [[("text", 0, 8), ("mod", 1, 16), ("text", 0, 8)]])
    q_bits_blind = np.array([[bam.text_token()]], np.uint32)
    q_bits_vis = np.array([[bam.text_token((1,))]], np.uint32)
    q_pos = np.array([[32]], np.int32)

    g_blind = build_decode_grid(table, rids, q_bits_blind[:, 0],
                                q_pos[:, 0])
    g_vis = build_decode_grid(table, rids, q_bits_vis[:, 0], q_pos[:, 0])
    assert g_vis.n_active_steps == 4          # every resident page
    assert g_blind.n_active_steps == 2        # image pages compacted out
    assert g_blind.n_dense_steps == 4
    assert g_blind.skip_fraction == pytest.approx(0.5)

    q = jnp.asarray(np.random.default_rng(2).normal(size=(1, 4, hd)),
                    jnp.float32)
    for qb, grid in ((q_bits_blind, g_blind), (q_bits_vis, g_vis)):
        out_k = paged_decode_attention(
            q, k_pages, v_pages, jnp.asarray(qb), jnp.asarray(q_pos),
            jnp.asarray(table.bits), jnp.asarray(table.pos),
            grid.arrays(), interpret=True)
        pt = table.page_table_row(rids[0], 4)[None]
        out_r = paged_decode_ref(
            q, k_pages, v_pages, jnp.asarray(qb), jnp.asarray(q_pos),
            jnp.asarray(table.bits), jnp.asarray(table.pos),
            jnp.asarray(pt))
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# Engine vs dense decode_step, determinism, CP handoff
# ---------------------------------------------------------------------------

def _dense_generate(params, cfg, prompt, max_new, Tmax=64):
    cache = T.init_cache(cfg, 1, Tmax)
    logits = None
    for t, tok in enumerate(prompt):
        batch = {"tokens": jnp.asarray([[int(tok)]], jnp.int32),
                 "positions": jnp.asarray([[t]], jnp.int32)}
        logits, cache = T.decode_step(params, cfg, cache, batch)
    out = [int(jnp.argmax(logits[0, 0]))]
    for i in range(max_new - 1):
        batch = {"tokens": jnp.asarray([[out[-1]]], jnp.int32),
                 "positions": jnp.asarray([[len(prompt) + i]], jnp.int32)}
        logits, cache = T.decode_step(params, cfg, cache, batch)
        out.append(int(jnp.argmax(logits[0, 0])))
    return out


@pytest.mark.parametrize("cfg_kw", [
    dict(attn_softcap=10.0),
    dict(decode_kv_replicate=4),
    dict(sliding_window=6, local_global_pattern=2, attn_softcap=10.0),
])
def test_engine_matches_dense_decode(cfg_kw):
    cfg = tiny_cfg(**cfg_kw)
    params = api.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 64, size=n) for n in (7, 12)]
    ref = [_dense_generate(params, cfg, p, 4) for p in prompts]
    for attn in ("xla", "interpret"):
        eng = ServingEngine(params, cfg, num_pages=24, page_size=8,
                            max_batch=3, attn=attn)
        rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        got = eng.run()
        assert [got[r] for r in rids] == ref, attn


def test_engine_determinism_continuous_vs_sequential():
    """Continuous batching must be composition-invariant: the tokens a
    request generates do not depend on which other requests share its
    batch. Batched engine == one-request-at-a-time engine."""
    cfg = tiny_cfg(attn_softcap=10.0)
    params = api.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 64, size=n) for n in (5, 11, 3, 8)]

    eng = ServingEngine(params, cfg, num_pages=48, page_size=8,
                        max_batch=4, attn="xla")
    rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    batched = [eng.run()[r] for r in rids]

    solo = []
    for p in prompts:
        e1 = ServingEngine(params, cfg, num_pages=48, page_size=8,
                           max_batch=1, attn="xla")
        r = e1.submit(p, max_new_tokens=5)
        solo.append(e1.run()[r])
    assert batched == solo


def test_engine_multimodal_and_page_reuse():
    """Multimodal prompts decode through the kernel path, and pages
    freed by finished requests are reused with scrubbed metadata (a
    later request over recycled pages matches a fresh engine)."""
    cfg = tiny_cfg(attn_softcap=10.0)
    params = api.init(jax.random.PRNGKey(0), cfg)
    segs = [("text", 0, 4), ("mod", 1, 8), ("text", 0, 4)]
    bits, pos = bam.build_sample_bits(segs, 16)
    prompt = np.arange(1, 17, dtype=np.int32)

    def run(engine, gen_bits):
        rid = engine.submit(prompt, bits=bits, positions=pos,
                            max_new_tokens=4, gen_bits=gen_bits)
        return engine.run()[rid]

    gb = bam.text_token((1,))
    eng = ServingEngine(params, cfg, num_pages=8, page_size=8,
                        max_batch=2, attn="interpret")
    first = run(eng, gb)
    # pool is 7 allocatable pages; the first request used 3 and freed
    # them — the rerun must land on recycled pages and match exactly
    second = run(eng, gb)
    fresh = run(ServingEngine(params, cfg, num_pages=8, page_size=8,
                              max_batch=2, attn="interpret"), gb)
    assert first == second == fresh
    assert eng.table.num_free == 7


def test_cp_plan_prefill_layout_equivalence():
    """A ContextPlan-permuted prefill writes the same decode state:
    generation from a plan-laid-out cache matches the identity layout,
    and the request's pages carry rank ownership."""
    cfg = tiny_cfg(attn_softcap=10.0)
    params = api.init(jax.random.PRNGKey(0), cfg)
    Tp = 16
    prompt = np.arange(1, Tp + 1, dtype=np.int32)
    bits = np.full(Tp, bam.text_token(), np.uint32)
    pos = np.arange(Tp, dtype=np.int32)
    plan = plan_context(bits, pos, num_ranks=2, block_size=4)

    outs = {}
    for key, p in (("plain", None), ("plan", plan)):
        eng = ServingEngine(params, cfg, num_pages=16, page_size=4,
                            max_batch=1, attn="xla")
        rid = eng.submit(prompt, max_new_tokens=4, plan=p)
        eng.step()                       # prefill only
        if p is not None:
            owners = eng.table.page_owner[eng.table.pages_of(rid)[:4]]
            assert set(owners.tolist()) == {0, 1}
        outs[key] = eng.run()[rid]
    assert outs["plan"] == outs["plain"]


# ---------------------------------------------------------------------------
# Satellites: ragged dense decode_step + _cache_cfg ValueError
# ---------------------------------------------------------------------------

def test_decode_step_ragged_rows():
    """Regression: decode_step used row 0's position for every row's
    cache insert. Two requests at staggered lengths batched together
    must produce the same logits as each decoded alone."""
    cfg = tiny_cfg(attn_softcap=10.0)
    params = api.init(jax.random.PRNGKey(0), cfg)
    Tmax = 16
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 64, size=3), rng.integers(1, 64, size=7)]

    caches, solo_logits = [], []
    for p in prompts:
        cache = T.init_cache(cfg, 1, Tmax)
        for t, tok in enumerate(p):
            batch = {"tokens": jnp.asarray([[int(tok)]], jnp.int32),
                     "positions": jnp.asarray([[t]], jnp.int32)}
            logits, cache = T.decode_step(params, cfg, cache, batch)
        caches.append(cache)
        solo_logits.append(logits)

    stacked = jax.tree.map(
        lambda a, b: jnp.concatenate([a, b],
                                     axis=1 if a.ndim == 5 else 0),
        caches[0], caches[1])
    # replay the *last* token of each prompt batched at ragged rows:
    # rewind each row's final insert by scrubbing its bits slot
    cur = jnp.asarray([[len(prompts[0]) - 1], [len(prompts[1]) - 1]],
                      jnp.int32)
    stacked["bits"] = stacked["bits"].at[
        jnp.arange(2), cur[:, 0]].set(jnp.uint32(0))
    batch = {"tokens": jnp.asarray([[int(prompts[0][-1])],
                                    [int(prompts[1][-1])]], jnp.int32),
             "positions": cur}
    logits, new_cache = T.decode_step(params, cfg, stacked, batch)
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(solo_logits[0][0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(logits[1]),
                               np.asarray(solo_logits[1][0]), atol=1e-5)
    # and each row's K/V landed at its own offset: bits restored
    for i, p in enumerate(prompts):
        got = np.asarray(new_cache["bits"][i, :len(p)])
        assert (got != 0).all()
        assert not np.asarray(new_cache["bits"][i, len(p):]).any()


def test_cache_cfg_divisibility_valueerror():
    cfg = tiny_cfg(num_heads=4, num_kv_heads=2, decode_kv_replicate=3)
    with pytest.raises(ValueError) as e:
        T.init_cache(cfg, 1, 8)
    assert "decode_kv_replicate=3" in str(e.value)
    assert "num_heads=4" in str(e.value)


def test_paged_cache_guards():
    table = PageTable(4, 4)
    table.alloc(0, 12)                    # all 3 allocatable pages
    with pytest.raises(RuntimeError, match="exhausted"):
        table.alloc(1, 4)
    with pytest.raises(IndexError):
        table.coords(0, [12])
    table.free(0)
    assert table.num_free == 3
    cfg = tiny_cfg()
    cache = init_paged_cache(cfg, 4, 4)
    assert cache["k"].shape == (2, 4, 2, 4, 8)
    assert int(cache["bits"].sum()) == 0


def test_submit_rejects_infeasible_page_budget():
    """A request whose prompt+max_new page budget exceeds the whole
    pool must be rejected AT SUBMIT with a structured error — not sit
    at the head of the FIFO forever waiting for pages that can never
    free up (the engine would spin to max_ticks)."""
    from repro.serving import InfeasibleRequest
    cfg = tiny_cfg()
    params = api.init(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, num_pages=4, page_size=4,
                        max_batch=2)
    # capacity = 3 pages (page 0 is the null page) = 12 tokens;
    # 8 prompt tokens + 15 generated - 1 = 22 cached tokens -> 6 pages
    with pytest.raises(InfeasibleRequest) as e:
        eng.submit(np.arange(8) % cfg.vocab_size, max_new_tokens=15)
    err = e.value
    assert err.needed_pages == 6 and err.capacity == 3
    assert err.prompt_len == 8 and err.max_new_tokens == 15
    assert "never" in str(err)
    # nothing was queued, no rid leaked, and the engine still serves
    # feasible work afterwards
    assert not eng.queue and not eng.requests
    rid = eng.submit(np.arange(6) % cfg.vocab_size, max_new_tokens=4)
    assert rid == 0
    out = eng.run()
    assert len(out[rid]) == 4
