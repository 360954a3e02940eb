"""kernellint: static + small-case checks over the Pallas kernels.

Pallas BlockSpec mistakes are brutal to debug at runtime (shape errors
deep inside Mosaic, or silent garbage on the interpret path), and the
grid-compaction machinery has a correctness obligation no type system
sees: the compacted grid must cover every tile the bitfield mask
allows. This module checks both *before* a kernel ever runs.

AST rules (over ``src/repro/kernels/*.py`` — or any source handed to
:func:`lint_source`):

* ``blockspec-index-arity`` — every ``pl.BlockSpec`` index map that
  appears inside a grid-bearing call (``pl.pallas_call(grid=...)`` or
  ``pltpu.PrefetchScalarGridSpec(grid=...)``) must take exactly
  ``len(grid)`` arguments, plus ``num_scalar_prefetch`` more for
  scalar-prefetch grids. Named index maps are resolved against every
  ``def`` in the module (any nesting depth).
* ``blockspec-rank-mismatch`` — a BlockSpec's block-shape tuple and
  its index map's returned tuple must have the same length.

Both rules only fire on statically decidable sites (literal grids,
literal spec lists, lambdas or resolvable names) — undecidable sites
are skipped, never guessed at.

Small-case dynamic rules (numpy-only, no kernel launch):

* ``block-map-coverage`` — exhaustive check on small shapes that
  ``bam.build_block_map`` grids cover every (q, k) pair
  ``bam.allowed_mask`` allows, in BOTH the q-major and k-major
  orderings, and that ``first``/``last`` flags frame each major
  block's steps correctly (accumulator init/flush).
* ``scalar-prefetch-static`` — ``BlockMask`` must stay hashable (it
  rides through ``jax.custom_vjp`` as a static argument) and its
  prefetch arrays must be int32.
* ``block-shape-divides`` — the kernel wrapper's padding really does
  round every sequence axis up to a block multiple (the property every
  BlockSpec shape in the file relies on).
* ``decode-grid-coverage`` — the serving decode grid
  (``serving.paged_cache.build_decode_grid``) visits every physical
  page the dense mask allows, frames each batch row exactly once,
  routes inactive/pad steps to the null page, and keeps ``pad_to``
  steps inert.
* ``page-grid-divisibility`` — page-table allocations are whole pages,
  the flat KV view is exactly page-padded, and
  ``paged_decode_attention`` rejects operands whose shapes disagree
  with the pool before any kernel is built.
* ``tpu-block-tiling`` — Mosaic's tiling rule, checked on the CPU where
  no TPU compiler need be present: in every ``pallas_call`` the kernel
  wrappers trace at LLM-S widths, the last two dimensions of each VMEM
  block are multiples of (8, 128) or equal to the array's. Interpret
  mode accepts any block, so only this rule (or a compile for the chip)
  catches a layout the chip refuses.
"""
from __future__ import annotations

import ast
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .findings import Finding, finding, register_rule
from .jaxprlint import iter_jaxprs

register_rule("blockspec-index-arity", "kernellint",
              "BlockSpec index maps take grid-rank (+ scalar-prefetch) "
              "arguments")
register_rule("blockspec-rank-mismatch", "kernellint",
              "BlockSpec block shapes and index-map results have the "
              "same rank")
register_rule("block-map-coverage", "kernellint",
              "build_block_map grids cover every tile the bitfield "
              "mask allows")
register_rule("scalar-prefetch-static", "kernellint",
              "scalar-prefetch operands are hashable/static")
register_rule("block-shape-divides", "kernellint",
              "kernel-wrapper padding rounds sequence axes to block "
              "multiples")
register_rule("decode-grid-coverage", "kernellint",
              "build_decode_grid visits every page the bitfield mask "
              "allows and frames each batch row exactly once")
register_rule("page-grid-divisibility", "kernellint",
              "page-table capacity, pool shapes, and the decode "
              "kernel's page blocks agree on page_size")
register_rule("tpu-block-tiling", "kernellint",
              "the last two dimensions of every Pallas block are "
              "multiples of (8, 128) or equal to the array's")

#: Mosaic's minimum tile: (sublanes, lanes) of a 32-bit vector register
TPU_TILE = (8, 128)

KERNELS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "kernels")


# ---------------------------------------------------------------------------
# AST rules
# ---------------------------------------------------------------------------

def _call_name(node: ast.Call) -> str:
    """Trailing attribute name of the called function ('pallas_call',
    'BlockSpec', ...), however it is qualified."""
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    if isinstance(node.func, ast.Name):
        return node.func.id
    return ""


def _collect_defs(tree: ast.AST) -> Dict[str, List[ast.FunctionDef]]:
    defs: Dict[str, List[ast.FunctionDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, []).append(node)
    return defs


def _positional_arity(args: ast.arguments) -> int:
    """Grid-index arity of an index map: positional args minus any
    defaulted trailing ones (``lambda b, h, iq, ik, n_rep=n_rep: ...``
    is the standard closure-capture idiom — the defaulted arg is a
    captured constant, not a grid index)."""
    return len(args.posonlyargs) + len(args.args) - len(args.defaults)


def _return_tuple_len(fn: ast.AST) -> Optional[int]:
    """Length of the tuple a lambda/def returns, when statically
    known."""
    if isinstance(fn, ast.Lambda):
        body = fn.body
        return len(body.elts) if isinstance(body, ast.Tuple) else None
    if isinstance(fn, ast.FunctionDef):
        rets = [n for n in ast.walk(fn) if isinstance(n, ast.Return)]
        if len(rets) == 1 and isinstance(rets[0].value, ast.Tuple):
            return len(rets[0].value.elts)
    return None


def _iter_blockspecs(node: ast.AST):
    """Every pl.BlockSpec(...) Call lexically under ``node``."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and _call_name(sub) == "BlockSpec":
            yield sub


def _kw(node: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


def lint_source(src: str, filename: str = "<source>") -> List[Finding]:
    """Run the AST rules over one Python source string."""
    out: List[Finding] = []
    tree = ast.parse(src, filename=filename)
    defs = _collect_defs(tree)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if name not in ("pallas_call", "PrefetchScalarGridSpec",
                        "GridSpec"):
            continue
        grid = _kw(node, "grid")
        if not isinstance(grid, ast.Tuple):
            continue                       # grid not a literal: skip
        rank = len(grid.elts)
        n_prefetch = 0
        if name == "PrefetchScalarGridSpec":
            pf = _kw(node, "num_scalar_prefetch")
            if isinstance(pf, ast.Constant) and \
                    isinstance(pf.value, int):
                n_prefetch = pf.value
            else:
                continue                   # undecidable prefetch count
        expected = rank + n_prefetch

        for spec in _iter_blockspecs(node):
            loc = f"{filename}:{spec.lineno}"
            if len(spec.args) < 2:
                continue                   # BlockSpec() defaults: skip
            shape, index_map = spec.args[0], spec.args[1]
            arity: Optional[int] = None
            ret_len: Optional[int] = None
            if isinstance(index_map, ast.Lambda):
                arity = _positional_arity(index_map.args)
                ret_len = _return_tuple_len(index_map)
            elif isinstance(index_map, ast.Name):
                cands = defs.get(index_map.id, [])
                arities = {_positional_arity(fn.args) for fn in cands}
                if len(arities) == 1:
                    arity = arities.pop()
                lens = {_return_tuple_len(fn) for fn in cands}
                if len(lens) == 1:
                    ret_len = lens.pop()
            if arity is not None and arity != expected:
                out.append(finding(
                    "blockspec-index-arity", loc,
                    f"index map takes {arity} args but the grid is "
                    f"rank {rank}"
                    + (f" with {n_prefetch} scalar-prefetch operands "
                       f"(expected {expected})" if n_prefetch
                       else f" (expected {expected})")))
            if ret_len is not None and isinstance(shape, ast.Tuple) \
                    and ret_len != len(shape.elts):
                out.append(finding(
                    "blockspec-rank-mismatch", loc,
                    f"block shape is rank {len(shape.elts)} but the "
                    f"index map returns {ret_len} coordinates"))
    return out


def lint_file(path: str) -> List[Finding]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    return lint_source(src, os.path.relpath(path))


# ---------------------------------------------------------------------------
# Dynamic small-case rules
# ---------------------------------------------------------------------------

#: small multimodal layouts covering text-only, modality islands,
#: interleave, multi-doc, and pad-tail cases (kind, modality, length)
_COVERAGE_LAYOUTS: Tuple[Tuple[Tuple[str, int, int], ...], ...] = (
    (("text", 0, 12),),
    (("text", 0, 4), ("mod", 1, 6), ("text", 0, 2)),
    (("mod", 1, 5), ("mod", 2, 4), ("text", 0, 3)),
    (("text", 0, 3), ("newdoc", 0, 0), ("text", 0, 5), ("mod", 1, 3)),
    (("mod", 1, 7), ("text", 0, 2)),                    # pad tail below
)


def check_block_map_coverage(layouts=_COVERAGE_LAYOUTS,
                             block_sizes: Sequence[int] = (4, 8),
                             windows: Sequence[int] = (0, 3),
                             seq_len: int = 14) -> List[Finding]:
    """Exhaustive small-case proof obligation: every (q, k) pair the
    dense ``allowed_mask`` allows must land in an active tile of
    ``build_block_map``'s compacted grid — in both orderings — and the
    first/last flags must frame each major index's steps exactly once."""
    from repro.core import bam
    out: List[Finding] = []
    for li, segs in enumerate(layouts):
        bits, pos = bam.build_sample_bits(list(segs), seq_len)
        dense = np.asarray(bam.allowed_mask(bits[None], bits[None],
                                            pos[None], pos[None]))[0]
        for bq in block_sizes:
            for bk in block_sizes:
                for w in windows:
                    if w:
                        dense_w = np.asarray(bam.allowed_mask(
                            bits[None], bits[None], pos[None],
                            pos[None], window=w))[0]
                    else:
                        dense_w = dense
                    bm = bam.build_block_map(bits, bits, pos, pos,
                                             bq, bk, window=w)
                    loc = (f"layout{li} bq={bq} bk={bk} window={w}")
                    out += _coverage_findings(dense_w, bm, bq, bk, loc)
    return out


def _coverage_findings(dense: np.ndarray, bm, bq: int, bk: int,
                       loc: str) -> List[Finding]:
    out: List[Finding] = []
    Tq, Tk = dense.shape
    active_q = {(iq, ik) for iq, ik, _f, _l, a in bm.q_steps if a}
    active_k = {(iq, ik) for iq, ik, _f, _l, a in bm.k_steps if a}
    qs, ks = np.nonzero(dense)
    needed = {(int(q) // bq, int(k) // bk) for q, k in zip(qs, ks)}
    for tile in sorted(needed - active_q):
        out.append(finding(
            "block-map-coverage", loc,
            f"q-major grid misses active tile (q_block={tile[0]}, "
            f"k_block={tile[1]}) — allowed pairs would be dropped"))
    for tile in sorted(needed - active_k):
        out.append(finding(
            "block-map-coverage", loc,
            f"k-major grid misses active tile (q_block={tile[0]}, "
            f"k_block={tile[1]})"))
    for major, steps, pick in (("q", bm.q_steps, 0),
                               ("k", bm.k_steps, 1)):
        seen: Dict[int, List[Tuple[int, int]]] = {}
        for step in steps:
            seen.setdefault(step[pick], []).append((step[2], step[3]))
        majors = bm.nq if major == "q" else bm.nk
        for i in range(majors):
            flags = seen.get(i, [])
            if not flags:
                out.append(finding(
                    "block-map-coverage", loc,
                    f"{major}-major grid has no step for "
                    f"{major}_block={i} — its output/grad rows are "
                    f"never initialized"))
                continue
            if sum(f for f, _l in flags) != 1 or \
                    sum(l for _f, l in flags) != 1 or \
                    flags[0][0] != 1 or flags[-1][1] != 1:
                out.append(finding(
                    "block-map-coverage", loc,
                    f"{major}-major first/last flags malformed for "
                    f"{major}_block={i}: {flags}"))
    return out


def check_scalar_prefetch_static() -> List[Finding]:
    """The compacted grid rides through ``jax.custom_vjp`` as a static
    argument — it must hash, compare by value, and produce int32
    prefetch operands."""
    from repro.core import bam
    out: List[Finding] = []
    bits, pos = bam.build_sample_bits(
        [("text", 0, 4), ("mod", 1, 4)], 8)
    bm = bam.build_block_map(bits, bits, pos, pos, 4, 4)
    try:
        hash(bm)
    except TypeError as e:
        out.append(finding(
            "scalar-prefetch-static", "bam.BlockMask",
            f"BlockMask is unhashable ({e}) — it cannot be a "
            f"custom_vjp static argument"))
        return out
    bm2 = bam.build_block_map(bits, bits, pos, pos, 4, 4)
    if bm != bm2 or hash(bm) != hash(bm2):
        out.append(finding(
            "scalar-prefetch-static", "bam.BlockMask",
            "equal BlockMasks do not compare/hash equal — jit "
            "caching on the static arg would always miss"))
    for major in ("q", "k"):
        for j, arr in enumerate(bm.arrays(major)):
            if arr.dtype != np.int32:
                out.append(finding(
                    "scalar-prefetch-static", "bam.BlockMask.arrays",
                    f"{major}-major prefetch operand {j} is "
                    f"{arr.dtype}, not int32"))
    return out


def check_block_divisibility(
        cases: Sequence[Tuple[int, int, int]] = ((40, 16, 16),
                                                 (40, 16, 8),
                                                 (7, 4, 4),
                                                 (64, 16, 16))
        ) -> List[Finding]:
    """The kernel wrapper pads every sequence axis to a block multiple
    before building its grid; block shapes must divide the padded dims
    for every (T, block_q, block_k) it will meet."""
    import jax.numpy as jnp
    from repro.kernels import ops
    out: List[Finding] = []
    for T, bq, bk in cases:
        q = jnp.zeros((1, T, 2, 4))
        bits = jnp.zeros((1, T), jnp.uint32)
        pos = jnp.zeros((1, T), jnp.int32)
        padded = ops._pad_all(q, q, q, bits, bits, pos, pos, bq, bk)
        qp, kp, vp, qb, kb = padded[0], padded[1], padded[2], \
            padded[3], padded[4]
        loc = f"ops._pad_all T={T} bq={bq} bk={bk}"
        if qp.shape[1] % bq or qb.shape[1] % bq:
            out.append(finding(
                "block-shape-divides", loc,
                f"q axis padded to {qp.shape[1]} — not a multiple of "
                f"block_q={bq}"))
        if kp.shape[1] % bk or vp.shape[1] % bk or kb.shape[1] % bk:
            out.append(finding(
                "block-shape-divides", loc,
                f"k axis padded to {kp.shape[1]} — not a multiple of "
                f"block_k={bk}"))
    return out


def check_decode_grid_coverage(layouts=_COVERAGE_LAYOUTS,
                               page_sizes: Sequence[int] = (4, 8),
                               seq_len: int = 14) -> List[Finding]:
    """Serving twin of ``check_block_map_coverage``: the decode grid's
    physical-page step list must visit every page holding a KV slot the
    dense mask allows, frame each batch row's steps exactly once
    (online-softmax init/flush), route every inactive or padding step
    to the null page, and give empty batch rows a flush step."""
    from repro.core import bam
    from repro.serving.paged_cache import (NULL_PAGE, PageTable,
                                           build_decode_grid,
                                           decode_grid_bucket)
    out: List[Finding] = []
    queries = (bam.text_token(), bam.text_token((1, 2)),
               bam.modality_token(1))
    for li, segs in enumerate(layouts):
        bits, pos = bam.build_sample_bits(list(segs), seq_len)
        for ps in page_sizes:
            table = PageTable(8, ps)
            table.alloc(0, seq_len)
            table.write(0, np.arange(seq_len), bits, pos)
            pages = table.pages_of(0)
            kv_bits, kv_pos = table.kv_view(0)
            for qi, qb in enumerate(queries):
                qp = int(pos.max()) + 1
                loc = f"layout{li} ps={ps} query{qi}"
                grid = build_decode_grid(
                    table, [0, None], np.array([qb, 0], np.uint32),
                    np.array([qp, 0], np.int32))
                dense = np.asarray(bam.allowed_mask(
                    np.array([[qb]], np.uint32), kv_bits[None],
                    np.array([[qp]], np.int32), kv_pos[None]))[0, 0]
                needed = {pages[int(s) // ps] for s in
                          np.nonzero(dense)[0]}
                active = {int(p) for p, r, a in
                          zip(grid.page, grid.req, grid.active)
                          if a and r == 0}
                for page in sorted(needed - active):
                    out.append(finding(
                        "decode-grid-coverage", loc,
                        f"grid never visits page {page} though the "
                        f"mask allows slots in it — KV would be "
                        f"dropped from the decode softmax"))
                for row in (0, 1):
                    sel = grid.req == row
                    f, l = grid.first[sel], grid.last[sel]
                    if f.sum() != 1 or l.sum() != 1 or not f[0] \
                            or not l[-1]:
                        out.append(finding(
                            "decode-grid-coverage", loc,
                            f"batch row {row} is not framed exactly "
                            f"once (first={f.tolist()}, "
                            f"last={l.tolist()}) — scratch init/flush "
                            f"would misfire"))
                if (grid.page[grid.active == 0] != NULL_PAGE).any():
                    out.append(finding(
                        "decode-grid-coverage", loc,
                        "inactive step points at a real page — it "
                        "would DMA data the kernel must not read"))
                padded = build_decode_grid(
                    table, [0, None], np.array([qb, 0], np.uint32),
                    np.array([qp, 0], np.int32),
                    pad_to=decode_grid_bucket(grid.n_steps + 1))
                pad = padded.arrays()
                if padded.n_active_steps != grid.n_active_steps or \
                        pad[4][grid.n_steps:].any() or \
                        pad[2][grid.n_steps:].any() or \
                        pad[3][grid.n_steps:].any():
                    out.append(finding(
                        "decode-grid-coverage", loc,
                        "pad_to steps are not inert (active/first/"
                        "last must all be 0 past the real steps)"))
            table.free(0)
    return out


def check_page_divisibility(
        cases: Sequence[Tuple[int, int]] = ((5, 4), (9, 8), (1, 4),
                                            (16, 8), (17, 8))
        ) -> List[Finding]:
    """Page arithmetic the decode kernel's BlockSpecs rely on: every
    allocation is a whole number of pages, the flat KV view is exactly
    page-padded, and the kernel wrapper rejects metadata whose shape
    disagrees with the pool's (P, page_size)."""
    import jax.numpy as jnp
    from repro.kernels.paged_decode import paged_decode_attention
    from repro.serving.paged_cache import PageTable
    out: List[Finding] = []
    for n_tokens, ps in cases:
        table = PageTable(16, ps)
        table.alloc(0, n_tokens)
        cap = table.capacity(0)
        loc = f"PageTable n_tokens={n_tokens} page_size={ps}"
        if cap % ps or cap < n_tokens:
            out.append(finding(
                "page-grid-divisibility", loc,
                f"capacity {cap} is not a page multiple covering "
                f"{n_tokens} tokens"))
        kv_bits, kv_pos = table.kv_view(0)
        if len(kv_bits) != cap or len(kv_pos) != cap:
            out.append(finding(
                "page-grid-divisibility", loc,
                f"kv_view length {len(kv_bits)} != page-padded "
                f"capacity {cap} — the kernel's page blocks would "
                f"run off the metadata"))
    # wrapper-side validation: shape disagreements must raise before
    # any pallas_call is built
    ps = 4
    q = jnp.zeros((1, 2, 8))
    pages = jnp.zeros((3, 2, ps, 8))
    bits_ok = jnp.zeros((3, ps), jnp.uint32)
    pos_ok = jnp.zeros((3, ps), jnp.int32)
    steps = tuple(jnp.zeros(2, jnp.int32) for _ in range(5))
    bad = (
        ("kv metadata off-page", dict(kv_bits=jnp.zeros((3, ps + 1),
                                                        jnp.uint32))),
        ("GQA non-divisible", dict(q=jnp.zeros((1, 3, 8)))),
        ("q metadata shape", dict(q_bits=jnp.zeros((2, 1), jnp.uint32))),
    )
    for label, override in bad:
        kw = dict(q=q, k_pages=pages, v_pages=pages,
                  q_bits=jnp.zeros((1, 1), jnp.uint32),
                  q_pos=jnp.zeros((1, 1), jnp.int32),
                  kv_bits=bits_ok, kv_pos=pos_ok, steps=steps)
        kw.update(override)
        try:
            paged_decode_attention(**kw)
        except ValueError:
            continue
        out.append(finding(
            "page-grid-divisibility", f"paged_decode_attention {label}",
            "mismatched operand accepted — the kernel would index "
            "out of bounds at runtime"))
    return out


def _block_dim(d) -> Optional[int]:
    """A BlockMapping dimension as an int; None for a squeezed one."""
    size = getattr(d, "block_size", d)
    return size if isinstance(size, int) else None


def tiling_findings(jaxpr: Any, location: str) -> List[Finding]:
    """``tpu-block-tiling`` over every ``pallas_call`` in ``jaxpr``
    (nested jaxprs included): each VMEM block's last two dimensions must
    be multiples of :data:`TPU_TILE` or equal to the array's."""
    out: List[Finding] = []
    pallas_calls = [eqn for sub in iter_jaxprs(jaxpr) for eqn in sub.eqns
                    if eqn.primitive.name == "pallas_call"]
    for eqn in pallas_calls:
        name = eqn.params.get("name_and_src_info", "pallas_call")
        for bm in eqn.params["grid_mapping"].block_mappings:
            space = getattr(bm.block_aval, "memory_space", None)
            if space is not None and "SMEM" in str(space).upper():
                continue
            block = [_block_dim(d) for d in bm.block_shape]
            array = list(bm.array_aval.shape)
            for axis, tile in zip((-1, -2), (TPU_TILE[1], TPU_TILE[0])):
                if len(block) < -axis:
                    continue
                b, a = block[axis], array[axis]
                if b is None or b == a or b % tile == 0:
                    continue
                out.append(finding(
                    "tpu-block-tiling", f"{location}: {name} {bm.origin}",
                    f"block {tuple(block)} on array {tuple(array)}: "
                    f"dimension {axis} is {b}, neither a multiple of "
                    f"{tile} nor the array's {a} — Mosaic refuses it"))
    return out


def check_tpu_block_tiling(B: int = 1, T: int = 256, H: int = 16,
                           Hkv: int = 4, hd: int = 128,
                           page_size: int = 16) -> List[Finding]:
    """Trace the training kernels (forward in every return mode, dense
    and compacted grids, fused backward) and the paged decode kernel at
    LLM-S head widths, and apply ``tpu-block-tiling`` to every
    ``pallas_call``. Tracing builds no kernel, so it runs on the CPU."""
    import jax
    import jax.numpy as jnp
    from repro.core import bam
    from repro.kernels.bam_attention import (bam_flash_attention,
                                             bam_flash_attention_bwd)
    from repro.kernels.paged_decode import paged_decode_attention
    S = jax.ShapeDtypeStruct
    q, kv = S((B, T, H, hd), jnp.bfloat16), S((B, T, Hkv, hd), jnp.bfloat16)
    bits, pos = S((B, T), jnp.uint32), S((B, T), jnp.int32)
    lse = S((B, H, T), jnp.float32)
    sb, sp = bam.build_sample_bits([("text", 0, T // 4), ("mod", 1, T // 2),
                                    ("text", 0, T // 4)], T)
    bm = bam.build_block_map(sb, sb, sp, sp, 128, 128)
    cases = []
    for label, grid in (("dense", None), ("compacted", bm)):
        for mode in ("out", "residual", "stats"):
            cases.append((f"bam_flash_attention {mode} {label}",
                          lambda *a, m=mode, g=grid: bam_flash_attention(
                              *a, return_mode=m, block_map=g),
                          (q, kv, kv, bits, bits, pos, pos)))
        cases.append((f"bam_flash_attention_bwd {label}",
                      lambda *a, g=grid: bam_flash_attention_bwd(
                          *a, block_map=g),
                      (q, kv, kv, q, q, lse, bits, bits, pos, pos)))
    P, Bd = 8, 4
    pages = S((P, Hkv, page_size, hd), jnp.bfloat16)
    steps = tuple(S((16,), jnp.int32) for _ in range(5))
    cases.append(("paged_decode_attention", paged_decode_attention,
                  (S((Bd, H, hd), jnp.bfloat16), pages, pages,
                   S((Bd, 1), jnp.uint32), S((Bd, 1), jnp.int32),
                   S((P, page_size), jnp.uint32),
                   S((P, page_size), jnp.int32), steps)))
    out: List[Finding] = []
    for label, fn, args in cases:
        out += tiling_findings(jax.make_jaxpr(fn)(*args), label)
    return out


def lint_kernels(path: Optional[str] = None) -> List[Finding]:
    """All kernellint rules: AST rules over every ``.py`` under
    ``path`` (default: ``src/repro/kernels``) + the dynamic
    small-case rules."""
    root = path or KERNELS_DIR
    out: List[Finding] = []
    for name in sorted(os.listdir(root)):
        if name.endswith(".py"):
            out += lint_file(os.path.join(root, name))
    out += check_block_map_coverage()
    out += check_scalar_prefetch_static()
    out += check_block_divisibility()
    out += check_decode_grid_coverage()
    out += check_page_divisibility()
    out += check_tpu_block_tiling()
    return out
