"""Paged KV cache for decode serving (host-side page table + device pool).

The serving cache is a pool of fixed-size pages rather than one dense
[B, Tmax] strip per request:

* ``PageTable`` (host, numpy) — owns the free list and the per-request
  logical-token -> (physical page, slot) mapping, plus host mirrors of
  the per-slot BAM bitfields and positions. The mirrors are what make
  the cache *multimodal-aware*: page compaction for the decode kernel
  is computed from the same ``repro.core.bam`` machinery that drives
  the training kernels' grid compaction.
* ``init_paged_cache`` (device) — the page pool itself:
  ``k``/``v`` [L, P, Hkv, page_size, hd] plus device copies of the
  bits/pos slot metadata (the decode kernel evaluates the mask
  in-registers from these, exactly like the training kernels).

Page 0 is a reserved **null page**: its bits stay 0 (= never
attends / attended), so any padded page-table entry or inactive batch
row can safely point at it — reads are masked out, writes are garbage
into a slot nothing will ever read.

Because BAM mask semantics use *explicit* positions (never iota), the
physical order of tokens inside the pool is irrelevant to correctness.
That is what lets a ``ContextPlan``-permuted prefill (CP ranks hold
permuted token blocks) write its K/V straight into the decode pool with
no re-gather: allocate the prompt's pages in plan layout
(``plan_page_owners``) and each CP rank's tokens land in a contiguous
run of rank-owned pages.

``build_decode_grid`` turns the table + per-request query bitfields
into the flattened step list the single-query flash-decode kernel
consumes (``repro.kernels.paged_decode``): per request, a k-major sweep
over only the pages the bitfield mask can reach — fully-masked pages
are compacted out of the grid and cost no grid step or DMA. The
per-request page pruning reuses ``bam.build_block_map`` with
``block_q=1`` (the decode query is one token) and ``block_k=page_size``
so the coverage obligations already proven for the training grids
(kernellint ``block-map-coverage``) carry over.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from repro.core import bam

#: reserved all-zero-bits page every padded/inactive reference points at
NULL_PAGE = 0


# ---------------------------------------------------------------------------
# Host-side page table
# ---------------------------------------------------------------------------

class PageTable:
    """Free-list page allocator + logical->physical token mapping.

    One instance serves all layers (the pool's layer axis is stacked on
    device; the mapping is layer-invariant). All state is host numpy —
    the engine mutates it between jitted steps.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError(
                f"num_pages={num_pages}: need at least the null page "
                f"plus one allocatable page")
        if page_size < 1:
            raise ValueError(f"page_size={page_size} must be >= 1")
        self.num_pages = num_pages
        self.page_size = page_size
        self.bits = np.zeros((num_pages, page_size), np.uint32)
        self.pos = np.full((num_pages, page_size), -1, np.int32)
        #: informational CP ownership (rank id, -1 = unowned) — set by
        #: plan-layout prefill so docs/benchmarks can show rank-local
        #: writes; correctness never depends on it
        self.page_owner = np.full(num_pages, -1, np.int32)
        self._free: List[int] = list(range(num_pages - 1, NULL_PAGE, -1))
        self._pages: Dict[int, List[int]] = {}
        self._len: Dict[int, int] = {}

    # -- allocation --------------------------------------------------------

    @property
    def num_free(self) -> int:
        return len(self._free)

    def requests(self) -> List[int]:
        return sorted(self._pages)

    def pages_of(self, rid: int) -> List[int]:
        return list(self._pages[rid])

    def length(self, rid: int) -> int:
        return self._len[rid]

    def capacity(self, rid: int) -> int:
        return len(self._pages.get(rid, ())) * self.page_size

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def alloc(self, rid: int, n_tokens: int) -> List[int]:
        """Grow ``rid``'s page list until it can hold ``n_tokens``
        tokens. Returns the newly allocated physical pages. Raises
        ``RuntimeError`` when the pool cannot satisfy the request (the
        engine's admission control checks ``num_free`` first)."""
        pages = self._pages.setdefault(rid, [])
        self._len.setdefault(rid, 0)
        need = self.pages_needed(n_tokens) - len(pages)
        if need > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: request {rid} needs {need} more "
                f"pages for {n_tokens} tokens but only {len(self._free)} "
                f"of {self.num_pages - 1} allocatable pages are free")
        new = [self._free.pop() for _ in range(max(need, 0))]
        pages.extend(new)
        return new

    def free(self, rid: int) -> None:
        """Release all of ``rid``'s pages back to the pool, scrubbing
        the host bits/pos mirrors so a reused page never leaks stale
        mask metadata (the device arrays are scrubbed by the engine)."""
        for p in self._pages.pop(rid, ()):
            self.bits[p] = 0
            self.pos[p] = -1
            self.page_owner[p] = -1
            self._free.append(p)
        self._len.pop(rid, None)

    # -- logical <-> physical ---------------------------------------------

    def coords(self, rid: int, idx) -> Tuple[np.ndarray, np.ndarray]:
        """Logical token indices -> (physical page, slot) arrays."""
        idx = np.asarray(idx, np.int64)
        pages = np.asarray(self._pages[rid], np.int32)
        if idx.size and int(idx.max()) >= len(pages) * self.page_size:
            raise IndexError(
                f"request {rid}: token index {int(idx.max())} exceeds "
                f"allocated capacity {len(pages) * self.page_size}")
        return pages[idx // self.page_size], \
            (idx % self.page_size).astype(np.int32)

    def write(self, rid: int, idx, bits, pos) -> None:
        """Record tokens in the host mirrors (device scatter happens
        inside the jitted step with the same coordinates)."""
        page, slot = self.coords(rid, idx)
        self.bits[page, slot] = np.asarray(bits, np.uint32)
        self.pos[page, slot] = np.asarray(pos, np.int32)
        idx = np.asarray(idx, np.int64)
        if idx.size:
            self._len[rid] = max(self._len[rid], int(idx.max()) + 1)

    def kv_view(self, rid: int) -> Tuple[np.ndarray, np.ndarray]:
        """The request's logical KV metadata, page-padded: (bits, pos)
        flat arrays of length n_pages * page_size (trailing slots of
        the last page carry bits=0 / pos=-1 and mask out)."""
        pages = self._pages[rid]
        return self.bits[pages].reshape(-1), self.pos[pages].reshape(-1)

    def page_table_row(self, rid: int, max_pages: int) -> np.ndarray:
        """Dense [max_pages] physical-page row for the XLA gather path,
        padded with the null page."""
        pages = self._pages[rid]
        if len(pages) > max_pages:
            raise ValueError(
                f"request {rid} holds {len(pages)} pages > "
                f"max_pages={max_pages}")
        row = np.full(max_pages, NULL_PAGE, np.int32)
        row[:len(pages)] = pages
        return row


# ---------------------------------------------------------------------------
# Device page pool
# ---------------------------------------------------------------------------

def init_paged_cache(cfg, num_pages: int, page_size: int, dtype=None):
    """Device page pool for ``cfg``: ``{"k","v"}`` [L, P, Hkv, page_size,
    hd] (Hkv honors ``cfg.decode_kv_replicate``, like the dense decode
    cache; head-major so the decode kernel reads one (page_size, hd)
    tile per page and KV head) plus ``{"bits","pos"}`` [P, page_size]
    slot metadata the kernel masks from."""
    from repro.models.transformer import _cache_cfg
    ccfg = _cache_cfg(cfg)
    dtype = jnp.dtype(cfg.dtype) if dtype is None else dtype
    shape = (cfg.num_layers, num_pages, ccfg.num_kv_heads, page_size,
             ccfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "bits": jnp.zeros((num_pages, page_size), jnp.uint32),
            "pos": jnp.full((num_pages, page_size), -1, jnp.int32)}


# ---------------------------------------------------------------------------
# Decode grid: per-request active-page compaction
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecodeGrid:
    """Flattened step list for the single-query flash-decode kernel.

    One step = (batch row ``req``, physical page, first, last, active);
    each request's steps are consecutive (k-major sweep over its active
    pages) so the kernel's online-softmax scratch can init on ``first``
    and flush on ``last`` — the same framing contract as
    ``bam.BlockMask``. ``active == 0`` steps exist only to (a) flush a
    request none of whose pages are reachable and (b) pad the step
    count to a static bucket (``pad_to``) so the jit cache is stable
    while lengths grow.
    """
    page_size: int
    window: int
    req: np.ndarray      # [n_steps] int32 batch row
    page: np.ndarray     # [n_steps] int32 physical page
    first: np.ndarray    # [n_steps] int32 0/1
    last: np.ndarray     # [n_steps] int32 0/1
    active: np.ndarray   # [n_steps] int32 0/1
    n_dense_steps: int   # total pages held by the batched requests

    @property
    def n_steps(self) -> int:
        return len(self.req)

    @property
    def n_active_steps(self) -> int:
        return int(self.active.sum())

    @property
    def skip_fraction(self) -> float:
        """Fraction of resident pages the compacted grid never visits
        (masked pages cost no grid step and no K/V DMA)."""
        return 1.0 - self.n_active_steps / max(self.n_dense_steps, 1)

    def arrays(self):
        """(req, page, first, last, active) int32 — the kernel's
        scalar-prefetch operands."""
        return (self.req, self.page, self.first, self.last, self.active)


def build_decode_grid(table: PageTable, rids: Sequence[Optional[int]],
                      q_bits, q_pos, *, window: int = 0,
                      pad_to: Optional[int] = None) -> DecodeGrid:
    """Active-page step list for one decode batch.

    ``rids[i]`` is the request occupying batch row ``i`` (``None`` =
    empty row: contributes one inactive flush step against the null
    page). ``q_bits``/``q_pos``: [B] host arrays for the current query
    token of each row — the engine must have ``write``-n the current
    token into the table first, so the query can attend itself.

    Page pruning is ``bam.build_block_map`` with ``block_q=1`` /
    ``block_k=page_size`` over the request's page-padded KV metadata —
    the mask reduction, q-major flattening, and first/last framing are
    the exact machinery the training kernels' compacted grids use.
    ``window`` must be 0 unless every decode layer shares the same
    sliding window (per-layer windows mask in-kernel instead; grid
    pruning with a nonzero window would drop pages a full-attention
    layer still needs).
    """
    q_bits = np.asarray(q_bits, np.uint32)
    q_pos = np.asarray(q_pos, np.int32)
    if len(rids) != len(q_bits) or len(rids) != len(q_pos):
        raise ValueError(
            f"rids/q_bits/q_pos disagree on batch size: "
            f"{len(rids)}/{len(q_bits)}/{len(q_pos)}")
    req, page, first, last, active = [], [], [], [], []
    n_dense = 0
    for i, rid in enumerate(rids):
        if rid is None:
            req.append(i)
            page.append(NULL_PAGE)
            first.append(1)
            last.append(1)
            active.append(0)
            continue
        pages = table.pages_of(rid)
        n_dense += len(pages)
        kv_bits, kv_pos = table.kv_view(rid)
        bm = bam.build_block_map(
            q_bits[i:i + 1], kv_bits, q_pos[i:i + 1], kv_pos,
            block_q=1, block_k=table.page_size, window=window)
        for (_iq, ik, f, l, a) in bm.q_steps:
            req.append(i)
            page.append(pages[ik] if a else NULL_PAGE)
            first.append(f)
            last.append(l)
            active.append(a)
    if pad_to is not None:
        if pad_to < len(req):
            raise ValueError(
                f"pad_to={pad_to} < {len(req)} real decode steps")
        while len(req) < pad_to:
            req.append(0)
            page.append(NULL_PAGE)
            first.append(0)
            last.append(0)
            active.append(0)
    return DecodeGrid(
        page_size=table.page_size, window=window,
        req=np.asarray(req, np.int32), page=np.asarray(page, np.int32),
        first=np.asarray(first, np.int32), last=np.asarray(last, np.int32),
        active=np.asarray(active, np.int32), n_dense_steps=n_dense)


def decode_grid_bucket(n_steps: int, granule: int = 16) -> int:
    """Round a step count up to a retrace bucket: the step arrays are
    traced operands but their LENGTH is a static shape, so bucketing
    keeps the jit cache warm while caches grow."""
    return max(granule, -(-n_steps // granule) * granule)


# ---------------------------------------------------------------------------
# ContextPlan page layout (CP prefill -> sharded decode cache handoff)
# ---------------------------------------------------------------------------

def plan_page_owners(layout: Dict, page_size: int) -> np.ndarray:
    """Per-page CP rank ownership for a prompt laid out in ContextPlan
    order.

    ``layout`` is ``ContextPlan.apply(seq_len)``'s dict: ``perm`` maps
    plan-layout slots -> source token indices and per-rank slot counts
    differ by at most one. Writing the prompt's K/V in *plan-layout
    order* (slot j of the cache holds source token ``perm[j]``) makes
    each rank's tokens a contiguous slot run, so rank r's prefill
    output lands in pages ``owners == r`` — no cross-rank re-gather
    between prefill and decode. Returns [n_pages] int32 rank ids; a
    page straddling two ranks' slot ranges is owned by the rank holding
    its first slot (only possible when counts don't divide
    ``page_size``)."""
    n = len(layout["perm"])
    ranks = int(layout["num_ranks"])
    base, extra = divmod(n, ranks)
    counts = [base + (1 if r < extra else 0) for r in range(ranks)]
    slot_rank = np.repeat(np.arange(ranks, dtype=np.int32), counts)
    n_pages = -(-n // page_size)
    return slot_rank[np.arange(n_pages) * page_size]
