"""Block-table (paged) packed-KV cache: one shared page pool per layer.

Production serving never has one contiguous KV cache per sequence: requests
arrive and finish continuously, their lengths are unknown up front, and a
pre-sized ``(B, S_max, ...)`` buffer wastes ``S_max - len`` slots per
sequence.  The vLLM insight is to virtualize the cache -- fixed-size *pages*
in one shared pool, per-sequence *block tables* mapping logical page ``p``
of a sequence to a physical page id -- so memory is allocated in page
quanta as sequences grow and returned the moment they finish.

Here that idea composes with the paper's transprecision storage: the pool
holds the *packed* binary8/16/16alt payloads (container-width bytes in HBM,
the 4x byte win of ``kernels/flash_attention.py``), and the page size is
required to be a multiple of the codec's word-packing lane count
(``kernels/codec.pack_word_tile``: 4 x 8 b / 2 x 16 b lanes per u32 word)
so every page stays u32-word-aligned regardless of format -- the sub-word
vectorized-container layout of Anderson & Gregg (arXiv 1601.07789) applied
at page granularity.

Two halves, deliberately split:

:class:`PagedKVCache`
    The *device* state -- a pytree of arrays (pools, block tables, sequence
    lengths) that flows through ``jax.jit`` decode steps unchanged in
    structure.  All device ops (:func:`append_decode`,
    :func:`write_prefill`, :func:`write_prefill_layers`,
    :func:`release_slot`) are functional updates.

:class:`PagePool`
    The *host* allocator -- a free list plus per-slot page ownership.  Page
    allocation is an admission-control decision (can this request fit?
    must one be evicted?), which is inherently host-side control flow, so
    it lives outside jit; the serving loop in ``launch/serve.py`` drives it
    and pushes refreshed block tables into the device state between steps.

Unmapped block-table entries are ``-1``.  Device writes through an unmapped
entry are *dropped* (scatter ``mode="drop"`` via an out-of-bounds sentinel),
and the decode kernel masks unmapped pages, so a freed slot is inert without
any pool zeroing -- page reuse just overwrites stale payload bytes.
"""
from __future__ import annotations

from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Default page granule: 64 tokens x head_dim lanes keeps a page's K tile a
# healthy multiple of the f32 (8, 128) VPU tile while staying fine-grained
# enough that internal fragmentation averages page_size/2 tokens/sequence.
DEFAULT_PAGE_SIZE = 64


class PoolError(RuntimeError):
    """Classified page-allocator misuse: freeing or releasing a slot that
    owns nothing (double free), growing/truncating a slot that was never
    allocated, or a slot index outside the table.

    Raising (instead of the old silent no-op / bare KeyError) is what
    makes the engine's quarantine path safe: once a slot's pages are
    quarantined, any further ``free_slot`` on it fails loudly rather than
    silently recycling suspect pages.  Lives in the kernels layer (the
    allocator never imports the engine); the serve CLI maps it to its own
    exit code like the ``repro.engine.resilience.EngineError`` family.
    """

    exit_code = 76
    kind = "pool"


def page_alignment(fmt=None) -> int:
    """Smallest legal page-size multiple for ``fmt``.

    lcm(8, lanes-per-u32-word): 8 sublanes for the f32 compute tile, and
    4/2/1 lanes so a page boundary never splits a packed u32 word
    (``codec.pack_word_tile``).  8 covers every paper format; the function
    exists so the constraint is stated once, next to its reason.
    """
    del fmt  # lanes (4 | 2 | 1) always divide the sublane tile of 8
    return 8


def validate_page_size(page_size: int, fmt=None) -> int:
    align = page_alignment(fmt)
    if page_size <= 0 or page_size % align:
        raise ValueError(
            f"page_size {page_size} must be a positive multiple of {align} "
            f"(u32-word alignment of the packed codec lanes + f32 sublane "
            f"tile)")
    return page_size


class PagedKVCache(NamedTuple):
    """Device half of the paged cache (a jit-stable pytree of arrays).

    k_pool / v_pool: (num_pages, page_size, n_kv, head_dim) in the policy's
        kv_cache storage dtype -- bit-identical to the packed (e, m)
        container, exactly like the contiguous ``KVCache``.
    block_tables: (n_slots, pages_per_seq) int32; entry ``[s, p]`` is the
        physical page holding positions [p*page_size, (p+1)*page_size) of
        the sequence in slot ``s``, or -1 when unmapped.
    seq_lens: (n_slots,) int32 tokens currently stored per slot.
    """
    k_pool: jax.Array
    v_pool: jax.Array
    block_tables: jax.Array
    seq_lens: jax.Array

    @property
    def num_pages(self) -> int:
        return self.k_pool.shape[0]

    @property
    def page_size(self) -> int:
        return self.k_pool.shape[1]

    @property
    def n_slots(self) -> int:
        return self.block_tables.shape[0]

    @property
    def pages_per_seq(self) -> int:
        return self.block_tables.shape[1]

    @property
    def capacity(self) -> int:
        """Max tokens one slot can address through its block table."""
        return self.pages_per_seq * self.page_size


def init_paged_cache(n_slots: int, num_pages: int, page_size: int,
                     pages_per_seq: int, n_kv: int, head_dim: int,
                     dtype) -> PagedKVCache:
    validate_page_size(page_size)
    shape = (num_pages, page_size, n_kv, head_dim)
    # two buffers, never one shared: a program that donates both pools
    # (the prefill worker's pool write) cannot donate one buffer twice
    return PagedKVCache(
        k_pool=jnp.zeros(shape, dtype), v_pool=jnp.zeros(shape, dtype),
        block_tables=jnp.full((n_slots, pages_per_seq), -1, jnp.int32),
        seq_lens=jnp.zeros((n_slots,), jnp.int32))


def _scatter_tokens(pool, phys, off, vals):
    """pool[phys[i], off[i]] = vals[i], dropping unmapped (phys < 0) rows.

    The drop sentinel is ``num_pages`` (unambiguously out of bounds for
    ``mode="drop"``) rather than relying on negative-index semantics.
    """
    phys = jnp.where(phys < 0, pool.shape[0], phys)
    return pool.at[phys, off].set(vals, mode="drop")


def append_decode(cache: PagedKVCache, k, v) -> PagedKVCache:
    """Append one decode token per slot at position ``seq_lens[s]``.

    k / v: (n_slots, 1, n_kv, head_dim), any float dtype (cast to the pool
    storage dtype here).  Slots whose next position has no mapped page --
    free slots, or a serving loop that forgot to extend the table -- are
    dropped and their length does NOT advance, so host and device length
    bookkeeping can never silently diverge.
    """
    pos = cache.seq_lens
    lp = jnp.clip(pos // cache.page_size, 0, cache.pages_per_seq - 1)
    phys = cache.block_tables[jnp.arange(cache.n_slots), lp]
    off = pos % cache.page_size
    mapped = (phys >= 0) & (pos < cache.capacity)
    phys = jnp.where(mapped, phys, -1)
    kq = k[:, 0].astype(cache.k_pool.dtype)
    vq = v[:, 0].astype(cache.v_pool.dtype)
    return cache._replace(
        k_pool=_scatter_tokens(cache.k_pool, phys, off, kq),
        v_pool=_scatter_tokens(cache.v_pool, phys, off, vq),
        seq_lens=jnp.where(mapped, pos + 1, pos))


def write_chunk(cache: PagedKVCache, slot, k, v, offset) -> PagedKVCache:
    """Scatter one prefill *chunk* (positions offset..offset+S-1) into
    ``slot``'s mapped pages.

    k / v: (S, n_kv, head_dim) -- one sequence's chunk.  The chunk is free
    to straddle page boundaries, cover less or more than one page, and end
    ragged; token ``i`` lands at logical position ``offset + i`` exactly
    where :func:`write_prefill` would have put it (the whole-prompt write
    is the ``offset=0`` special case and delegates here).  Pages must
    already be mapped by the host allocator; unmapped tails are dropped and
    the recorded length clamped to ``offset + #mapped``, so chunked prefill
    only ever stages O(chunk) transient tokens instead of O(prompt).
    """
    S = k.shape[0]
    pos = jnp.arange(S) + offset
    lp = jnp.clip(pos // cache.page_size, 0, cache.pages_per_seq - 1)
    phys = cache.block_tables[slot, lp]
    mapped = (phys >= 0) & (pos < cache.capacity)
    n_mapped = jnp.sum(mapped.astype(jnp.int32))
    phys = jnp.where(mapped, phys, -1)
    off = pos % cache.page_size
    return cache._replace(
        k_pool=_scatter_tokens(cache.k_pool, phys, off,
                               k.astype(cache.k_pool.dtype)),
        v_pool=_scatter_tokens(cache.v_pool, phys, off,
                               v.astype(cache.v_pool.dtype)),
        seq_lens=cache.seq_lens.at[slot].set(offset + n_mapped))


def append_block(cache: PagedKVCache, k, v) -> PagedKVCache:
    """Append ``K`` tokens per slot at positions ``seq_lens[s] + i``.

    k / v: (n_slots, K, n_kv, head_dim) -- the multi-token generalization
    of :func:`append_decode` used by speculative decoding: the draft model
    appends its k look-ahead tokens in one step, and the target's verify
    step appends the k tokens it is checking.  Token ``i`` of slot ``s``
    lands exactly where ``K`` sequential :func:`append_decode` calls would
    have put it (same cast, same drop semantics), so the verify path stays
    bit-identical to plain decode.  A slot's length advances by its run of
    *leading* mapped positions (a masked or capacity-exhausted slot
    advances 0..K), which keeps host and device length bookkeeping in
    lockstep with the allocator's page map.
    """
    K = k.shape[1]
    base = cache.seq_lens
    pos = base[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
    lp = jnp.clip(pos // cache.page_size, 0, cache.pages_per_seq - 1)
    phys = jnp.take_along_axis(cache.block_tables, lp, axis=1)
    mapped = (phys >= 0) & (pos < cache.capacity)
    phys = jnp.where(mapped, phys, -1)
    off = pos % cache.page_size
    adv = jnp.sum(jnp.cumprod(mapped.astype(jnp.int32), axis=1), axis=1)
    return cache._replace(
        k_pool=_scatter_tokens(cache.k_pool, phys, off,
                               k.astype(cache.k_pool.dtype)),
        v_pool=_scatter_tokens(cache.v_pool, phys, off,
                               v.astype(cache.v_pool.dtype)),
        seq_lens=base + adv)


def write_prefill(cache: PagedKVCache, slot, k, v) -> PagedKVCache:
    """Write a prefilled prompt (positions 0..S-1) into ``slot``'s pages.

    k / v: (S, n_kv, head_dim) -- one sequence, e.g. ``KVCache.k[0][:S]``
    from the transient contiguous prefill cache.  Pages must already be
    mapped by the host allocator; unmapped tails are dropped (and the
    recorded length clamped to what was actually mapped).
    """
    return write_chunk(cache, slot, k, v, 0)


def _write_prompt_pages(pool, phys, x):
    """``_scatter_tokens`` for a prompt at offset 0, one whole page at a
    time: row ``r`` of logical page ``p`` gets ``x[p * page + r]``.  Rows
    past the prompt keep their bytes and unmapped (``phys < 0``) pages are
    left alone (their update rewrites page 0 with what it holds), exactly
    as the per-token scatter leaves them."""
    n, page = phys.shape[0], pool.shape[1]
    S = min(x.shape[0], n * page)
    x = jnp.pad(x[:S].astype(pool.dtype),
                ((0, n * page - S),) + ((0, 0),) * (x.ndim - 1))
    x = x.reshape((n, page) + x.shape[1:])
    row = jax.lax.broadcasted_iota(jnp.int32, (1, page) + x.shape[2:], 1)

    def page_write(p, pool):
        i = jnp.maximum(phys[p], 0)
        old = jax.lax.dynamic_index_in_dim(pool, i, 0)
        new = jax.lax.dynamic_index_in_dim(x, p, 0)
        keep = (row < S - p * page) & (phys[p] >= 0)
        return jax.lax.dynamic_update_index_in_dim(
            pool, jnp.where(keep, new, old), i, 0)

    return jax.lax.fori_loop(0, n, page_write, pool)


def write_prefill_layers(caches, slot, ks, vs) -> List[PagedKVCache]:
    """:func:`write_prefill` into every layer's pool at once.

    caches: one :class:`PagedKVCache` per attention layer; ks / vs: that
    layer's (S, n_kv, head_dim) prompt K/V.  The result is bit-identical
    to ``write_prefill`` per layer (same cast, same drop of unmapped or
    over-capacity positions, same ``seq_lens[slot]`` clamp), but each pool
    is updated in place one page at a time.  The TPU's default layout of
    a pool depends on its shape (``(.., 128, 8, 64)`` binary8 keeps the
    in-page row axis minor-most, ``(.., 128, 4, 128)`` is row-major), and
    a scatter of token rows or of whole pages relayouts every pool whose
    layout does not suit it; a one-page update is cheap in any layout.
    Meant to run inside one jitted program that donates the pools
    (``engine.worker.PrefillWorker``).
    """
    out = []
    for c, k, v in zip(caches, ks, vs):
        S, page = k.shape[0], c.page_size
        n = min(-(-S // page), c.pages_per_seq)
        phys = c.block_tables[slot, :n]
        rows = jnp.clip(S - page * jnp.arange(n), 0, page)
        n_mapped = jnp.sum(jnp.where(phys >= 0, rows, 0).astype(jnp.int32))
        out.append(c._replace(
            k_pool=_write_prompt_pages(c.k_pool, phys, k),
            v_pool=_write_prompt_pages(c.v_pool, phys, v),
            seq_lens=c.seq_lens.at[slot].set(n_mapped)))
    return out


def set_seq_len(cache: PagedKVCache, slot, n) -> PagedKVCache:
    """Host-declared length for ``slot``.  Page-streaming transports copy
    finished pages into the pool wholesale (no :func:`write_chunk` on the
    destination), so the device-side length is set explicitly at handoff."""
    return cache._replace(
        seq_lens=cache.seq_lens.at[slot].set(jnp.asarray(n, jnp.int32)))


def truncate_seq_lens(cache: PagedKVCache, max_lens) -> PagedKVCache:
    """Device half of speculative rollback: clamp every slot's length to
    ``max_lens`` (per-slot int32).  Entries past the clamp stay as stale
    pool bytes -- every reader masks positions at or beyond ``seq_lens``,
    and the host allocator's :meth:`PagePool.truncate` returns the pages
    past the truncation point to the free list."""
    return cache._replace(
        seq_lens=jnp.minimum(cache.seq_lens,
                             jnp.asarray(max_lens, jnp.int32)))


def release_slot(cache: PagedKVCache, slot: int) -> PagedKVCache:
    """Unmap a slot (free/evict).  Pool bytes are left stale on purpose --
    unmapped pages are masked by every reader, and the next
    :func:`write_prefill`/:func:`append_decode` through a fresh table
    overwrites them (page reuse).  An out-of-range slot raises
    :class:`PoolError` (an in-range device check would need a host
    transfer per release; the host allocator's ``free_slot`` owns the
    already-freed check)."""
    if not 0 <= int(slot) < cache.n_slots:
        raise PoolError(
            f"release_slot: slot {slot} outside 0..{cache.n_slots - 1}")
    return cache._replace(
        block_tables=cache.block_tables.at[slot].set(-1),
        seq_lens=cache.seq_lens.at[slot].set(0))


def set_block_tables(cache: PagedKVCache, tables) -> PagedKVCache:
    """Push a host-refreshed block table into the device state."""
    return cache._replace(
        block_tables=jnp.asarray(tables, jnp.int32))


# ---------------------------------------------------------------------------
# contiguous <-> paged bridges
# ---------------------------------------------------------------------------

def paged_view_of_contiguous(ck, cv, page_size: int = DEFAULT_PAGE_SIZE):
    """View a contiguous (B, S, H, dh) cache as (pools, block_tables).

    The identity paging: sequence ``b``'s logical page ``p`` is physical
    page ``b * n_pages + p``.  Pure reshape (plus zero-padding when
    ``page_size`` does not divide S; padded slots sit beyond every valid
    length).  This is how a ``decode_impl="paged"`` spelling runs over an
    ordinary :class:`repro.models.attention.KVCache` -- same kernel, same
    block-table plumbing, degenerate table -- which keeps the paged backend
    benchmarkable and oracle-testable without a serving loop.
    """
    B, S = ck.shape[0], ck.shape[1]
    page = max(8, min(page_size, S))
    n_pages = -(-S // page)
    pad = n_pages * page - S
    if pad:
        ck = jnp.pad(ck, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(cv, ((0, 0), (0, pad), (0, 0), (0, 0)))
    shape = (B * n_pages, page) + ck.shape[2:]
    tables = jnp.arange(B * n_pages, dtype=jnp.int32).reshape(B, n_pages)
    return ck.reshape(shape), cv.reshape(shape), tables


def gather_pages(pool, block_tables):
    """Materialize the contiguous (B, pages_per_seq * page_size, H, dh)
    view of a paged pool -- the XLA dequantize-path gather (unmapped pages
    come back as physical page 0 and must be masked by the caller; the
    reference in ``paged_attention.py`` does)."""
    tbl = jnp.clip(block_tables, 0, pool.shape[0] - 1)
    g = pool[tbl]  # (B, pages_per_seq, page_size, H, dh)
    B, P, page = g.shape[0], g.shape[1], g.shape[2]
    return g.reshape((B, P * page) + g.shape[3:])


# ---------------------------------------------------------------------------
# host-side allocator (admission control for the serving loop)
# ---------------------------------------------------------------------------

class PagePool:
    """Free-list page allocator + host mirror of tables and lengths.

    Purely host-side numpy/python state: the serving loop consults it for
    admission (``can_admit``), growth (``ensure_capacity``) and eviction,
    then pushes ``self.tables`` into the device :class:`PagedKVCache` via
    :func:`set_block_tables`.  Freed pages return to the free list in LIFO
    order so reuse is immediate (and deliberately exercised by tests:
    stale payload bytes in a reused page must be invisible).

    **Namespaces.**  One physical free list can back several logical page
    maps -- speculative decoding keeps the target model's KV and the draft
    model's KV for the *same* slot under distinct namespace tags (default
    ``""`` for the target, ``"draft"`` for the draft), so admission,
    growth, eviction and the occupancy stats stay one allocator.  Every
    mutation takes an ``ns`` keyword (default: the default namespace, which
    keeps the pre-namespace API intact: ``pool.tables`` / ``pool.lens`` /
    ``pool.owned`` are the default namespace's views); ``free_slot`` frees
    a slot across ALL namespaces atomically -- evicting a sequence can
    never strand its draft pages."""

    def __init__(self, num_pages: int, page_size: int, n_slots: int,
                 pages_per_seq: int):
        validate_page_size(page_size)
        self.num_pages = num_pages
        self.page_size = page_size
        self.n_slots = n_slots
        self.pages_per_seq = pages_per_seq
        self.free: List[int] = list(range(num_pages - 1, -1, -1))
        self._ns: dict = {}             # tag -> {owned, lens, tables}
        self._ensure_ns("")
        self.peak_pages_used = 0
        # pages pulled out of circulation by quarantine_slot: suspected-bad
        # physical memory, never returned to the free list
        self.quarantined: List[int] = []

    def _ensure_ns(self, ns: str) -> dict:
        if ns not in self._ns:
            self._ns[ns] = {
                "owned": {},            # slot -> [physical page ids]
                "lens": np.zeros(self.n_slots, np.int64),
                "tables": np.full((self.n_slots, self.pages_per_seq), -1,
                                  np.int32),
            }
        return self._ns[ns]

    # -- default-namespace views (the pre-namespace API) ---------------------
    @property
    def owned(self) -> dict:
        return self._ns[""]["owned"]

    @property
    def lens(self) -> np.ndarray:
        return self._ns[""]["lens"]

    @property
    def tables(self) -> np.ndarray:
        return self._ns[""]["tables"]

    @property
    def namespaces(self) -> tuple:
        return tuple(self._ns)

    def ns_owned(self, ns: str = "") -> dict:
        return self._ensure_ns(ns)["owned"]

    def ns_lens(self, ns: str = "") -> np.ndarray:
        return self._ensure_ns(ns)["lens"]

    def ns_tables(self, ns: str = "") -> np.ndarray:
        return self._ensure_ns(ns)["tables"]

    # -- queries -------------------------------------------------------------
    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    @property
    def pages_used(self) -> int:
        return self.num_pages - len(self.free)

    def occupancy(self) -> float:
        """Fraction of physical pages currently allocated."""
        return self.pages_used / max(self.num_pages, 1)

    def internal_fragmentation(self) -> float:
        """Fraction of *allocated* pool slots holding no valid token --
        the bytes block-tables waste (vs a perfectly packed pool), the
        quantity vLLM drove to <4 %.  0.0 when nothing is allocated.
        Sums valid tokens across every namespace (draft pages are
        allocated pool slots like any other)."""
        slots = self.pages_used * self.page_size
        if slots == 0:
            return 0.0
        valid = sum(float(ns["lens"].sum()) for ns in self._ns.values())
        return 1.0 - valid / slots

    def can_admit(self, n_tokens: int, *more_tokens: int) -> bool:
        """True when every requested sequence fits: each token count maps
        to its own block table (<= pages_per_seq) and the page *sum* fits
        the free list.  Speculative admission passes the target and draft
        needs together -- one admission decision over one allocator."""
        needs = [self.pages_for(max(n, 1)) for n in (n_tokens,) + more_tokens]
        return (sum(needs) <= len(self.free)
                and max(needs) <= self.pages_per_seq)

    # -- mutations -----------------------------------------------------------
    def _check_slot(self, op: str, slot: int) -> None:
        if not 0 <= slot < self.n_slots:
            raise PoolError(
                f"{op}: slot {slot} outside 0..{self.n_slots - 1}")

    def _owned_pages(self, op: str, slot: int, space: dict,
                     ns: str) -> List[int]:
        pages = space["owned"].get(slot)
        if pages is None:
            raise PoolError(
                f"{op}: slot {slot} owns no pages in namespace {ns!r}")
        return pages

    def allocate(self, slot: int, n_tokens: int, *, ns: str = "") -> bool:
        """Map pages for a fresh ``n_tokens``-token sequence in ``slot``."""
        self._check_slot("allocate", slot)
        space = self._ensure_ns(ns)
        if slot in space["owned"]:
            raise PoolError(
                f"allocate: slot {slot} already allocated in namespace "
                f"{ns!r}")
        if not self.can_admit(n_tokens):
            return False
        need = self.pages_for(max(n_tokens, 1))
        pages = [self.free.pop() for _ in range(need)]
        space["owned"][slot] = pages
        space["tables"][slot, :need] = pages
        space["lens"][slot] = n_tokens
        self.peak_pages_used = max(self.peak_pages_used, self.pages_used)
        return True

    def ensure_capacity(self, slot: int, n_tokens: int, *,
                        ns: str = "") -> bool:
        """Grow ``slot``'s mapping to cover ``n_tokens`` total tokens.
        False when the pool is out of pages (caller evicts) or the block
        table is full (sequence hit ``pages_per_seq * page_size``)."""
        self._check_slot("ensure_capacity", slot)
        space = self._ensure_ns(ns)
        pages = self._owned_pages("ensure_capacity", slot, space, ns)
        need = self.pages_for(n_tokens)
        if need > self.pages_per_seq:
            return False
        while len(pages) < need:
            if not self.free:
                return False
            pg = self.free.pop()
            space["tables"][slot, len(pages)] = pg
            pages.append(pg)
        self.peak_pages_used = max(self.peak_pages_used, self.pages_used)
        return True

    def note_decode_step(self, slot: int, *, ns: str = "") -> None:
        self._ensure_ns(ns)["lens"][slot] += 1

    def truncate(self, slot: int, n_tokens: int, *, ns: str = "") -> int:
        """Speculative rollback, host half: shrink ``slot``'s recorded
        length to ``n_tokens`` and return exactly the pages past the
        truncation point to the free list (LIFO, like ``free_slot``).
        -> #pages freed."""
        self._check_slot("truncate", slot)
        space = self._ensure_ns(ns)
        pages = self._owned_pages("truncate", slot, space, ns)
        keep = self.pages_for(max(n_tokens, 1))
        excess = pages[keep:]
        del pages[keep:]
        self.free.extend(reversed(excess))
        space["tables"][slot, keep:] = -1
        space["lens"][slot] = n_tokens
        return len(excess)

    def free_slot(self, slot: int) -> int:
        """Return ``slot``'s pages -- across EVERY namespace, atomically --
        to the free list; -> #pages freed.  A slot that owns nothing in
        any namespace (never allocated, already freed, or quarantined)
        raises :class:`PoolError`: the old silent no-op let a double free
        pass unnoticed, which the quarantine path cannot afford."""
        self._check_slot("free_slot", slot)
        if not any(slot in space["owned"] for space in self._ns.values()):
            raise PoolError(
                f"free_slot: slot {slot} owns no pages in any namespace "
                f"(double free, or freed after quarantine?)")
        freed = 0
        for space in self._ns.values():
            pages = space["owned"].pop(slot, [])
            self.free.extend(reversed(pages))
            space["tables"][slot] = -1
            space["lens"][slot] = 0
            freed += len(pages)
        return freed

    def quarantine_slot(self, slot: int) -> int:
        """Pull ``slot``'s pages -- across EVERY namespace -- OUT of
        circulation: they move to ``self.quarantined`` instead of the free
        list, so physical pages that held non-finite state are never
        handed to another sequence; -> #pages quarantined.  A subsequent
        ``free_slot`` on the same slot raises (no double release)."""
        self._check_slot("quarantine_slot", slot)
        if not any(slot in space["owned"] for space in self._ns.values()):
            raise PoolError(
                f"quarantine_slot: slot {slot} owns no pages in any "
                f"namespace")
        n = 0
        for space in self._ns.values():
            pages = space["owned"].pop(slot, [])
            self.quarantined.extend(pages)
            space["tables"][slot] = -1
            space["lens"][slot] = 0
            n += len(pages)
        return n

    def stats(self) -> dict:
        return {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "pages_used": self.pages_used,
            "peak_pages_used": self.peak_pages_used,
            "quarantined_pages": len(self.quarantined),
            "occupancy": round(self.occupancy(), 4),
            "internal_fragmentation":
                round(self.internal_fragmentation(), 4),
        }


def pool_fragmentation(lengths, page_size: int) -> float:
    """Analytic internal fragmentation for per-sequence ``lengths`` under
    page granule ``page_size`` (the benchmark's fragmentation column: what
    fraction of allocated pool slots a paged layout wastes)."""
    lengths = np.asarray(lengths, np.int64)
    pages = -(-lengths // page_size)
    slots = int(pages.sum()) * page_size
    if slots == 0:
        return 0.0
    return 1.0 - float(lengths.sum()) / slots
