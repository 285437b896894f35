"""Pallas paged-attention decode: block-table gather + in-register decode.

The serving companion to ``kernels/flash_attention.py``: same one-token
online-softmax decode over a packed KV cache, but the cache is *paged* --
fixed-size pages scattered through a shared pool, addressed per sequence
through a block table (``kernels/paged_cache.py``).  The kernel never sees
a contiguous cache and never materializes one: the block table rides in as
a *scalar-prefetch* operand (``pltpu.PrefetchScalarGridSpec``), so the
BlockSpec index map reads ``tables[b, p]`` and the Pallas pipeline DMAs
each sequence's physical pages straight from the pool in HBM -- the gather
IS the address computation, there is no XLA gather op and no wide copy.
Each fetched page tile is then expanded in-register through the shared
codec (``codec.decode_tile`` via ``flash_attention._payload_to_f32``), so
HBM still moves container-width bytes: the paper's 4x byte win survives
non-contiguous caches.

Grid: (B, pages_per_seq), pages innermost ("arbitrary") carrying every
head's running (max, sum, acc) online-softmax triple, exactly like the
contiguous kernel with ``block_kv = page_size``.  Masking is two-level:
positions at or past ``lengths[b]`` are invalid, and *unmapped* pages
(table entry < 0) are masked wholesale -- which is also what makes the
pool shardable: the ``flash_shmap+paged`` wrapper in
``kernels/dispatch.py`` gives every device the pool shard it owns plus a
table with non-owned pages set to -1, and merges the per-device partials
(m, l) exactly as for the contiguous case.

``paged_decode_reference`` is the XLA oracle: gather the pool through the
block table (materializing the contiguous wide copy the kernel avoids),
then the same decode -> QK^T -> masked softmax -> PV order as
``flash_decode_reference``.  Tests pin kernel vs oracle to <= 1e-6 for all
four paper formats, ragged lengths, >= 3 non-contiguous pages per
sequence, and page reuse after free/realloc.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import CompilerParams
from repro.core.formats import get_format

from .flash_attention import (NEG_INF, _MIN_SUBLANE, _decode_layout,
                              _flush_heads, _fold_heads, _payload_to_f32)
from .paged_cache import gather_pages


def _paged_decode_kernel(len_ref, tbl_ref, q_ref, k_ref, v_ref, *refs,
                         fmt, scale, page_size, n_pages, with_residuals):
    if with_residuals:
        o_ref, mo_ref, lo_ref, acc_ref, m_ref, l_ref = refs
    else:
        (o_ref, acc_ref, m_ref, l_ref), mo_ref, lo_ref = refs, None, None
    b, pi = pl.program_id(0), pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (q_ref.shape[1], page_size), 1)
    # two-level validity: ragged length AND page actually mapped (unmapped
    # pages -- free slots, table tails, non-owned shards -- are fetched as
    # a clamped placeholder and must not contribute)
    mask = (pos < len_ref[b]) & (tbl_ref[b, pi] >= 0)
    _fold_heads(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, fmt, scale, mask)

    @pl.when(pi == n_pages - 1)
    def _flush():
        _flush_heads(o_ref, mo_ref, lo_ref, acc_ref, m_ref, l_ref)


def paged_decode(q, k_pool, v_pool, fmt, lengths, block_tables, *,
                 scale: Optional[float] = None,
                 return_residuals: bool = False,
                 interpret: bool | None = None):
    """Single-token GQA attention over a paged packed KV pool.

    q:            (B, H, G, dh) float -- one query token per sequence.
    k_pool / v_pool:
                  (num_pages, page_size, H, dh) packed (e, m) containers
                  (uint8/16/32) when ``fmt`` is given, or plain floats.
    lengths:      (B,) int32 valid tokens per sequence.
    block_tables: (B, pages_per_seq) int32 physical page ids; -1 = unmapped
                  (masked -- also how pool shards mask non-owned pages).
    Returns (B, H, G, dh) float32; ``return_residuals`` adds the flash
    partials (m, l) of shape (B, H, G) for the shard-merge wrapper.
    """
    fmt = get_format(fmt) if fmt is not None else None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, G, dh = q.shape
    num_pages, page = k_pool.shape[0], k_pool.shape[1]
    assert k_pool.shape == v_pool.shape == (num_pages, page, H, dh), (
        q.shape, k_pool.shape, v_pool.shape)
    n_pages = block_tables.shape[1]
    assert block_tables.shape == (B, n_pages), block_tables.shape
    if scale is None:
        scale = float(1.0 / np.sqrt(dh))

    pg = (-G) % _MIN_SUBLANE
    if pg:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pg), (0, 0)))
    Gp = G + pg
    lengths = jnp.minimum(lengths.astype(jnp.int32),
                          n_pages * page)                   # (B,)
    tables = block_tables.astype(jnp.int32)

    kern = functools.partial(_paged_decode_kernel, fmt=fmt,
                             scale=np.float32(scale), page_size=page,
                             n_pages=n_pages,
                             with_residuals=return_residuals)
    # index maps receive (grid ids..., *scalar-prefetch refs); the pool
    # block index is the block-table lookup itself, clamped so unmapped
    # entries fetch page 0 (fully masked in the kernel body).  One block
    # is a whole page with all H heads: one contiguous DMA per page.
    qmap = lambda b, p, lens, tbl: (b, 0, 0, 0)             # noqa: E731
    pmap = lambda b, p, lens, tbl: (                        # noqa: E731
        jnp.maximum(tbl[b, p], 0), 0, 0, 0)
    widths, scratch = _decode_layout(H, Gp, dh, return_residuals)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((None, H, Gp, dh), qmap),
            pl.BlockSpec((None, page, H, dh), pmap),
            pl.BlockSpec((None, page, H, dh), pmap),
        ],
        out_specs=[pl.BlockSpec((None, H, Gp, w), qmap) for w in widths],
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, Gp, w), jnp.float32)
                   for w in widths],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, tables, q, k_pool, v_pool)
    if return_residuals:
        o, m, l = out
        return o[:, :, :G, :], m[:, :, :G, 0], l[:, :, :G, 0]
    return out[0][:, :, :G, :]


def paged_decode_reference(q, k_pool, v_pool, fmt, lengths, block_tables, *,
                           scale: Optional[float] = None,
                           return_residuals: bool = False):
    """The XLA dequantize oracle for :func:`paged_decode`.

    Gathers the pool contiguous through the block table (materializing
    exactly the wide copy the kernel's scalar-prefetch DMA avoids), then
    mirrors ``flash_decode_reference``'s operation order with the same
    two-level (length AND mapped-page) mask.
    """
    fmt = get_format(fmt) if fmt is not None else None
    B, H, G, dh = q.shape
    page = k_pool.shape[1]
    if scale is None:
        scale = float(1.0 / np.sqrt(dh))
    k = _payload_to_f32(gather_pages(k_pool, block_tables), fmt)
    v = _payload_to_f32(gather_pages(v_pool, block_tables), fmt)
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32), k,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    S = s.shape[-1]
    pos = jnp.arange(S)[None, :]
    mapped = jnp.repeat(block_tables >= 0, page, axis=1)    # (B, S)
    valid = (pos < lengths.astype(jnp.int32)[:, None]) & mapped
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    num = jnp.einsum("bhgs,bshd->bhgd", p, v,
                     preferred_element_type=jnp.float32)
    den = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.where(den > 0, num / den, 0.0)
    if return_residuals:
        return out, m[..., 0], den[..., 0]
    return out


def paged_hbm_bytes(batch: int, lengths, n_kv: int, head_dim: int, fmt, *,
                    page_size: int, g: int = 1, q_bytes: int = 4) -> int:
    """HBM bytes one paged decode step streams: every *mapped* page of the
    K and V pools (container-width payload -- allocated pages are fetched
    whole, which is the internal-fragmentation cost made visible), the
    block tables, and the query rows."""
    fmt = get_format(fmt) if fmt is not None else None
    item = 4 if fmt is None else fmt.container_dtype.dtype.itemsize
    lengths = np.asarray(lengths, np.int64)
    pages = int((-(-lengths // page_size)).sum())
    kv = 2 * pages * page_size * n_kv * head_dim * item
    tables = pages * 4
    return kv + tables + batch * n_kv * g * head_dim * q_bytes


def paged_ring_ppermute_bytes(num_pages: int, page_size: int, n_kv: int,
                              head_dim: int, fmt, *, n_devices: int) -> int:
    """Interconnect bytes ONE device sends per decode step under the
    ``ring+paged`` wrapper: its (num_pages / n_devices)-page K and V pool
    shards, passed whole to the neighbor on each of the n_devices - 1
    rotations (the block table stays put and is rewritten locally to the
    rotating owner's page ids, so only payload bytes move)."""
    fmt = get_format(fmt) if fmt is not None else None
    item = 4 if fmt is None else fmt.container_dtype.dtype.itemsize
    shard = (num_pages // n_devices) * page_size * n_kv * head_dim * item
    return 2 * shard * (n_devices - 1)
