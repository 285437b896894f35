"""Pallas decode over a paged *latent* pool (MLA, DeepSeek-V2).

Latent attention caches one row per token and layer: the compressed KV
``c`` (``kv_lora_rank`` values, after its RMSNorm) and the rope key
``k_pe`` (``qk_rope_head_dim`` values, after its rope), stored in the
policy's kv_cache format and padded to whole 128-lane tiles
(``paged_cache.CacheSpec``).  Decode runs *absorbed*: every head's query
is carried into the latent space (``q~ = [W_UK^T q_nope, q_pe]``), so one
cached row serves as the key of all heads and its first ``v_dim`` values
as their value; the caller maps the result back out through ``W_UV``.

``latent_decode``
    Grid (B, pages_per_seq), pages innermost ("arbitrary"), the block
    table and lengths as scalar-prefetch operands like
    ``paged_attention.paged_decode``: each grid step DMAs one physical
    page of rows straight from the pool, decodes it in-register (the
    shared codec), and folds it into every head's running (max, sum, acc)
    online softmax.  Each row is read from HBM once per step for all
    heads.  Pages past a sequence's length map to its last live page, so
    the pipeline fetches nothing new for them and the body skips them.

``latent_decode_reference`` is the XLA oracle: gather the pool through the
block table, decode, then the same masked softmax and mix in f32.

``latent_prefill``
    The prefill runs un-absorbed: per head a causal attention of the
    prompt over itself with query/key width ``qk_nope + qk_rope`` (192) and
    value width ``v_head_dim`` (128).  Grid (B, H, query blocks, key
    blocks), key blocks innermost: the scores of one (query block, key
    block) tile live in VMEM only, tiles above the diagonal are skipped,
    and their key index stays on the last live block, so the pipeline
    fetches nothing for them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import CompilerParams
from repro.core.formats import get_format

from .flash_attention import (NEG_INF, _finalize, _online_update,
                              _payload_to_f32)
from .paged_cache import gather_pages


def _kernel(len_ref, tbl_ref, q_ref, row_ref, o_ref, acc_ref, m_ref, l_ref,
            *, fmt, scale, page, n_pages, v_dim):
    b, pi = pl.program_id(0), pl.program_id(1)
    n = len_ref[b]

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((pi * page < n) & (tbl_ref[b, pi] >= 0))
    def _fold():
        rows = _payload_to_f32(row_ref[...], fmt)              # (page, W)
        q = q_ref[...].astype(jnp.float32)                     # (H, W)
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _online_update(s, rows[:, :v_dim], acc_ref, m_ref, l_ref, pos < n)

    @pl.when(pi == n_pages - 1)
    def _flush():
        o_ref[...] = _finalize(acc_ref, l_ref)


def latent_decode(q, pool, fmt, lengths, block_tables, *, scale: float,
                  v_dim: int, interpret: bool | None = None):
    """One absorbed query token per sequence over a paged latent pool.

    q:            (B, H, W) float -- absorbed queries, zero past the
                  latent values (W = the pool's stored row width).
    pool:         (num_pages, page_size, W) packed (e, m) containers when
                  ``fmt`` is given, or plain floats.
    lengths:      (B,) int32 valid tokens per sequence.
    block_tables: (B, pages_per_seq) int32; -1 = unmapped (masked).
    Returns (B, H, v_dim) float32: softmax(scale * q . row) @ row[:v_dim].
    """
    fmt = get_format(fmt) if fmt is not None else None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, W = q.shape
    num_pages, page = pool.shape[0], pool.shape[1]
    assert pool.shape == (num_pages, page, W), (q.shape, pool.shape)
    n_pages = block_tables.shape[1]
    assert block_tables.shape == (B, n_pages), block_tables.shape
    lengths = jnp.minimum(lengths.astype(jnp.int32), n_pages * page)
    tables = block_tables.astype(jnp.int32)

    def pmap(b, p, lens, tbl):
        # past the length, stay on the last live page: an unchanged block
        # index makes the pipeline skip the copy
        last = jnp.maximum((lens[b] - 1) // page, 0)
        return (jnp.maximum(tbl[b, jnp.minimum(p, last)], 0), 0, 0)

    qmap = lambda b, p, lens, tbl: (b, 0, 0)                # noqa: E731
    kern = functools.partial(_kernel, fmt=fmt, scale=np.float32(scale),
                             page=page, n_pages=n_pages, v_dim=v_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[pl.BlockSpec((None, H, W), qmap),
                  pl.BlockSpec((None, page, W), pmap)],
        out_specs=pl.BlockSpec((None, H, v_dim), qmap),
        scratch_shapes=[pltpu.VMEM((H, v_dim), jnp.float32),
                        pltpu.VMEM((H, 128), jnp.float32),
                        pltpu.VMEM((H, 128), jnp.float32)],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, v_dim), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="latent_decode",
    )(lengths, tables, q.astype(jnp.float32), pool)


def latent_decode_reference(q, pool, fmt, lengths, block_tables, *,
                            scale: float, v_dim: int):
    """The XLA oracle for :func:`latent_decode`: gather the pool through
    the block table (the copy the kernel's DMA avoids), decode, then the
    masked softmax and the mix in f32."""
    fmt = get_format(fmt) if fmt is not None else None
    page = pool.shape[1]
    rows = _payload_to_f32(gather_pages(pool, block_tables), fmt)  # (B,S,W)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    pos = jnp.arange(s.shape[-1])[None, :]
    mapped = jnp.repeat(block_tables >= 0, page, axis=1)
    valid = ((pos < lengths.astype(jnp.int32)[:, None]) & mapped)[:, None]
    s = jnp.where(valid, s, NEG_INF)
    p = jnp.where(valid, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    den = jnp.sum(p, -1, keepdims=True)
    out = jnp.einsum("bhs,bsv->bhv", p, rows[..., :v_dim],
                     preferred_element_type=jnp.float32)
    return jnp.where(den > 0, out / jnp.where(den > 0, den, 1.0), 0.0)


# ---------------------------------------------------------------------------
# un-absorbed causal prefill
# ---------------------------------------------------------------------------

PREFILL_BLOCK_Q = 512
PREFILL_BLOCK_KV = 512


def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                    scale, block_q, block_kv, n_kv):
    qi_blk, si = pl.program_id(2), pl.program_id(3)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(si * block_kv <= qi_blk * block_q + block_q - 1)
    def _fold():
        # operands in their stored precision, products accumulated in f32
        v = v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qi = qi_blk * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ki = si * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = ki <= qi
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(si == n_kv - 1)
    def _flush():
        o_ref[...] = _finalize(acc_ref, l_ref)


def latent_prefill(q, k, v, *, scale: float,
                   block_q: int = PREFILL_BLOCK_Q,
                   block_kv: int = PREFILL_BLOCK_KV,
                   interpret: bool | None = None):
    """Causal self-attention of a prompt, head by head, in VMEM tiles.

    q, k: (B, S, H, dk); v: (B, S, H, dv), all of one float dtype (the
    products take them as they are and accumulate in f32).
    Returns (B, S, H, dv) float32: softmax(scale * q . k, causal) @ v.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    assert k.shape == (B, S, H, dk) and v.shape[:3] == (B, S, H), (
        q.shape, k.shape, v.shape)
    bq, bkv = min(block_q, S), min(block_kv, S)
    # padded queries are sliced off below; padded keys lie after every
    # real query, so the causal mask hides them
    n_q, n_kv = -(-S // bq), -(-S // bkv)
    pad = lambda x, n: jnp.pad(x, ((0, 0), (0, n - S), (0, 0), (0, 0)))  # noqa
    # head-major, so that a block is a (rows, width) slab of one head
    q = jnp.swapaxes(pad(q, n_q * bq), 1, 2)
    k = jnp.swapaxes(pad(k, n_kv * bkv), 1, 2)
    v = jnp.swapaxes(pad(v, n_kv * bkv), 1, 2)

    def kv_map(b, h, i, s):
        # above the diagonal, stay on the last live block: no new copy
        return (b, h, jnp.minimum(s, (i * bq + bq - 1) // bkv), 0)

    kern = functools.partial(_prefill_kernel, scale=np.float32(scale),
                             block_q=bq, block_kv=bkv, n_kv=n_kv)
    out = pl.pallas_call(
        kern,
        grid=(B, H, n_q, n_kv),
        in_specs=[pl.BlockSpec((None, None, bq, dk),
                               lambda b, h, i, s: (b, h, i, 0)),
                  pl.BlockSpec((None, None, bkv, dk), kv_map),
                  pl.BlockSpec((None, None, bkv, dv), kv_map)],
        out_specs=pl.BlockSpec((None, None, bq, dv),
                               lambda b, h, i, s: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, n_q * bq, dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, dv), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="latent_prefill",
    )(q, k, v)
    return jnp.swapaxes(out, 1, 2)[:, :S]
