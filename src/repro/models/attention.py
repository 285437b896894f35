"""Grouped-query attention with transprecision KV caches.

Paths:
  * full       -- training / short prefill: materialized (B, H, S, S) scores
                  (per-layer remat bounds the live buffer).
  * chunked    -- long prefill: Python-unrolled q-chunks, each attending the
                  causal KV prefix; score memory is O(chunk * S) and the HLO
                  stays loop-free (exact cost_analysis; see DESIGN.md).
  * decode     -- one token against a cached KV of length S_max.

The KV cache is stored in the policy's ``kv_cache`` format (binary8/e5m2 by
default policy => 4x less HBM per token than f32, the paper's
memory-access reduction applied to serving).  Sliding-window archs keep a
ring buffer of ``window`` entries.

Attention backends (``decode_impl`` on the config, overridable per policy)
are resolved through the registry in ``repro.kernels.dispatch``; this
module registers the model-level adapters at import time.  Legal spellings
compose a wrapper with a base backend, ``wrapper+base``:

  * ``"xla"``          -- dequantize the cache through XLA, then
                          dot/softmax/dot (oracle and fallback).
  * ``"flash_pallas"`` -- fused Pallas kernel (kernels/flash_attention.py)
                          that reads the packed KV payload bits directly and
                          decodes tiles in-register: the bandwidth-bound
                          decode step moves container-width bytes (4x less
                          than f32 for binary8).  Also serves causal prefill
                          (differentiable; backward recomputes via the XLA
                          reference).  Runs in interpret mode off-TPU.
                          Precision note: operand *storage* formats are
                          honored (values enter the kernel exactly as
                          stored), but softmax probabilities live and die in
                          VMEM registers, so the ``attn_probs`` narrowing
                          the XLA paths apply to their *materialized* probs
                          does not occur -- the fused paths are strictly
                          wider (f32 probs/accumulation), never narrower.
  * ``"flash_shmap"``  -- a *wrapper*: shard_map any inner decode backend
                          over the cache's sequence axis (mesh axis
                          "model") and merge the per-shard online-softmax
                          partials (max / sum-correction combine) with
                          three tiny collectives.  ``"flash_shmap"`` alone
                          means ``"flash_shmap+xla"``.
  * ``"flash_shmap+flash_pallas"``
                       -- the composed multi-chip serving path: every
                          device streams its own 1/n_model of the *packed*
                          cache through the fused kernel; exact softmax
                          attention (tests pin it to the XLA oracle at
                          <= 1e-6 on a 2-device host mesh).
  * ``"paged"``        -- block-table decode over a shared page pool
                          (kernels/paged_attention.py + paged_cache.py):
                          the continuous-batching cache layout.  Reads a
                          :class:`~repro.kernels.paged_cache.PagedKVCache`
                          directly, or any contiguous ``KVCache`` through
                          the degenerate identity paging
                          (``paged_view_of_contiguous``).  Composes as
                          ``"flash_shmap+paged"``: the *pool* is sharded
                          over the mesh's model axis.
  * ``"ring"``         -- the other merge topology: same sharding as
                          ``flash_shmap`` (sequence axis, or pool page
                          axis for ``ring+paged``), but the K/V shards
                          *rotate* around the mesh ring via neighbor-only
                          ``ppermute`` while each device folds every
                          incoming shard into its running online-softmax
                          state -- no all-to-one collective, peak
                          per-device live KV is one shard.  ``"ring"``
                          alone means ``"ring+xla"`` (the debuggable
                          oracle spelling); ``"ring+flash_pallas"`` and
                          ``"ring+paged"`` are the serving compositions
                          (conformance-pinned <= 1e-6 on a 2-device mesh).

Prefill (fresh and continuation-from-packed-cache) goes through the same
registry (``dispatch.resolve_prefill``); a composed spelling resolves to
its base backend there.  ``prefill_to_cache`` is a thin wrapper over
:func:`mha` with ``cache_capacity`` -- the cache is built from the very
K/V the attention consumed, not a private recompute path -- and
:func:`prefill_from_cache` appends a continuation chunk to an existing
packed cache and attends over prefix+chunk via the registry (the flash
backend reads the packed payload directly).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import PrecisionPolicy
from repro.kernels import dispatch, paged_cache
from repro.kernels.paged_cache import PagedKVCache
from .layers import (act_cast, aeinsum, dense_init, norm_init, pdot,
                     peinsum, rmsnorm, rope)

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_max, n_kv, dh) in kv_cache dtype; latent rows
    v: jax.Array  # (B, S_max, width) with v None for an MLA layer
    pos: jax.Array  # () int32 -- next write position (monotonic)

    @property
    def capacity(self):
        return self.k.shape[1]


def attn_init(key, cfg, dtype, cross: bool = False):
    if cfg.latent:
        return mla_init(key, cfg, dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, cfg.q_dim), dtype=dtype),
        "wk": dense_init(ks[1], (d, cfg.kv_dim), dtype=dtype),
        "wv": dense_init(ks[2], (d, cfg.kv_dim), dtype=dtype),
        "wo": dense_init(ks[3], (cfg.q_dim, d), dtype=dtype),
    }


def init_cache(cfg, batch, length, policy: PrecisionPolicy,
               layer_kinds=None) -> list:
    """Per-layer KV caches (attention layers only; None elsewhere)."""
    kinds = layer_kinds if layer_kinds is not None else cfg.attn_pattern
    dt = policy.dtype("kv_cache")
    caches = []
    for kind in kinds:
        if kind != "attn":
            caches.append(None)
            continue
        cap = length if cfg.window is None else min(length, cfg.window)
        if cfg.latent:
            z = jnp.zeros((batch, cap) + cache_spec(cfg).stored_row, dt)
            caches.append(KVCache(k=z, v=None,
                                  pos=jnp.zeros((), jnp.int32)))
            continue
        z = jnp.zeros((batch, cap, cfg.n_kv, cfg.head_dim), dt)
        caches.append(KVCache(k=z, v=z, pos=jnp.zeros((), jnp.int32)))
    return caches


def cache_spec(cfg) -> paged_cache.CacheSpec:
    """What one attention layer of ``cfg`` caches per token."""
    if cfg.latent:
        return paged_cache.CacheSpec((cfg.latent_dim,), latent=True)
    return paged_cache.CacheSpec((cfg.n_kv, cfg.head_dim))


def _split_heads(x, n, dh):
    B, S, _ = x.shape
    return x.reshape(B, S, n, dh)


def _gqa_scores(q, k, policy):
    """q: (B, Sq, n_kv, G, dh); k: (B, Skv, n_kv, dh) -> (B, n_kv, G, Sq, Skv)
    f32 accumulation."""
    return peinsum("bqhgd,bkhd->bhgqk", q, k, policy, "attn_w", out_act=False)


def _softmax_weighted(scores_f32, v, policy, valid=None):
    """softmax in f32 (range-critical), probs re-cast to attn_probs format,
    then prob @ v with f32 accumulation.

    ``valid`` (broadcastable bool over the score shape) re-masks the probs:
    a FULLY-masked row's softmax degrades to uniform 1/S (the mean of V
    instead of the zero every masked path must produce), while rows with
    any valid slot are untouched (their masked probs are exactly 0 already)
    -- caught by tests/test_conformance.py on zero-length decode rows."""
    probs = jax.nn.softmax(scores_f32, axis=-1)
    if valid is not None:
        probs = jnp.where(valid, probs, 0.0)
    probs = act_cast(probs, policy, "attn_probs")
    out = peinsum("bhgqk,bkhd->bqhgd", probs, v, policy, "attn_w")
    return out


def _causal_mask(sq, skv, q_offset, window: Optional[int]):
    qi = jnp.arange(sq)[:, None] + q_offset
    ki = jnp.arange(skv)[None, :]
    m = ki <= qi
    if window is not None:
        m &= ki > qi - window
    return m  # (sq, skv) bool


def _dequant_cache(ck, cv, policy):
    """Bring cache arrays into the XLA compute representation (the oracle /
    fallback op order; see EXPERIMENTS.md Perf #3 for the bf16 fast path)."""
    if policy.mode == "native" and ck.dtype != jnp.float32:
        # dequantize straight to the compute dtype: one fusable cast instead
        # of the f8 -> f32 -> act-format double materialization.  e5m2 ->
        # bf16 is exact (2-bit significand subset); dots accumulate in f32.
        return ck.astype(jnp.bfloat16), cv.astype(jnp.bfloat16)
    return (act_cast(ck.astype(jnp.float32), policy),
            act_cast(cv.astype(jnp.float32), policy))


def _cache_payload(ck, cv, policy):
    """Cache arrays -> (k_payload, v_payload, fmt) for packed-KV kernels.

    The cache's native narrow dtype is bit-identical to the packed (e, m)
    container (QTensor.from_native), so the payload is a pure bitcast and
    HBM streams container-width bytes.  Emulated mode stores sanitized f32
    values; binary32 is f32 -- both read unpacked (fmt None).
    """
    fmt = policy.fmt("kv_cache")
    if policy.mode == "native" and not fmt.is_binary32:
        return (jax.lax.bitcast_convert_type(ck, fmt.container_dtype),
                jax.lax.bitcast_convert_type(cv, fmt.container_dtype), fmt)
    return ck.astype(jnp.float32), cv.astype(jnp.float32), None


# ---------------------------------------------------------------------------
# registered decode backends (contract: see kernels/dispatch.py)
# ---------------------------------------------------------------------------

@dispatch.register_decode("xla")
def _decode_xla(q, ck, cv, n_valid, *, scale, policy,
                return_residuals: bool = False):
    """Dequantize-through-XLA decode: the oracle and the fallback."""
    kk, vv = _dequant_cache(ck, cv, policy)
    qg = q[:, None]                                   # (B, 1, H, G, dh)
    scores = _gqa_scores(qg, kk, policy).astype(jnp.float32) * scale
    valid = (jnp.arange(ck.shape[1])[None, :]
             < n_valid.astype(jnp.int32)[:, None])    # (B, S)
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    if not return_residuals:
        # valid= zeroes fully-masked (zero-length) rows, which plain
        # softmax would turn into the mean of V
        return _softmax_weighted(scores, vv, policy,
                                 valid[:, None, None, None, :])[:, 0]
    m = jnp.max(scores, axis=-1)                      # (B, H, G, 1)
    e = jnp.exp(scores - m[..., None])
    e = jnp.where(valid[:, None, None, None, :], e, 0.0)
    l = jnp.sum(e, axis=-1)
    # explicit zero guard: a subnormal epsilon would be flushed by XLA
    # CPU's FTZ and divide 0/0
    ln = l[..., None]
    probs = act_cast(jnp.where(ln > 0, e / jnp.where(ln > 0, ln, 1.0), 0.0),
                     policy, "attn_probs")
    out = peinsum("bhgqk,bkhd->bqhgd", probs, vv, policy, "attn_w",
                  out_act=False)
    return out[:, 0], m[..., 0], l[..., 0]


@dispatch.register_decode("flash_pallas")
def _decode_flash_pallas(q, ck, cv, n_valid, *, scale, policy,
                         return_residuals: bool = False):
    """Fused packed-KV flash decode (kernels/flash_attention.py): HBM
    streams container-width bytes -- the paper's memory-access reduction
    applied *inside* the bandwidth-bound step."""
    from repro.kernels.flash_attention import flash_decode

    kp, vp, fmt = _cache_payload(ck, cv, policy)
    return flash_decode(q.astype(jnp.float32), kp, vp, fmt,
                        n_valid.astype(jnp.int32), scale=scale,
                        return_residuals=return_residuals)


@dispatch.register_decode("paged")
def _decode_paged(q, ck, cv, n_valid, *, scale, policy, block_tables=None,
                  return_residuals: bool = False):
    """Block-table decode over the shared page pool
    (kernels/paged_attention.py): ck/cv are the pools
    (num_pages, page_size, H, dh) in storage dtype, ``n_valid`` the
    per-slot sequence lengths, ``block_tables`` the logical->physical page
    map.  The packed payload is gathered page-by-page via scalar-prefetch
    DMA and decoded in-register -- the 4x byte win on a non-contiguous,
    continuously-batched cache."""
    from repro.kernels.paged_attention import paged_decode

    if block_tables is None:
        raise ValueError(
            "decode_impl 'paged' reads the cache through a block table; "
            "pass block_tables=(B, pages_per_seq) int32 (use a PagedKVCache "
            "or paged_cache.paged_view_of_contiguous for a contiguous one)")
    kp, vp, fmt = _cache_payload(ck, cv, policy)
    return paged_decode(q.astype(jnp.float32), kp, vp, fmt,
                        n_valid.astype(jnp.int32), block_tables, scale=scale,
                        return_residuals=return_residuals)


# ---------------------------------------------------------------------------
# registered prefill backends
# ---------------------------------------------------------------------------

@dispatch.register_prefill("xla")
def _prefill_xla(qg, k, v, *, scale, policy, window, prefix_len, chunk,
                 q_offset: int = 0, fmt=None):
    """Causal prefill through XLA: full masked softmax, or the unrolled
    q-chunked loop for long sequences (score memory O(chunk * S), loop-free
    HLO)."""
    if fmt is not None:  # packed payload (prefill-from-packed-cache reuse)
        from repro.core.qtensor import decode as _qdecode
        k = act_cast(_qdecode(k, fmt), policy)
        v = act_cast(_qdecode(v, fmt), policy)
    B, S = qg.shape[0], qg.shape[1]
    skv = k.shape[1]
    if chunk is not None and S > chunk:
        # ---- unrolled q-chunked causal prefill ----------------------------
        n_chunks = (S + chunk - 1) // chunk
        outs = []
        for ci in range(n_chunks):
            lo, hi = ci * chunk, min((ci + 1) * chunk, S)
            kv_hi = q_offset + hi
            if prefix_len > kv_hi:
                kv_hi = prefix_len
            kv_hi = min(kv_hi, skv)
            qs = jax.lax.slice_in_dim(qg, lo, hi, axis=1)
            ks = jax.lax.slice_in_dim(k, 0, kv_hi, axis=1)
            vs = jax.lax.slice_in_dim(v, 0, kv_hi, axis=1)
            scores = _gqa_scores(qs, ks, policy).astype(jnp.float32) * scale
            m = _causal_mask(hi - lo, kv_hi, q_offset + lo, window)
            if prefix_len:
                pm = (jnp.arange(kv_hi)[None, :] < prefix_len)
                m = m | pm
            scores = jnp.where(m[None, None, None, :, :], scores, NEG_INF)
            outs.append(_softmax_weighted(scores, vs, policy))
        return jnp.concatenate(outs, axis=1)
    # ---- full attention ----------------------------------------------------
    scores = _gqa_scores(qg, k, policy).astype(jnp.float32) * scale
    m = _causal_mask(S, skv, q_offset, window)
    if prefix_len:
        m = m | (jnp.arange(skv)[None, :] < prefix_len)
    scores = jnp.where(m[None, None, None, :, :], scores, NEG_INF)
    return _softmax_weighted(scores, v, policy)


@dispatch.register_prefill("flash_pallas")
def _prefill_flash_pallas(qg, k, v, *, scale, policy, window, prefix_len,
                          chunk, q_offset: int = 0, fmt=None):
    """Fused chunked-causal prefill: the q-chunk loop lives in the Pallas
    grid instead of unrolled Python, score memory is O(block_q * block_kv)
    VMEM.  Float K/V (fresh prefill) is differentiable -- backward
    recomputes via the XLA reference; packed K/V (``fmt`` set) reads the
    cache payload in-register (continuation / cache-reuse)."""
    from repro.kernels.flash_attention import (DEFAULT_BLOCK_Q, flash_prefill,
                                               flash_prefill_diff)

    # chunk is the XLA path's q-chunk (up to attn_chunk=4096); as a Pallas
    # block it only tiles the grid, so clamp it to a VMEM-sized block
    bq = min(chunk or DEFAULT_BLOCK_Q, DEFAULT_BLOCK_Q)
    if fmt is None:
        out = flash_prefill_diff(
            qg.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), scale=scale, window=window,
            prefix_len=prefix_len, q_offset=q_offset, block_q=bq)
    else:
        out = flash_prefill(
            qg.astype(jnp.float32), k, v, fmt, scale=scale, window=window,
            prefix_len=prefix_len, q_offset=q_offset, block_q=bq)
    return act_cast(out, policy)


@dispatch.register_prefill("paged")
def _prefill_paged(qg, k, v, *, scale, policy, window, prefix_len, chunk,
                   q_offset: int = 0, fmt=None):
    """Prefill for the paged backend.  Paging is a property of how the
    *cache* is stored, not of fresh prefill K/V (dense activations that
    exist contiguously anyway), so attention delegates to the fused flash
    prefill; the serving loop then writes the resulting cache into pages
    (``paged_cache.write_prefill`` -- prefill-to-pages through this same
    registry dispatch)."""
    return _prefill_flash_pallas(qg, k, v, scale=scale, policy=policy,
                                 window=window, prefix_len=prefix_len,
                                 chunk=chunk, q_offset=q_offset, fmt=fmt)


# ---------------------------------------------------------------------------
# the attention entry points
# ---------------------------------------------------------------------------

def mha(p, x, cfg, policy: PrecisionPolicy, *,
        positions=None, causal: bool = True,
        prefix_len: int = 0,
        cache: Optional[KVCache] = None,
        kv_source=None,
        chunk: Optional[int] = None,
        cache_capacity: Optional[int] = None):
    """General attention entry point.

    kv_source: cross-attention source sequence (enc-dec); disables causal.
    prefix_len: bidirectional prefix (prefix-LM / VLM).
    cache: decode mode -- x is (B, 1, d), cache is updated and returned.
    chunk: q-chunked long prefill.
    cache_capacity: prefill-to-cache mode -- build and return a populated
        KVCache of this capacity from the K/V this very call attended with
        (no recompute; the registry path and the cache see the same bits).
    Returns (out, new_cache).
    """
    if cfg.latent:
        if kv_source is not None or prefix_len or not causal:
            raise ValueError("latent attention (MLA) is causal self-"
                             "attention only")
        return mla(p, x, cfg, policy, positions=positions, cache=cache,
                   cache_capacity=cache_capacity)
    B, S, _ = x.shape
    n_kv, dh = cfg.n_kv, cfg.head_dim
    G = cfg.n_heads // n_kv

    q = _split_heads(pdot(x, p["wq"], policy, "attn_w"), cfg.n_heads, dh)
    if kv_source is None:
        k = _split_heads(pdot(x, p["wk"], policy, "attn_w"), n_kv, dh)
        v = _split_heads(pdot(x, p["wv"], policy, "attn_w"), n_kv, dh)
    else:
        k = _split_heads(pdot(kv_source, p["wk"], policy, "attn_w"), n_kv, dh)
        v = _split_heads(pdot(kv_source, p["wv"], policy, "attn_w"), n_kv, dh)
        causal = False

    # paged caches have one write position *per slot* (ragged continuous
    # batching), contiguous caches a single scalar ``pos``
    paged = isinstance(cache, PagedKVCache)
    cache_pos = 0
    if cache is not None:
        cache_pos = cache.seq_lens[:, None] if paged else cache.pos
    if positions is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32) + cache_pos
    if kv_source is None and cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, jnp.arange(k.shape[1])[None, :] + cache_pos,
                 cfg.rope_theta)

    scale = np.float32(1.0 / np.sqrt(dh))
    qg = q.reshape(B, S, n_kv, G, dh)
    impl = decode_impl(cfg, policy)

    new_cache = None
    if paged:
        # ---- decode over a paged (block-table) cache ----------------------
        if S != 1:
            raise ValueError("paged KV caches decode one token at a time; "
                             "prefill lands via paged_cache.write_chunk")
        if cfg.window is not None and cache.capacity > cfg.window:
            raise ValueError(
                f"paged KV cache capacity {cache.capacity} exceeds the "
                f"sliding window {cfg.window}; size the pool so "
                f"pages_per_seq * page_size <= window (every cached token "
                f"then sits inside the window) or use a contiguous KVCache "
                f"ring buffer")
        with jax.named_scope("kv_append"):
            new_cache = paged_cache.append_decode(cache, k, v)
        fn = dispatch.resolve_decode(impl)
        if dispatch.canonicalize_impl(impl)[-1] == "paged":
            with jax.named_scope("attn_kernel"):
                out = fn(qg[:, 0], new_cache.k_pool, new_cache.v_pool,
                         new_cache.seq_lens, scale=scale, policy=policy,
                         block_tables=new_cache.block_tables)
        else:
            # contiguous-impl bridge (the reverse of
            # paged_view_of_contiguous): gather every slot's pages into the
            # (B, pages_per_seq * page_size, H, dh) view and hand the
            # per-slot lengths to the contiguous decode contract.  Unmapped
            # pages alias physical page 0 in the gather; their positions sit
            # at or beyond seq_lens, which every decode backend masks -- so
            # ALL registry spellings serve one paged state.
            with jax.named_scope("gather_pages"):
                ckg = paged_cache.gather_pages(new_cache.k_pool,
                                               new_cache.block_tables)
                cvg = paged_cache.gather_pages(new_cache.v_pool,
                                               new_cache.block_tables)
            with jax.named_scope("attn_kernel"):
                out = fn(qg[:, 0], ckg, cvg, new_cache.seq_lens,
                         scale=scale, policy=policy)
        out = act_cast(out, policy)[:, None]
    elif cache is not None:
        # ---- decode: append k/v then attend over the cache ----------------
        kq = k.astype(cache.k.dtype)
        vq = v.astype(cache.v.dtype)
        if cfg.window is not None and cache.capacity == cfg.window:
            slot = jnp.mod(cache.pos, cache.capacity)
        else:
            slot = jnp.minimum(cache.pos, cache.capacity - 1)
        ck = jax.lax.dynamic_update_slice_in_dim(cache.k, kq, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache.v, vq, slot, axis=1)
        new_cache = KVCache(k=ck, v=cv, pos=cache.pos + S)
        # valid positions: slot index occupied (pos' = pos + S); a full ring
        # buffer has every slot valid (order is irrelevant under softmax)
        if cfg.window is not None and cache.capacity == cfg.window:
            n_valid = jnp.minimum(cache.pos + S, cache.capacity)
        else:
            n_valid = cache.pos + S
        if S == 1:
            fn = dispatch.resolve_decode(impl)
            lengths = jnp.broadcast_to(
                jnp.asarray(n_valid, jnp.int32)[None], (B,))
            if dispatch.canonicalize_impl(impl)[-1] == "paged":
                # contiguous cache through the paged kernel: the identity
                # block table (same bits, degenerate paging) -- lets the
                # paged backend run anywhere a KVCache does (dry-run cells,
                # oracle tests) without a serving loop.  Clamp the running
                # token count to the true capacity BEFORE the view: its
                # page-granule zero padding sits beyond S, and an
                # unclamped count would let those slots dilute the softmax
                lengths = jnp.minimum(lengths, ck.shape[1])
                kp_, vp_, tbl = paged_cache.paged_view_of_contiguous(ck, cv)
                out = fn(qg[:, 0], kp_, vp_, lengths, scale=scale,
                         policy=policy, block_tables=tbl)
            else:
                out = fn(qg[:, 0], ck, cv, lengths, scale=scale,
                         policy=policy)
            out = act_cast(out, policy)[:, None]
        else:
            # legacy multi-token append: every new token attends the whole
            # occupied cache (no intra-chunk causality; used nowhere on the
            # serving path -- prefer prefill_from_cache for continuation)
            kk, vv = _dequant_cache(ck, cv, policy)
            scores = _gqa_scores(qg, kk, policy).astype(jnp.float32) * scale
            valid = jnp.arange(cache.capacity) < n_valid
            scores = jnp.where(valid[None, None, None, None, :], scores,
                               NEG_INF)
            out = _softmax_weighted(scores, vv, policy)
    elif causal and kv_source is None:
        # ---- prefill through the registry ---------------------------------
        fn = dispatch.resolve_prefill(impl)
        with jax.named_scope("attn_kernel"):
            out = fn(qg, k, v, scale=scale, policy=policy,
                     window=cfg.window, prefix_len=prefix_len, chunk=chunk)
    else:
        # ---- non-causal full attention (encoder self-attn / cross-attn) ---
        scores = _gqa_scores(qg, k, policy).astype(jnp.float32) * scale
        out = _softmax_weighted(scores, v, policy)

    if cache_capacity is not None and cache is None and kv_source is None:
        new_cache = _build_cache(k, v, cfg, policy, cache_capacity, S)

    out = out.reshape(B, S, cfg.q_dim)
    return pdot(out, p["wo"], policy, "attn_w"), new_cache


def verify_paged(p, x, cfg, policy: PrecisionPolicy, cache: PagedKVCache):
    """Speculative-verify attention: append ``K`` tokens per slot to the
    paged cache, then attend each position through the registered *decode*
    backend -- bit-identical, position by position, to ``K`` sequential
    single-token :func:`mha` decode calls.

    x: (B, K, d) -- the k tokens under verification, batched over slots.
    The projections / rope / output matmul run once over all K positions
    (one weight pass instead of K -- the speculative-decoding win on the
    bandwidth-bound weight stream), while the attention core is a
    Python-unrolled per-position loop over the SAME registry decode
    contract the plain decode step uses: position ``i`` sees
    ``n_valid = seq_lens_before + i + 1`` (its own token included), entries
    written for later positions sit at or beyond that bound and every
    backend masks them.  A slot whose block-table row is masked (-1)
    drops all K writes, keeps its length frozen, and produces the same
    discarded garbage row as the plain decode step.

    Returns (out (B, K, q_dim), new_cache with K appended per mapped slot).
    The caller rolls back rejected positions by truncating ``seq_lens``
    (:func:`repro.kernels.paged_cache.truncate_seq_lens`) -- entries past
    the truncation point are stale bytes every reader masks.
    """
    _refuse_latent(cfg, "verify_paged")
    B, K, _ = x.shape
    n_kv, dh = cfg.n_kv, cfg.head_dim
    G = cfg.n_heads // n_kv
    if cfg.window is not None and cache.capacity > cfg.window:
        raise ValueError(
            f"paged KV cache capacity {cache.capacity} exceeds the sliding "
            f"window {cfg.window}; size the pool so pages_per_seq * "
            f"page_size <= window")

    q = _split_heads(pdot(x, p["wq"], policy, "attn_w"), cfg.n_heads, dh)
    k = _split_heads(pdot(x, p["wk"], policy, "attn_w"), n_kv, dh)
    v = _split_heads(pdot(x, p["wv"], policy, "attn_w"), n_kv, dh)
    base = cache.seq_lens
    positions = base[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    with jax.named_scope("kv_append"):
        new_cache = paged_cache.append_block(cache, k, v)

    scale = np.float32(1.0 / np.sqrt(dh))
    qg = q.reshape(B, K, n_kv, G, dh)
    impl = decode_impl(cfg, policy)
    fn = dispatch.resolve_decode(impl)
    paged_base = dispatch.canonicalize_impl(impl)[-1] == "paged"
    if not paged_base:
        # contiguous-impl bridge, hoisted: one gather serves all K
        # positions -- entries at or beyond each position's n_valid are
        # masked by the backend, so the post-append view is exact for
        # every position (same reasoning as the mha paged branch)
        with jax.named_scope("gather_pages"):
            ckg = paged_cache.gather_pages(new_cache.k_pool,
                                          new_cache.block_tables)
            cvg = paged_cache.gather_pages(new_cache.v_pool,
                                          new_cache.block_tables)
    outs = []
    for i in range(K):
        # frozen (masked / unmapped) slots advanced 0..i tokens; clamping
        # to the post-append length reproduces the sequential decode
        # step's n_valid exactly for every slot
        n_valid = jnp.minimum(base + (i + 1), new_cache.seq_lens)
        with jax.named_scope("attn_kernel"):
            if paged_base:
                o = fn(qg[:, i], new_cache.k_pool, new_cache.v_pool,
                       n_valid, scale=scale, policy=policy,
                       block_tables=new_cache.block_tables)
            else:
                o = fn(qg[:, i], ckg, cvg, n_valid, scale=scale,
                       policy=policy)
        outs.append(act_cast(o, policy))
    out = jnp.stack(outs, axis=1)
    out = out.reshape(B, K, cfg.q_dim)
    return pdot(out, p["wo"], policy, "attn_w"), new_cache


def decode_impl(cfg, policy: PrecisionPolicy) -> str:
    """Resolve the attention backend: the policy override (serving-time
    knob, no model rebuild) wins over the config default."""
    return (getattr(policy, "decode_impl", None)
            or getattr(cfg, "decode_impl", "xla"))


def _build_cache(k, v, cfg, policy, capacity: int, S: int) -> KVCache:
    """Populate a fresh KVCache from prefill K/V (post-rope, pre-cast).

    Ring-buffer invariant: the token at absolute position ``p`` lives at
    slot ``p % cap`` -- the same convention the decode path writes with
    (``slot = pos % cap``), so the first decode step after a long prefill
    overwrites the *oldest* cached token, not an arbitrary one.
    """
    dt = policy.dtype("kv_cache")
    cap = capacity if cfg.window is None else min(capacity, cfg.window)
    take = min(S, cap)
    kk = k[:, S - take:].astype(dt)
    vv = v[:, S - take:].astype(dt)
    if take == cap and (S - take) % cap:
        # full ring: rotate so position p sits at slot p % cap
        kk = jnp.roll(kk, (S - take) % cap, axis=1)
        vv = jnp.roll(vv, (S - take) % cap, axis=1)
        ck, cv = kk, vv
    else:
        ck = jnp.zeros((k.shape[0], cap, cfg.n_kv, cfg.head_dim), dt)
        cv = jnp.zeros_like(ck)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, kk, 0, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, vv, 0, axis=1)
    return KVCache(k=ck, v=cv, pos=jnp.asarray(S, jnp.int32))


def prefill_to_cache(p, x, cfg, policy, capacity: int, positions=None,
                     prefix_len: int = 0, chunk=None):
    """Run prefill attention AND produce the populated cache for decode.

    A thin wrapper over :func:`mha` with ``cache_capacity``: attention and
    cache share one K/V computation and one dispatch path."""
    return mha(p, x, cfg, policy, positions=positions, causal=True,
               prefix_len=prefix_len, chunk=chunk, cache_capacity=capacity)


def prefill_from_cache(p, x, cfg, policy, cache: KVCache, q_offset: int,
                       prefix_len: int = 0, chunk=None):
    """Continuation (chunked) prefill against an existing packed cache.

    Appends this chunk's K/V at static position ``q_offset``, then attends
    the chunk's queries causally over prefix + chunk through the SAME
    registry dispatch as decode/prefill: the ``flash_pallas`` base backend
    reads the packed cache payload directly (no wide materialization), the
    ``xla`` base backend dequantizes -- no private code path.

    Requires a non-ring cache with ``capacity >= q_offset + S``.
    Returns (out, new_cache with pos = q_offset + S).
    """
    _refuse_latent(cfg, "prefill_from_cache")
    B, S, _ = x.shape
    n_kv, dh = cfg.n_kv, cfg.head_dim
    G = cfg.n_heads // n_kv
    if cfg.window is not None and cache.capacity == cfg.window:
        raise ValueError("prefill_from_cache does not support ring-buffer "
                         "(sliding-window) caches; decode step-by-step")
    if q_offset + S > cache.capacity:
        raise ValueError(f"chunk [{q_offset}, {q_offset + S}) exceeds cache "
                         f"capacity {cache.capacity}")

    q = _split_heads(pdot(x, p["wq"], policy, "attn_w"), cfg.n_heads, dh)
    k = _split_heads(pdot(x, p["wk"], policy, "attn_w"), n_kv, dh)
    v = _split_heads(pdot(x, p["wv"], policy, "attn_w"), n_kv, dh)
    positions = (jnp.arange(S)[None, :] + q_offset).astype(jnp.int32)
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    ck = jax.lax.dynamic_update_slice_in_dim(
        cache.k, k.astype(cache.k.dtype), q_offset, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache.v, v.astype(cache.v.dtype), q_offset, axis=1)
    new_cache = KVCache(k=ck, v=cv, pos=jnp.asarray(q_offset + S, jnp.int32))

    scale = np.float32(1.0 / np.sqrt(dh))
    qg = q.reshape(B, S, n_kv, G, dh)
    impl = decode_impl(cfg, policy)
    fn = dispatch.resolve_prefill(impl)
    kp, vp, fmt = _cache_payload(ck, cv, policy)
    # slots beyond q_offset + S - 1 are causally masked (ki > every qi), so
    # attending over the full capacity is exact
    out = fn(qg, kp, vp, scale=scale, policy=policy, window=cfg.window,
             prefix_len=prefix_len, chunk=chunk, q_offset=q_offset, fmt=fmt)
    out = out.reshape(B, S, cfg.q_dim)
    return pdot(out, p["wo"], policy, "attn_w"), new_cache


def prefill_paged_chunk(p, x, cfg, policy, cache: PagedKVCache, slot: int,
                        q_offset: int, chunk=None):
    """One chunked-prefill step for ONE sequence, straight into its pages.

    The page-granular sibling of :func:`prefill_from_cache`: compute this
    chunk's K/V, scatter them into ``slot``'s mapped pages at positions
    [q_offset, q_offset + S) (``paged_cache.write_chunk``), then attend the
    chunk's queries causally over the slot's gathered pages through the
    SAME registry prefill dispatch.  The only transient contiguous K/V
    buffer is the chunk itself -- O(chunk) tokens per layer instead of the
    O(prompt) staging cache a whole-prompt ``write_prefill`` needs.

    x: (1, S, d) -- chunked prefill is per-sequence (continuous batching
    admits one request at a time); ``slot``/``q_offset`` must be static
    under jit (the XLA prefill path does Python arithmetic on the offset).
    Positions at or beyond q_offset + S in the gathered view (stale page
    tails, unmapped pages aliasing page 0) are causally masked, so
    attending over the slot's full addressable capacity is exact.
    Returns (out, new_cache with seq_lens[slot] = q_offset + S).
    """
    _refuse_latent(cfg, "prefill_paged_chunk")
    B, S, _ = x.shape
    n_kv, dh = cfg.n_kv, cfg.head_dim
    G = cfg.n_heads // n_kv
    if B != 1:
        raise ValueError("prefill_paged_chunk is per-sequence (B == 1)")
    if cfg.window is not None and cache.capacity > cfg.window:
        raise ValueError(
            f"paged KV cache capacity {cache.capacity} exceeds the sliding "
            f"window {cfg.window}; chunked paged prefill needs every cached "
            f"token inside the window")
    if q_offset + S > cache.capacity:
        raise ValueError(f"chunk [{q_offset}, {q_offset + S}) exceeds the "
                         f"slot capacity {cache.capacity}")

    q = _split_heads(pdot(x, p["wq"], policy, "attn_w"), cfg.n_heads, dh)
    k = _split_heads(pdot(x, p["wk"], policy, "attn_w"), n_kv, dh)
    v = _split_heads(pdot(x, p["wv"], policy, "attn_w"), n_kv, dh)
    positions = (jnp.arange(S)[None, :] + q_offset).astype(jnp.int32)
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    with jax.named_scope("kv_append"):
        new_cache = paged_cache.write_chunk(cache, slot, k[0], v[0],
                                            q_offset)

    scale = np.float32(1.0 / np.sqrt(dh))
    qg = q.reshape(B, S, n_kv, G, dh)
    impl = decode_impl(cfg, policy)
    fn = dispatch.resolve_prefill(impl)
    with jax.named_scope("gather_pages"):
        tbl = new_cache.block_tables[slot:slot + 1]
        ck = paged_cache.gather_pages(new_cache.k_pool, tbl)
        cv = paged_cache.gather_pages(new_cache.v_pool, tbl)
    kp, vp, fmt = _cache_payload(ck, cv, policy)
    with jax.named_scope("attn_kernel"):
        out = fn(qg, kp, vp, scale=scale, policy=policy, window=cfg.window,
                 prefix_len=0, chunk=chunk, q_offset=q_offset, fmt=fmt)
    out = out.reshape(B, S, cfg.q_dim)
    return pdot(out, p["wo"], policy, "attn_w"), new_cache


def _refuse_latent(cfg, what: str) -> None:
    if cfg.latent:
        raise ValueError(
            f"{what}: latent attention (MLA) serves whole-prompt prefill "
            f"and one-token decode only (no chunked prefill, no "
            f"speculative verify)")


# ---------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2 arXiv:2405.04434, no q-LoRA)
# ---------------------------------------------------------------------------
#
# q = x W_q (heads of qk_nope + qk_rope values); [c, k_pe] = x W_kv_a with
# c RMS-normed and k_pe (one rope key shared by all heads) rotated; per
# head k_nope and v come from c through W_kv_b = [W_UK | W_UV].  Prefill
# runs that formulation (un-absorbed): through XLA in blocks of queries,
# or through a Pallas kernel that keeps every score tile in VMEM, so that
# a long prompt's scores stay small beside the pool.  Decode runs absorbed: the
# query goes into the latent space, q~ = [W_UK^T q_nope, q_pe], attends
# the cached rows [c, k_pe] directly, and out = W_UV (sum p c).  The rope
# halves rotate together (the published checkpoint pairs them
# interleaved: with the weights drawn here, a permutation of columns).

MLA_Q_BLOCK = 256      # query rows per block of the prefill attention


def mla_init(key, cfg, dtype):
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H * cfg.head_dim), dtype=dtype),
        "wkv_a": dense_init(ks[1], (d, cfg.latent_dim), dtype=dtype),
        "kv_norm": norm_init(r, "rmsnorm"),
        "wkv_b": dense_init(ks[2], (r, H * (cfg.qk_nope_head_dim
                                           + cfg.v_head_dim)), dtype=dtype),
        "wo": dense_init(ks[3], (H * cfg.v_head_dim, d), dtype=dtype),
    }


def mla_scale(cfg) -> np.float32:
    """Softmax scale: qk head size ** -0.5, times YaRN's mscale squared."""
    m = cfg.yarn.softmax_mscale if cfg.yarn is not None else 1.0
    return np.float32(cfg.head_dim ** -0.5 * m)


def _mla_project(p, x, cfg, policy, positions):
    """(q_nope (B,S,H,nope), q_pe (B,S,H,rope) rotated, latent rows
    (B,S,width): [c normed, k_pe rotated, zero padding])."""
    B, S, _ = x.shape
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = _split_heads(pdot(x, p["wq"], policy, "attn_w"), cfg.n_heads,
                     cfg.head_dim)
    kv = pdot(x, p["wkv_a"], policy, "attn_w")
    c = rmsnorm(kv[..., :r], p["kv_norm"]["gamma"], policy)
    q_pe = rope(q[..., nope:], positions, cfg.rope_theta, cfg.yarn)
    k_pe = rope(kv[..., None, r:], positions, cfg.rope_theta, cfg.yarn)
    pad = cache_spec(cfg).stored_row[0] - cfg.latent_dim
    row = jnp.concatenate(
        [c, k_pe[:, :, 0].astype(c.dtype), jnp.zeros((B, S, pad), c.dtype)],
        axis=-1)
    return q[..., :nope], q_pe, row


def _mla_heads(p, cfg):
    """W_kv_b as (W_UK (r, H, nope), W_UV (r, H, v))."""
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                           cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _mla_prefill(q_nope, q_pe, row, p, cfg, policy):
    """Causal un-absorbed attention over the prompt itself, through the
    latent prefill backend that ``decode_impl`` names: (B, S, H, v)."""
    B, S, H, _ = q_nope.shape
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = _split_heads(pdot(row[..., :r], p["wkv_b"], policy, "attn_w"), H,
                      nope + cfg.v_head_dim)
    k_pe = jnp.broadcast_to(row[:, :, None, r:cfg.latent_dim],
                            (B, S, H, cfg.qk_rope_head_dim))
    k = jnp.concatenate([kv[..., :nope], k_pe.astype(kv.dtype)], axis=-1)
    q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], axis=-1)
    fn = dispatch.resolve_latent_prefill(decode_impl(cfg, policy))
    return fn(q, k, kv[..., nope:], scale=mla_scale(cfg), policy=policy)


@dispatch.register_latent_prefill("xla")
def _latent_prefill_xla(q, k, v, *, scale, policy):
    """``MLA_Q_BLOCK`` queries at a time against every key, masked."""
    B, S, H, _ = q.shape
    # head-major keys and values, laid out once for every query block
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    blk = min(MLA_Q_BLOCK, S)
    nb = -(-S // blk)
    q = jnp.pad(q, ((0, 0), (0, nb * blk - S), (0, 0), (0, 0)))
    q = jnp.moveaxis(q.reshape(B, nb, blk, H, q.shape[-1]), 1, 0)

    def block(args):
        qb, i = args
        s = peinsum("bqhd,bhkd->bhqk", qb, k, policy, "attn_w",
                    out_act=False).astype(jnp.float32) * scale
        causal = (jnp.arange(S)[None, :]
                  <= (i * blk + jnp.arange(blk))[:, None])
        s = jnp.where(causal, s, NEG_INF)
        probs = act_cast(jax.nn.softmax(s, axis=-1), policy, "attn_probs")
        return peinsum("bhqk,bhkd->bqhd", probs, v, policy, "attn_w")

    out = jax.lax.map(block, (q, jnp.arange(nb)))
    return jnp.moveaxis(out, 0, 1).reshape(B, nb * blk, H, -1)[:, :S]


@dispatch.register_latent_prefill("pallas")
def _latent_prefill_pallas(q, k, v, *, scale, policy):
    """The Pallas kernel (kernels/latent_attention.py): each score tile
    stays in VMEM, tiles above the diagonal are skipped."""
    from repro.kernels.latent_attention import latent_prefill

    return act_cast(latent_prefill(q, k, v, scale=scale), policy)


def _latent_contiguous(q, rows, n_valid, *, scale, v_dim):
    """Absorbed decode over a contiguous (B, S, W) latent cache (the
    single-request reference path): f32 softmax and mix."""
    s = aeinsum("bhw,bsw->bhs", q, rows) * scale
    valid = (jnp.arange(rows.shape[1])[None, :]
             < n_valid[:, None])[:, None]
    s = jnp.where(valid, s, NEG_INF)
    probs = jnp.where(valid, jax.nn.softmax(s, axis=-1), 0.0)
    return aeinsum("bhs,bsv->bhv", probs, rows[..., :v_dim])


def mla(p, x, cfg, policy: PrecisionPolicy, *, positions=None,
        cache=None, cache_capacity: Optional[int] = None):
    """Latent attention: prefill (no cache; ``cache_capacity`` also builds
    a contiguous latent :class:`KVCache`) or one-token absorbed decode over
    a paged latent pool or a contiguous latent cache.  Returns (out,
    new_cache)."""
    B, S, _ = x.shape
    H, vd = cfg.n_heads, cfg.v_head_dim
    paged = isinstance(cache, PagedKVCache)
    base = 0
    if cache is not None:
        base = cache.seq_lens[:, None] if paged else cache.pos
    if positions is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32) + base
    q_nope, q_pe, row = _mla_project(p, x, cfg, policy, positions)

    if cache is None:
        with jax.named_scope("attn_kernel"):
            out = _mla_prefill(q_nope, q_pe, row, p, cfg, policy)
        new_cache = None
        if cache_capacity is not None:
            dt = policy.dtype("kv_cache")
            rows = jnp.zeros((B, max(cache_capacity, S), row.shape[-1]), dt)
            rows = jax.lax.dynamic_update_slice_in_dim(
                rows, row.astype(dt), 0, axis=1)
            new_cache = KVCache(k=rows, v=None, pos=jnp.asarray(S, jnp.int32))
        return pdot(out.reshape(B, S, H * vd), p["wo"], policy,
                    "attn_w"), new_cache

    if S != 1:
        raise ValueError("latent attention decodes one token at a time")
    w_uk, w_uv = _mla_heads(p, cfg)
    q_c = peinsum("bhn,rhn->bhr", q_nope[:, 0], w_uk, policy, "attn_w",
                  out_act=False)
    pad = row.shape[-1] - cfg.kv_lora_rank - cfg.qk_rope_head_dim
    q = jnp.concatenate([q_c.astype(jnp.float32),
                         q_pe[:, 0].astype(jnp.float32),
                         jnp.zeros((B, H, pad), jnp.float32)], axis=-1)
    scale = mla_scale(cfg)
    if paged:
        with jax.named_scope("latent_append"):
            new_cache = paged_cache.append_decode(cache, row, None)
        fn = dispatch.resolve_latent_decode(decode_impl(cfg, policy))
        with jax.named_scope("mla_decode"):
            o_c = fn(q, new_cache.k_pool, new_cache.seq_lens, scale=scale,
                     v_dim=cfg.kv_lora_rank, policy=policy,
                     block_tables=new_cache.block_tables)
    else:
        slot = jnp.minimum(cache.pos, cache.capacity - 1)
        rows = jax.lax.dynamic_update_slice_in_dim(
            cache.k, row.astype(cache.k.dtype), slot, axis=1)
        new_cache = KVCache(k=rows, v=None, pos=cache.pos + 1)
        n_valid = jnp.broadcast_to(jnp.minimum(cache.pos + 1,
                                               cache.capacity), (B,))
        o_c = _latent_contiguous(q, rows, n_valid, scale=scale,
                                 v_dim=cfg.kv_lora_rank)
    out = peinsum("bhr,rhv->bhv", o_c, w_uv, policy, "attn_w")
    return pdot(out.reshape(B, 1, H * vd), p["wo"], policy,
                "attn_w"), new_cache


def _latent_payload(pool, policy):
    """A latent pool as the kernel reads it: the packed container bits of
    a narrow native format (a pure bitcast), else f32 values."""
    fmt = policy.fmt("kv_cache")
    if policy.mode == "native" and not fmt.is_binary32:
        return jax.lax.bitcast_convert_type(pool, fmt.container_dtype), fmt
    return pool.astype(jnp.float32), None


@dispatch.register_latent_decode("xla")
def _latent_decode_xla(q, pool, n_valid, *, scale, v_dim, policy,
                       block_tables):
    """Gather the pool's pages and decode through XLA: the oracle."""
    from repro.kernels.latent_attention import latent_decode_reference

    payload, fmt = _latent_payload(pool, policy)
    return latent_decode_reference(q, payload, fmt, n_valid, block_tables,
                                   scale=scale, v_dim=v_dim)


@dispatch.register_latent_decode("pallas")
def _latent_decode_pallas(q, pool, n_valid, *, scale, v_dim, policy,
                          block_tables):
    """The Pallas kernel over the paged latent pool
    (kernels/latent_attention.py): each row read from HBM once per step
    for every head, at its stored width."""
    from repro.kernels.latent_attention import latent_decode

    payload, fmt = _latent_payload(pool, policy)
    return latent_decode(q, payload, fmt, n_valid, block_tables,
                         scale=scale, v_dim=v_dim)
