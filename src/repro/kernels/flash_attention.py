"""Pallas TPU kernels: fused transprecision flash-attention (decode + prefill).

Why this kernel exists
----------------------
The serving hot path is HBM-bandwidth bound: every decode step streams the
whole KV cache past the MXU once.  The cache is already *stored* packed in a
narrow (e, m) format (binary8/e5m2 by default policy -- the paper's
vectorized narrow-format storage, 4x fewer bytes than f32), but the XLA
decode path dequantizes it to f32/bf16 *outside* the attention dot, so the
materialized wide copy round-trips through HBM and the byte reduction never
reaches the bandwidth-bound step.  These kernels read the packed
binary8/binary16/binary16alt payloads directly from HBM, decode each VMEM
tile in-register on the VPU via the shared codec
(``repro.kernels.codec.decode_tile`` -- the same bit math as ``qmatmul.py``
and ``core.qtensor``, one source of truth validated exhaustively against
native casts), and compute online-softmax attention with f32 accumulation.
HBM attention bytes drop by the full container ratio (4x for binary8, 2x
for the 16-bit formats).

Both decode entry points optionally return the flash partials (running max
``m`` and softmax sum ``l``) so the ``flash_shmap`` wrapper backend in
``kernels/dispatch.py`` can merge exact attention across sequence shards.

Kernels
-------
``flash_decode``
    One query token per sequence against a packed KV cache of capacity S.
    Grid (B, S/block_kv); each KV tile holds all H heads (the head loop
    runs in the body) and a VMEM running (max, sum, acc) triple per head
    carries the online softmax across KV tiles.  Ragged per-sequence
    lengths mask invalid slots, which also covers the sliding-window ring
    buffer (every written slot is valid; order is irrelevant under
    softmax).

``flash_prefill``
    Chunked causal prefill: grid (B, H, Sq/block_q, Skv/block_kv), KV
    innermost.  Causal / sliding-window / bidirectional-prefix masks are
    generated in-register.  Accepts packed payloads or plain float K/V
    (``fmt=None``) -- at prefill time K/V are usually still activations.

Numerics
--------
Softmax statistics and both dots accumulate in f32 (the FlexFloat "compute
wide" contract).  ``flash_decode_reference`` is the XLA dequantize oracle:
it mirrors the kernel's operation order exactly (decode -> QK^T -> exp with
running max -> PV / sum), so kernel and oracle differ only in the f32
summation order the compiler picks; tests bound that difference for all
four paper formats.

Integration
-----------
``models/attention.py`` routes decode here when ``decode_impl ==
"flash_pallas"`` (config knob, overridable per ``PrecisionPolicy``); the XLA
path remains the oracle and the fallback.  Off-TPU the kernels run in
Pallas interpret mode -- bit-faithful, which is how the CPU-only CI
validates them; ``benchmarks/bench_attention.py`` reports decode-step time
and HBM bytes moved for packed vs f32 caches.
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
from repro.core.formats import FpFormat, get_format

from .codec import decode_tile as _decode

NEG_INF = -1e30  # finite sentinel: keeps exp(m_prev - m_new) well-defined

DEFAULT_BLOCK_KV = 256
DEFAULT_BLOCK_Q = 128
_MIN_SUBLANE = 8  # f32 sublane tile; G is padded up to this


def _payload_to_f32(x, fmt: Optional[FpFormat]):
    """In-register expansion of a packed tile to f32 (identity for floats)."""
    if fmt is None:
        return x.astype(jnp.float32)
    return _decode(x, fmt)


def _online_update(s, v, acc_ref, m_ref, l_ref, mask):
    """One online-softmax step: fold tile scores ``s`` (rows, bs) and tile
    values ``v`` (bs, dh) into the running (max, sum, acc) statistics."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)  # exact zero even when a whole tile is masked
    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _finalize(acc_ref, l_ref):
    l = l_ref[:, :1]
    return jnp.where(l > 0, acc_ref[...] / l, 0.0)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _decode_kernel(q_ref, k_ref, v_ref, len_ref, *refs,
                   fmt, scale, block_kv, n_kv, with_residuals):
    if with_residuals:
        o_ref, mo_ref, lo_ref, acc_ref, m_ref, l_ref = refs
    else:
        (o_ref, acc_ref, m_ref, l_ref), mo_ref, lo_ref = refs, None, None
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = si * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (q_ref.shape[1], block_kv), 1)
    _fold_heads(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, fmt, scale,
                pos < len_ref[pl.program_id(0)])

    @pl.when(si == n_kv - 1)
    def _flush():
        _flush_heads(o_ref, mo_ref, lo_ref, acc_ref, m_ref, l_ref)


def _fold_heads(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, fmt, scale,
                mask):
    """Fold one KV tile (bkv, H, dh) into every head's running softmax.

    The tile carries all H heads: a block that squeezed the head axis out
    would put a 1 against H in the second-minor (sublane) position, which
    the TPU lowering refuses; H equals the array's own dim, so the whole-
    head block tiles legally and one DMA moves a contiguous slab."""
    # decode the whole tile once, then slice heads out of the f32 value:
    # decoding per-head strided loads compiles about 8x slower
    k = _payload_to_f32(k_ref[...], fmt)                   # (bkv, H, dh)
    v = _payload_to_f32(v_ref[...], fmt)
    for h in range(k.shape[1]):
        q = q_ref[h].astype(jnp.float32)                   # (Gp, dh)
        s = jax.lax.dot_general(q, k[:, h, :], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        _online_update(s, v[:, h, :], acc_ref.at[h], m_ref.at[h],
                       l_ref.at[h], mask)


def _flush_heads(o_ref, mo_ref, lo_ref, acc_ref, m_ref, l_ref):
    for h in range(o_ref.shape[0]):
        o_ref[h] = _finalize(acc_ref.at[h], l_ref.at[h])
    if mo_ref is not None:
        mo_ref[...] = m_ref[...]
        lo_ref[...] = l_ref[...]


def _decode_layout(H: int, Gp: int, dh: int, return_residuals: bool):
    """Output lane widths (o, then m and l with residuals) and VMEM scratch
    shared by the contiguous and paged decode kernels: every grid step
    owns one sequence's whole (H, Gp, ...) query/output block."""
    scratch = [pltpu.VMEM((H, Gp, dh), jnp.float32),
               pltpu.VMEM((H, Gp, 128), jnp.float32),
               pltpu.VMEM((H, Gp, 128), jnp.float32)]
    widths = [dh] + ([128, 128] if return_residuals else [])
    return widths, scratch


def flash_decode(q, k_payload, v_payload, fmt, lengths, *,
                 scale: Optional[float] = None,
                 block_kv: int = DEFAULT_BLOCK_KV,
                 return_residuals: bool = False,
                 interpret: bool | None = None):
    """Single-token GQA attention over a packed KV cache.

    q:          (B, H, G, dh) float -- one query token, G queries per KV head.
    k_payload / v_payload:
                (B, S, H, dh) packed (e, m) containers (uint8/16/32) when
                ``fmt`` is given, or plain float arrays when ``fmt`` is None.
    lengths:    (B,) int32 -- number of valid cache slots per sequence
                (ragged batches; a full ring buffer passes its capacity).
    Returns (B, H, G, dh) float32; with ``return_residuals`` additionally the
    flash partials (m, l) of shape (B, H, G) -- the running softmax max and
    sum the ``flash_shmap`` wrapper merges across sequence shards.
    """
    fmt = get_format(fmt) if fmt is not None else None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, G, dh = q.shape
    S = k_payload.shape[1]
    assert k_payload.shape == v_payload.shape == (B, S, H, dh), (
        q.shape, k_payload.shape, v_payload.shape)
    if scale is None:
        scale = float(1.0 / np.sqrt(dh))

    pg = (-G) % _MIN_SUBLANE
    if pg:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pg), (0, 0)))
    Gp = G + pg
    bkv = min(block_kv, S)
    ps = (-S) % bkv
    if ps:  # zero payloads decode to 0.0 and sit beyond every length
        k_payload = jnp.pad(k_payload, ((0, 0), (0, ps), (0, 0), (0, 0)))
        v_payload = jnp.pad(v_payload, ((0, 0), (0, ps), (0, 0), (0, 0)))
    n_kv = (S + ps) // bkv
    # clamp: callers may pass a running token count that exceeds capacity
    # (decode past a full non-window cache); without this the padded slots
    # [S, S+ps) would count as valid and dilute the softmax
    lengths = jnp.minimum(lengths.astype(jnp.int32), S)

    kern = functools.partial(_decode_kernel, fmt=fmt,
                             scale=np.float32(scale), block_kv=bkv, n_kv=n_kv,
                             with_residuals=return_residuals)
    widths, scratch = _decode_layout(H, Gp, dh, return_residuals)
    qmap = lambda b, s: (b, 0, 0, 0)                        # noqa: E731
    out = pl.pallas_call(
        kern,
        grid=(B, n_kv),
        in_specs=[
            pl.BlockSpec((None, H, Gp, dh), qmap),
            pl.BlockSpec((None, bkv, H, dh), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((None, bkv, H, dh), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # all (B,) lengths
        ],
        out_specs=[pl.BlockSpec((None, H, Gp, w), qmap) for w in widths],
        out_shape=[jax.ShapeDtypeStruct((B, H, Gp, w), jnp.float32)
                   for w in widths],
        scratch_shapes=scratch,
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k_payload, v_payload, lengths)
    if return_residuals:
        o, m, l = out
        return o[:, :, :G, :], m[:, :, :G, 0], l[:, :, :G, 0]
    return out[0][:, :, :G, :]


def flash_decode_reference(q, k_payload, v_payload, fmt, lengths, *,
                           scale: Optional[float] = None,
                           return_residuals: bool = False):
    """The XLA dequantize path, mirroring the kernel's operation order.

    Decodes the full payload through XLA (materializing the wide copy the
    fused kernel avoids), then max -> exp -> PV / sum in f32.  Oracle for
    bit-level comparison in interpret mode.  ``return_residuals`` adds the
    flash partials (m, l), same contract as :func:`flash_decode`.
    """
    fmt = get_format(fmt) if fmt is not None else None
    B, H, G, dh = q.shape
    if scale is None:
        scale = float(1.0 / np.sqrt(dh))
    k = jax.vmap(lambda p: _payload_to_f32(p, fmt))(k_payload)  # (B,S,H,dh)
    v = jax.vmap(lambda p: _payload_to_f32(p, fmt))(v_payload)
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32), k,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    valid = (jnp.arange(s.shape[-1])[None, :]
             < lengths.astype(jnp.int32)[:, None])          # (B, S)
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


# ---------------------------------------------------------------------------
# chunked causal prefill
# ---------------------------------------------------------------------------

def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                    fmt, scale, block_q, block_kv, n_kv, window,
                    prefix_len, q_offset):
    qi_blk, si = pl.program_id(2), pl.program_id(3)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # prune KV blocks that are provably fully masked for this q block
    # (strictly-future tiles under causality, or entirely left of the
    # sliding window) -- about half the grid for pure causal prefill
    ki_min = si * block_kv
    ki_max = ki_min + block_kv - 1
    qi_min = q_offset + qi_blk * block_q
    qi_max = qi_min + block_q - 1
    live = ki_min <= qi_max
    if window is not None:
        live &= ki_max > qi_min - window
    if prefix_len:
        live |= ki_min < prefix_len

    @pl.when(live)
    def _update():
        bq = block_q
        q = q_ref[...].astype(jnp.float32)                 # (bq, Gp, dh)
        gp, dh = q.shape[1], q.shape[2]
        q2 = q.reshape(bq * gp, dh)
        k = _payload_to_f32(k_ref[...], fmt)               # (bkv, dh)
        v = _payload_to_f32(v_ref[...], fmt)
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        qi = q_offset + qi_blk * bq + rows // gp           # query position
        ki = ki_min + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = ki <= qi
        if window is not None:
            mask &= ki > qi - window
        if prefix_len:
            mask |= ki < prefix_len
        _online_update(s, v, acc_ref, m_ref, l_ref, mask)

    @pl.when(si == n_kv - 1)
    def _flush():
        o_ref[...] = _finalize(acc_ref, l_ref).reshape(o_ref.shape)


def flash_prefill(q, k_payload, v_payload, fmt=None, *,
                  scale: Optional[float] = None,
                  window: Optional[int] = None, prefix_len: int = 0,
                  q_offset: int = 0,
                  block_q: int = DEFAULT_BLOCK_Q,
                  block_kv: int = DEFAULT_BLOCK_KV,
                  interpret: bool | None = None):
    """Chunked causal GQA prefill with online softmax.

    q:          (B, Sq, H, G, dh) float.
    k_payload / v_payload:
                (B, Skv, H, dh) packed containers (``fmt`` set) or floats.
    window:     sliding-window size (local attention) or None.
    prefix_len: bidirectional prefix (prefix-LM / VLM).
    q_offset:   absolute position of q[0] (continuation chunks).
    Returns (B, Sq, H, G, dh) float32.
    """
    fmt = get_format(fmt) if fmt is not None else None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Sq, H, G, dh = q.shape
    Skv = k_payload.shape[1]
    assert k_payload.shape == v_payload.shape == (B, Skv, H, dh)
    if scale is None:
        scale = float(1.0 / np.sqrt(dh))

    pg = (-G) % _MIN_SUBLANE if G < _MIN_SUBLANE else 0
    if pg:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pg), (0, 0)))
    Gp = G + pg
    bq = min(block_q, Sq)
    pq = (-Sq) % bq
    if pq:  # padded queries see ki <= qi unmasked rows; sliced off below
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    bkv = min(block_kv, Skv)
    ps = (-Skv) % bkv
    if ps:  # padded ki > every real qi (self-attention) => causally masked
        k_payload = jnp.pad(k_payload, ((0, 0), (0, ps), (0, 0), (0, 0)))
        v_payload = jnp.pad(v_payload, ((0, 0), (0, ps), (0, 0), (0, 0)))
    n_q, n_kv = (Sq + pq) // bq, (Skv + ps) // bkv
    # head-major K/V: a (bkv, dh) block of a (B, H, Skv, dh) array tiles
    # legally, where a head-squeezed block of the (B, Skv, H, dh) layout
    # would put a 1 against H in the sublane position (refused by the TPU
    # lowering).  Prefill K/V are a fresh chunk or a gathered copy, so the
    # transpose adds one pass over bytes already being copied.
    k_payload = jnp.swapaxes(k_payload, 1, 2)
    v_payload = jnp.swapaxes(v_payload, 1, 2)

    kern = functools.partial(
        _prefill_kernel, fmt=fmt, scale=np.float32(scale), block_q=bq,
        block_kv=bkv, n_kv=n_kv, window=window, prefix_len=prefix_len,
        q_offset=q_offset)
    out = pl.pallas_call(
        kern,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, bq, None, Gp, dh),
                         lambda b, h, i, s: (b, i, h, 0, 0)),
            pl.BlockSpec((None, None, bkv, dh),
                         lambda b, h, i, s: (b, h, s, 0)),
            pl.BlockSpec((None, None, bkv, dh),
                         lambda b, h, i, s: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, None, Gp, dh),
                               lambda b, h, i, s: (b, i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq + pq, H, Gp, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq * Gp, dh), jnp.float32),
            pltpu.VMEM((bq * Gp, 128), jnp.float32),
            pltpu.VMEM((bq * Gp, 128), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k_payload, v_payload)
    return out[:, :Sq, :, :G, :]


def _prefill_xla_reference(q, k, v, scale, window, prefix_len, q_offset):
    """XLA oracle for ``flash_prefill`` on float K/V: one-shot masked
    softmax with the same mask semantics.  Also the recompute target for
    the custom backward below."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * np.float32(scale)
    Sq, Sk = q.shape[1], k.shape[1]
    qi = q_offset + jnp.arange(Sq)[:, None]
    ki = jnp.arange(Sk)[None, :]
    m = ki <= qi
    if window is not None:
        m &= ki > qi - window
    if prefix_len:
        m = m | (ki < prefix_len)
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=None)
def _prefill_diff(scale, window, prefix_len, q_offset, block_q, block_kv):
    def primal(q, k, v):
        return flash_prefill(q, k, v, None, scale=scale, window=window,
                             prefix_len=prefix_len, q_offset=q_offset,
                             block_q=block_q, block_kv=block_kv)

    def fwd(q, k, v):
        return primal(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda a, b, c: _prefill_xla_reference(
                a, b, c, scale, window, prefix_len, q_offset), q, k, v)
        return vjp(g)

    f = jax.custom_vjp(primal)
    f.defvjp(fwd, bwd)
    return f


def flash_prefill_diff(q, k, v, *, scale, window: Optional[int] = None,
                       prefix_len: int = 0, q_offset: int = 0,
                       block_q: int = DEFAULT_BLOCK_Q,
                       block_kv: int = DEFAULT_BLOCK_KV):
    """Differentiable ``flash_prefill`` on float K/V.

    Pallas has no AD in interpret mode, so the backward pass recomputes
    through the bit-equivalent XLA reference (flash-attention's standard
    recompute-backward, with XLA doing the rematerialization).  This is what
    ``models/attention.py`` routes training-time causal attention through
    when ``decode_impl="flash_pallas"``.
    """
    return _prefill_diff(float(scale), window, prefix_len, q_offset,
                         block_q, block_kv)(q, k, v)


def attention_hbm_bytes(batch: int, seq: int, n_kv: int, head_dim: int,
                        fmt, *, g: int = 1, q_bytes: int = 4) -> int:
    """HBM bytes one decode step streams through attention: the K and V
    payloads (the dominant term) plus the ``g`` query rows per KV head.
    The paper's Fig. 6 memory-access reduction, specialized to serving."""
    fmt = get_format(fmt) if fmt is not None else None
    item = 4 if fmt is None else fmt.container_dtype.dtype.itemsize
    kv = 2 * batch * seq * n_kv * head_dim * item
    return kv + batch * n_kv * g * head_dim * q_bytes


def ring_ppermute_bytes(batch: int, seq: int, n_kv: int, head_dim: int,
                        fmt, *, n_devices: int) -> int:
    """Interconnect bytes ONE device sends per decode step under the
    ``ring`` wrapper over a contiguous cache: its (seq / n_devices)-slot
    K and V payload shards, passed to the neighbor on each of the
    n_devices - 1 rotations.  Container-width payloads rotate, so the
    packed formats shrink the collective by the same ratio as HBM --
    the transprecision-cluster observation (explicit data rotation moves
    packed bytes) applied to the attention merge."""
    fmt = get_format(fmt) if fmt is not None else None
    item = 4 if fmt is None else fmt.container_dtype.dtype.itemsize
    shard = batch * (seq // n_devices) * n_kv * head_dim * item
    return 2 * shard * (n_devices - 1)
