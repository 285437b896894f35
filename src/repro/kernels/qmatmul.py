"""Pallas TPU kernel: transprecision matmul, decode-GEMV oriented.

The TPU-native adaptation of the paper's transprecision FPU for the compute
hot spot of every assigned architecture.  Operands are stored packed in their
(e, m) formats (4x/2x less HBM traffic for 8/16-bit formats -- the paper's
vectorized-memory-access win); each VMEM tile is decoded in-register on the
VPU, multiplied on the MXU with f32 accumulation (the "compute wide, store
narrow" FlexFloat contract), and the output is optionally re-sanitized to a
narrow format before it is written back.

Two shape regimes share one kernel body:

* **square/prefill** (M > GEMV_MAX_M): classic (bm, bn, bk) = (256, 256, 256)
  tiling, all three grid dims balanced.
* **skinny-M decode GEMV** (M <= GEMV_MAX_M, the serving decode step
  ``(B<=8, K) @ (K, N)``): M is one tiny sublane-aligned block and the
  *packed weight tiles are the grid's moving operand* -- each (bk, bn)
  weight tile streams from HBM exactly once per step, so per-decode-step
  weight bytes shrink by the container ratio (4x for binary8), while the
  small activation block stays resident.

The epilogue is fused: optional bias add, nonlinearity, multiplicative gate
(a second weight operand accumulated in the same K sweep -- the gated-FFN
pair ``act(x @ w_in + b) * (x @ w_gate)`` never round-trips its
ff-dimensional activations through HBM), and output quantization.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics) accumulating
into VMEM f32 scratch tiles.  Block dims are rounded up to the hardware
tiling (sublane multiple of the operand container dtype, lane multiple 128)
and operands padded -- ``min(bm, M)`` alone produced unaligned Mosaic tiles
for small/ragged dims.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import CompilerParams
from repro.core.formats import FpFormat, get_format

from .codec import decode_tile as _decode
from .codec import quantize_tile

DEFAULT_BLOCKS = (256, 256, 256)  # bm, bn, bk
# skinny-M decode: tiny M block, deep K so a whole d_model-deep reduction
# happens in one sweep (f32 accumulation order == the XLA dequantize
# oracle's), weight tiles the moving operand
GEMV_BLOCKS = (32, 256, 2048)
GEMV_MAX_M = 32                   # M at or below this takes the GEMV path

_LANE = 128  # last tile dim, every dtype


def _sublane(dtype) -> int:
    """Minimum second-to-last tile dim for ``dtype`` (Mosaic tiling)."""
    return {1: 32, 2: 16, 4: 8}[jnp.dtype(dtype).itemsize]


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def default_blocks(M: int, K: int, N: int) -> tuple:
    """Block heuristic: square tiling, except skinny-M (decode GEMV) where
    a tiny M block with wide K/N tiles streams the weight matrix once."""
    del K, N
    return GEMV_BLOCKS if M <= GEMV_MAX_M else DEFAULT_BLOCKS


def _apply_act(x, name: str):
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "relu2":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def _qmm_kernel(*refs, fmt_a, fmt_b, gated, has_bias, act, out_em, n_k,
                k_axis, out_dtype):
    it = iter(refs)
    a_ref = next(it)
    b_ref = next(it)
    g_ref = next(it) if gated else None
    bias_ref = next(it) if has_bias else None
    o_ref = next(it)
    acc_ref = next(it)
    acc2_ref = next(it) if gated else None

    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if gated:
            acc2_ref[...] = jnp.zeros_like(acc2_ref)

    a = _decode(a_ref[...], fmt_a) if fmt_a is not None else a_ref[...]
    af = a.astype(jnp.float32)
    b = _decode(b_ref[...], fmt_b) if fmt_b is not None else b_ref[...]
    acc_ref[...] += jnp.dot(af, b.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
    if gated:
        g = _decode(g_ref[...], fmt_b) if fmt_b is not None else g_ref[...]
        acc2_ref[...] += jnp.dot(af, g.astype(jnp.float32),
                                 preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        r = acc_ref[...]
        if has_bias:
            r = r + bias_ref[...].astype(jnp.float32)
        if act is not None:
            r = _apply_act(r, act)
        if gated:
            r = r * acc2_ref[...]
        if out_em is not None:
            r = quantize_tile(r, out_em[0], out_em[1], False)
        o_ref[...] = r.astype(out_dtype)


def qmatmul(a_payload, b_payload, fmt_a, fmt_b,
            out_fmt: Optional[FpFormat] = None, *,
            gate_payload=None, bias=None, act: Optional[str] = None,
            blocks=None, interpret: bool | None = None):
    """(M, K) @ (K, N) on packed transprecision operands; f32 accumulation.

    ``a_payload``/``b_payload`` are packed containers (from
    ``core.qtensor.encode``) when ``fmt_a``/``fmt_b`` are given, or plain
    float arrays when the corresponding format is None.  With a leading
    group axis -- (E, M, K) @ (E, K, N), the MoE experts -- every group
    is one more grid axis of the SAME kernel: one Mosaic program per
    grouped matmul instead of one per expert.

    Fused epilogue (all optional, applied in this order at the final K
    step): ``+ bias`` (shape (N,)), nonlinearity ``act`` ("silu" | "gelu" |
    "relu2"), ``* (a @ gate_payload)`` (a second weight operand in
    ``fmt_b``, accumulated in the same K sweep -- the gated-FFN pair in one
    kernel), quantize to ``out_fmt``.  Returns f32 (or ``out_fmt``-
    sanitized f32 when ``out_fmt`` is set).
    """
    fmt_a = get_format(fmt_a) if fmt_a is not None else None
    fmt_b = get_format(fmt_b) if fmt_b is not None else None
    out_em = None
    if out_fmt is not None:
        out_fmt = get_format(out_fmt)
        out_em = (out_fmt.e, out_fmt.m)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    gated = gate_payload is not None
    has_bias = bias is not None

    groups = a_payload.shape[:-2]
    assert groups == b_payload.shape[:-2] and len(groups) <= 1, (
        a_payload.shape, b_payload.shape)
    (M, K), (K2, N) = a_payload.shape[-2:], b_payload.shape[-2:]
    assert K == K2, (a_payload.shape, b_payload.shape)
    if gated:
        assert gate_payload.shape == b_payload.shape, (
            gate_payload.shape, b_payload.shape)
        assert gate_payload.dtype == b_payload.dtype
    bm, bn, bk = blocks if blocks is not None else default_blocks(M, K, N)
    # Round every block dim up to its hardware tile multiple: the sublane
    # (second-to-last) dim must be a multiple of the operand's minimum
    # sublane count (8/16/32 for 4/2/1-byte containers), the lane (last)
    # dim a multiple of 128.  bm is a sublane of both the a-tile and the
    # f32 out-tile; bk is the a-tile's lane AND the b-tile's sublane; bn is
    # a lane everywhere.  Clamping with min() alone handed Mosaic unaligned
    # tiles for small/ragged dims (e.g. M=3, K=100).
    bm = _round_up(min(bm, M), max(_sublane(a_payload.dtype), 8))
    bk = _round_up(min(bk, K), max(_LANE, _sublane(b_payload.dtype)))
    bn = _round_up(min(bn, N), _LANE)
    pm, pn, pk = _round_up(M, bm) - M, _round_up(N, bn) - N, \
        _round_up(K, bk) - K
    g0 = ((0, 0),) * len(groups)
    if pm or pk:
        a_payload = jnp.pad(a_payload, g0 + ((0, pm), (0, pk)))
    if pk or pn:
        b_payload = jnp.pad(b_payload, g0 + ((0, pk), (0, pn)))
        if gated:
            gate_payload = jnp.pad(gate_payload, g0 + ((0, pk), (0, pn)))
    Mp, Np, Kp = M + pm, N + pn, K + pk
    n_k = Kp // bk

    def spec(block, index):
        """BlockSpec over the (i, j, k) grid, behind the group axis (whose
        block dim is squeezed) when there is one."""
        if not groups:
            return pl.BlockSpec(block, index)
        return pl.BlockSpec((None,) + block,
                            lambda g, i, j, k: (g,) + index(i, j, k))

    operands = [a_payload, b_payload]
    in_specs = [
        spec((bm, bk), lambda i, j, k: (i, k)),
        spec((bk, bn), lambda i, j, k: (k, j)),
    ]
    if gated:
        operands.append(gate_payload)
        in_specs.append(spec((bk, bn), lambda i, j, k: (k, j)))
    if has_bias:
        assert bias.shape == (N,) and not groups, (bias.shape, N, groups)
        b2 = jnp.pad(bias.astype(jnp.float32), (0, pn)).reshape(1, Np)
        operands.append(b2)
        in_specs.append(spec((1, bn), lambda i, j, k: (0, j)))

    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if gated:
        scratch.append(pltpu.VMEM((bm, bn), jnp.float32))

    grid = groups + (Mp // bm, Np // bn, n_k)
    kern = functools.partial(_qmm_kernel, fmt_a=fmt_a, fmt_b=fmt_b,
                             gated=gated, has_bias=has_bias, act=act,
                             out_em=out_em, n_k=n_k, k_axis=len(grid) - 1,
                             out_dtype=jnp.float32)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=spec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(groups + (Mp, Np), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1)
            + ("arbitrary",)),
        interpret=interpret,
    )(*operands)
    return out[..., :M, :N]


def qmm_ffn(x, w_in_payload, w_gate_payload, fmt_w, *, bias=None,
            act: str = "silu", out_fmt: Optional[FpFormat] = None,
            blocks=None, interpret: bool | None = None):
    """Fused gated-FFN pair on a packed weight store:
    ``act(x @ w_in + bias) * (x @ w_gate)`` in ONE kernel -- both (ff)-wide
    activations live and die in VMEM scratch, never touching HBM.  Pass
    ``w_gate_payload=None`` for the ungated ``act(x @ w_in + bias)``."""
    return qmatmul(x, w_in_payload, None, fmt_w, out_fmt,
                   gate_payload=w_gate_payload, bias=bias, act=act,
                   blocks=blocks, interpret=interpret)


# ---------------------------------------------------------------------------
# analytic HBM byte model (the paper's Fig. 6 memory-access reduction,
# specialized to the weight side of a serving decode step)
# ---------------------------------------------------------------------------

def qmm_weight_bytes(K: int, N: int, fmt, *, gated: bool = False) -> int:
    """Packed-weight bytes one qmatmul streams from HBM (each (bk, bn)
    weight tile is fetched exactly once per call)."""
    item = 4 if fmt is None else get_format(fmt).container_dtype.dtype.itemsize
    return K * N * item * (2 if gated else 1)


def qmm_hbm_bytes(M: int, K: int, N: int, fmt_w, *, fmt_x=None,
                  gated: bool = False, bias: bool = False,
                  out_bytes: int = 4) -> int:
    """Total HBM bytes of one fused qmatmul: the weight stream (dominant
    for the decode shape M <= 8) plus activations in, result out, bias."""
    item_x = (4 if fmt_x is None
              else get_format(fmt_x).container_dtype.dtype.itemsize)
    total = qmm_weight_bytes(K, N, fmt_w, gated=gated)
    total += M * K * item_x + M * N * out_bytes
    if bias:
        total += N * 4
    return total
