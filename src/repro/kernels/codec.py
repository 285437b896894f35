"""The in-register transprecision codec: ONE place where a format's
(e, m, bias) becomes shifts and masks.

The paper's claim is that a single type system -- binary8 / binary16 /
binary16alt / binary32 behind one transprecision FPU (FPnew) -- serves every
workload.  The software analogue is that the bit-level interpretation of a
format must exist exactly once: this module owns every f32 field mask and
every encode/decode/round shift.  ``core.flexfloat`` (sanitization),
``core.qtensor`` (packed storage), and the Pallas kernel bodies in
``qmatmul`` / ``flash_attention`` / ``flexfloat_cast`` all call these tile
functions verbatim; ``tests/test_codec.py`` asserts at grep level that no
duplicated mask constants exist anywhere else under ``src/``.

Everything here is pure jnp lane ops on uint32/f32 (VPU-friendly: no f64, no
data-dependent control flow), safe both inside a Pallas kernel body and in
ordinary traced XLA code.  All functions are bit-exact; the quantizer is
validated exhaustively against native e5m2/e4m3/f16/bf16 casts in
``tests/test_formats.py``.

Tile functions
--------------
``quantize_tile(x, e, m)``    f32 -> f32 members of (e, m): RNE (or
                              stochastic), gradual underflow, Inf/NaN.
``encode_tile(x, fmt)``       already-quantized f32 -> packed (e, m) field
                              in the narrowest integer container.
``decode_tile(bits, fmt)``    exact expansion of packed fields to f32.
``pack_word_tile`` / ``unpack_word_tile``
                              4 x 8 b / 2 x 16 b lane packing into u32 words
                              (the FPU's vectorized load/store layout).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.formats import format_constants, get_format

_U32 = jnp.uint32

# ---------------------------------------------------------------------------
# The f32 field masks.  These hex constants appear ONLY in this module.
# ---------------------------------------------------------------------------
SIGN_F32 = np.uint32(0x8000_0000)   # sign bit
MAG_F32 = np.uint32(0x7FFF_FFFF)    # exponent + mantissa (magnitude)
EXP_F32 = np.uint32(0x7F80_0000)    # exponent field
MANT_F32 = np.uint32(0x007F_FFFF)   # mantissa field
QNAN_F32 = np.uint32(0x7FC0_0000)   # canonical quiet NaN
INF_F32 = np.uint32(0x7F80_0000)    # +Inf
QUIET_BIT_F32 = np.uint32(0x0040_0000)  # mantissa MSB (NaN quiet bit)
IMPLICIT_ONE_F32 = np.uint32(0x0080_0000)  # 1 << 23, the hidden leading one


def bits32(x) -> jax.Array:
    """f32 -> u32 bit pattern."""
    return lax.bitcast_convert_type(jnp.asarray(x, jnp.float32), _U32)


def float32(u) -> jax.Array:
    """u32 bit pattern -> f32."""
    return lax.bitcast_convert_type(u, jnp.float32)


# ---------------------------------------------------------------------------
# quantize: f32 -> f32 members of (e, m)  [FlexFloat sanitization]
# ---------------------------------------------------------------------------

def quantize_tile(x, e, m, saturate=False, key=None):
    """Round f32 values to format (e, m): RNE (or stochastic with ``key``),
    IEEE gradual underflow, Inf/NaN semantics.  Returns f32.

    Shared verbatim by ``core.flexfloat.quantize`` (jitted wrapper) and by
    the Pallas kernel body in ``flexfloat_cast`` -- one source of truth for
    the rounding bit manipulation.
    """
    if e == 8 and m == 23:
        # binary32 is the container format: rounding (deterministic OR
        # stochastic -- there are no discarded bits) is the identity.  The
        # generic subnormal path below must not run here: it clamps its
        # shift to >= 1, which would halve f32-denormal inputs.
        return jnp.asarray(x, jnp.float32)
    c = format_constants(e, m)
    u = bits32(x)
    sign = u & SIGN_F32
    mag = u & MAG_F32
    ef = (mag >> 23).astype(jnp.int32)  # biased f32 exponent, 0..255
    is_naninf = ef == 255
    is_nan = is_naninf & ((mag & ~EXP_F32) != 0)

    # ---- normal path: integer RNE (or stochastic) at cut `shift` ----------
    shift = c["shift"]
    if shift > 0:
        if key is None:
            lsb = (mag >> shift) & np.uint32(1)
            rnd = np.uint32((1 << (shift - 1)) - 1) + lsb
        else:
            rnd = jax.random.bits(key, mag.shape, jnp.uint32) >> (32 - shift)
        mag_r = (mag + rnd) & np.uint32(~((1 << shift) - 1) & 0xFFFFFFFF)
    else:
        mag_r = mag
    ovf = (mag_r >> 23).astype(jnp.int32) > (c["emax"] + 127)
    sat_bits = bits32(c["max_normal"])
    mag_r = jnp.where(ovf, sat_bits if saturate else INF_F32, mag_r)
    normal = float32(sign | mag_r)

    # ---- subnormal path: pure-integer RNE to quantum 2^qe -----------------
    # No FP arithmetic here: XLA CPU runs with DAZ/FTZ, so f32-denormal
    # operands/results of adds and muls are flushed to zero (verified), while
    # bit manipulation is exact.  value = sig * 2^exp2 with
    #   sig  = 2^23 + M (normal input)  |  M (f32-denormal input)
    #   exp2 = max(ef, 1) - 150
    # and we RNE-shift sig right by S = qe - exp2 (in [1, 25] after clamping;
    # S >= 25 provably yields 0 because sig < 2^24).
    qe = c["qe"]
    mant_f = mag & MANT_F32
    is_norm_in = ef > 0
    sig = jnp.where(is_norm_in, mant_f | IMPLICIT_ONE_F32, mant_f)
    exp2 = jnp.maximum(ef, 1) - 150
    s_amt = jnp.clip(qe - exp2, 1, 25).astype(_U32)
    half = (np.uint32(1) << (s_amt - 1))
    rem = sig & ((np.uint32(1) << s_amt) - 1)
    out_i = sig >> s_amt
    round_up = (rem > half) | ((rem == half) & ((out_i & 1) == 1))
    out_i = out_i + round_up.astype(_U32)
    sub = float32(sign | _int_times_pow2_bits(out_i, qe))

    use_sub = (ef - 127) < c["emin"]
    out = jnp.where(use_sub, sub, normal)

    # ---- Inf / NaN ---------------------------------------------------------
    special = float32(sign | jnp.where(is_nan, QNAN_F32, INF_F32))
    out = jnp.where(is_naninf, special, out)
    return out


def _int_times_pow2_bits(i, qe):
    """f32 bit pattern of ``i * 2^qe`` for small non-negative integers ``i``
    (< 2^24), without FP arithmetic (FTZ-safe):

      f32-normal result  (i >= 2^(-126-qe)): bits(float(i)) + (qe << 23)
      f32-denormal result: i << (qe + 149)
    """
    thresh = np.uint32(1) << max(0, min(-126 - qe, 23))
    # exact: i <= 2^23 after rounding.  Through int32, because the TPU
    # lowering has no unsigned -> float conversion.
    as_f = i.astype(jnp.int32).astype(jnp.float32)
    norm_bits = (bits32(as_f).astype(jnp.int32) + np.int32(qe << 23)
                 ).astype(_U32)
    den_bits = i << np.uint32(max(qe + 149, 0))
    bits = jnp.where(i >= thresh, norm_bits, den_bits)
    return jnp.where(i == 0, np.uint32(0), bits)


# ---------------------------------------------------------------------------
# encode: quantized f32 -> packed (e, m) container bits
# ---------------------------------------------------------------------------

def encode_tile(x, fmt) -> jax.Array:
    """Pack f32 values (already exact members of ``fmt``) into the (e, m)
    bit field, in the narrowest integer container (uint8/16/32)."""
    fmt = get_format(fmt)
    x = jnp.asarray(x, jnp.float32)
    if fmt.is_binary32:
        return bits32(x)

    c = format_constants(fmt.e, fmt.m)
    u = bits32(x)
    sign_t = (u >> 31).astype(_U32) << (fmt.e + fmt.m)
    mag = u & MAG_F32
    ef = (mag >> 23).astype(jnp.int32)
    mant_f = mag & MANT_F32

    # normal in target
    exp_t = (ef - 127 + c["bias"]).astype(_U32)
    mant_t = mant_f >> (23 - fmt.m)
    normal = (exp_t << fmt.m) | mant_t

    # denormal in target: mantissa field = |x| / 2^qe, an exact small integer.
    # Pure-integer extraction (XLA CPU flushes denormal FP operands, so no FP
    # math): |x| = sig * 2^exp2, already a multiple of 2^qe by construction,
    # hence mant = sig >> (qe - exp2) exactly.
    sig = jnp.where(ef > 0, mant_f | IMPLICIT_ONE_F32, mant_f)
    exp2 = jnp.maximum(ef, 1) - 150
    s_amt = jnp.clip(c["qe"] - exp2, 0, 31).astype(_U32)
    denorm = sig >> s_amt

    is_naninf = ef == 255
    is_nan = is_naninf & (mant_f != 0)
    special = (np.uint32((1 << fmt.e) - 1) << fmt.m) | jnp.where(
        is_nan, np.uint32(1 << (fmt.m - 1)), np.uint32(0))

    use_sub = (ef - 127) < c["emin"]
    field = jnp.where(is_naninf, special, jnp.where(use_sub, denorm, normal))
    return (sign_t | field).astype(fmt.container_dtype)


# ---------------------------------------------------------------------------
# decode: packed (e, m) container bits -> exact f32
# ---------------------------------------------------------------------------

def decode_tile(bits, fmt) -> jax.Array:
    """Exact expansion of packed (e, m) bit fields to float32.

    This is the in-register dequantize every packed-tensor kernel runs on
    its VMEM tiles (``qmatmul``, ``flash_attention``, ``flexfloat_cast``).
    """
    fmt = get_format(fmt)
    bits = jnp.asarray(bits)
    if fmt.is_binary32:
        return float32(bits.astype(_U32))

    c = format_constants(fmt.e, fmt.m)
    b = bits.astype(_U32)
    sign = ((b >> (fmt.e + fmt.m)) & np.uint32(1)) << 31
    exp_t = ((b >> fmt.m) & np.uint32((1 << fmt.e) - 1)).astype(jnp.int32)
    mant_t = b & np.uint32(fmt.mant_mask)

    # normal: rebias into f32
    normal = ((exp_t - c["bias"] + 127).astype(_U32) << 23) | (
        mant_t << (23 - fmt.m))

    # denormal: mant * 2^qe, reconstructed without FP math (FTZ-safe)
    denorm = _int_times_pow2_bits(mant_t, c["qe"])

    # Inf/NaN: max exponent
    is_special = exp_t == (1 << fmt.e) - 1
    special = EXP_F32 | jnp.where(mant_t != 0, QUIET_BIT_F32, np.uint32(0))

    mag = jnp.where(is_special, special,
                    jnp.where(exp_t == 0, denorm, normal))
    return float32(sign | mag)


# ---------------------------------------------------------------------------
# word packing: 4 x 8 b / 2 x 16 b lanes per u32 (the FPU's vector word)
# ---------------------------------------------------------------------------

def pack_word_tile(payload) -> jax.Array:
    """Pack a uint8/uint16 payload into uint32 words along the last axis --
    the FPU's 4x8b / 2x16b word layout.  Requires divisibility."""
    item = payload.dtype.itemsize
    if item == 4:
        return payload.astype(_U32)
    lanes = 4 // item
    *lead, n = payload.shape
    assert n % lanes == 0, (n, lanes)
    grouped = payload.reshape(*lead, n // lanes, lanes).astype(_U32)
    shifts = (jnp.arange(lanes, dtype=_U32) * np.uint32(8 * item))
    return jnp.sum(grouped << shifts, axis=-1, dtype=_U32)


def unpack_word_tile(words, dtype) -> jax.Array:
    """Inverse of :func:`pack_word_tile`."""
    item = jnp.dtype(dtype).itemsize
    if item == 4:
        return words.astype(dtype)
    lanes = 4 // item
    shifts = (jnp.arange(lanes, dtype=_U32) * np.uint32(8 * item))
    parts = (words[..., None] >> shifts) & np.uint32((1 << (8 * item)) - 1)
    *lead, n, _ = parts.shape
    return parts.reshape(*lead, n * lanes).astype(dtype)
