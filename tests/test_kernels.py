"""Kernel validation: sweep shapes/dtypes/formats, assert against ref.py
oracles (bit-exact for casts/codecs, allclose for matmul)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import (BINARY8, BINARY16, BINARY16ALT, BINARY32,
                                FpFormat)
from repro.core.qtensor import encode
from repro.kernels import ops, ref

FORMATS = [BINARY8, BINARY16, BINARY16ALT, FpFormat(6, 9), FpFormat(3, 4)]
SHAPES = [(8,), (128,), (1, 1), (7, 129), (256, 256), (3, 5, 64), (300, 513)]


def _rand(shape, seed, scale=4.0):
    rng = np.random.default_rng(seed)
    x = rng.normal(scale=scale, size=shape).astype(np.float32)
    # sprinkle specials and denormals
    flat = x.reshape(-1)
    if flat.size >= 8:
        flat[0], flat[1], flat[2] = np.inf, -np.inf, np.nan
        flat[3], flat[4] = 0.0, -0.0
        flat[5] = 1e-30
        flat[6] = -3e38
        flat[7] = 6e-8
    return jnp.asarray(x)


def _bits_equal(a, b, msg=""):
    a, b = np.asarray(a), np.asarray(b)
    nan_a, nan_b = np.isnan(a), np.isnan(b)
    np.testing.assert_array_equal(nan_a, nan_b, err_msg=msg)
    if a.dtype == np.float32:
        a, b = a.view(np.uint32), b.view(np.uint32)
    np.testing.assert_array_equal(np.where(nan_a, 0, a),
                                  np.where(nan_b, 0, b), err_msg=msg)


@pytest.mark.parametrize("fmt", FORMATS, ids=lambda f: f.name)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_cast_kernel_matches_oracle(fmt, shape):
    x = _rand(shape, hash((fmt.e, fmt.m, shape)) % 2**31)
    got = ops.cast(x, fmt, use_pallas=True)
    want = ref.flexfloat_cast_ref(x, fmt)
    assert got.shape == x.shape and got.dtype == jnp.float32
    _bits_equal(got, want, msg=f"{fmt} {shape}")


@pytest.mark.parametrize("fmt", FORMATS, ids=lambda f: f.name)
@pytest.mark.parametrize("shape", [(128,), (7, 129), (256, 256)], ids=str)
def test_pack_unpack_kernels_match_oracle(fmt, shape):
    x = _rand(shape, 11)
    packed = ops.pack(x, fmt, use_pallas=True)
    want_packed = ref.quantize_encode_ref(x, fmt)
    assert packed.dtype == fmt.container_dtype
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(want_packed))
    got = ops.unpack(packed, fmt, use_pallas=True)
    want = ref.dequantize_ref(want_packed, fmt)
    _bits_equal(got, want, msg=f"unpack {fmt} {shape}")


@pytest.mark.parametrize("fmt_a,fmt_b,out_fmt", [
    (BINARY8, BINARY8, None),
    (BINARY8, BINARY16, None),
    (BINARY16ALT, BINARY16ALT, BINARY16ALT),
    (BINARY16, BINARY16ALT, BINARY32),
    (None, BINARY8, None),
], ids=["b8b8", "b8b16", "b16alt+q", "mixed+q32", "f32xb8"])
@pytest.mark.parametrize("mkn", [(32, 32, 32), (128, 256, 64),
                                 (300, 140, 70), (257, 129, 511)], ids=str)
def test_qmatmul_matches_oracle(fmt_a, fmt_b, out_fmt, mkn):
    m, k, n = mkn
    rng = np.random.default_rng(m * n)
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    ap = encode(a, fmt_a) if fmt_a is not None else a
    bp = encode(b, fmt_b) if fmt_b is not None else b
    got = ops.matmul(ap, bp, fmt_a, fmt_b, out_fmt, use_pallas=True)
    want = ref.qmatmul_ref(ap, bp, fmt_a, fmt_b, out_fmt)
    assert got.shape == (m, n)
    # identical decode + f32 accumulate; only summation order may differ
    # between the tiled kernel and the single jnp.dot -> tight tolerance.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_qmatmul_vs_native_bf16():
    """binary16alt operands == native bf16 matmul with f32 accumulation."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(96, 48)), jnp.float32)
    ap, bp = encode(a, BINARY16ALT), encode(b, BINARY16ALT)
    got = ops.matmul(ap, bp, BINARY16ALT, BINARY16ALT, use_pallas=True)
    native = jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(native),
                               rtol=1e-5, atol=1e-5)


def test_cast_kernel_grid_boundary_padding():
    """Non-multiple shapes must not leak padding into results."""
    x = jnp.asarray(np.full((257, 300), 3.14159), jnp.float32)
    got = np.asarray(ops.cast(x, BINARY8, use_pallas=True))
    want = np.asarray(ref.flexfloat_cast_ref(x, BINARY8))
    np.testing.assert_array_equal(got, want)
    assert got.shape == (257, 300)


# ---------------------------------------------------------------------------
# skinny-M decode GEMV + fused epilogue (the packed-weight serving kernel)
# ---------------------------------------------------------------------------

from repro.core.qtensor import decode  # noqa: E402
from repro.kernels.qmatmul import (  # noqa: E402
    GEMV_MAX_M, default_blocks, qmatmul, qmm_ffn, qmm_hbm_bytes,
    qmm_weight_bytes)

QFMTS = [BINARY8, BINARY16, BINARY16ALT, BINARY32]


def _assert_oracle(got, want, scale, tol=1e-6):
    """|got - want| <= tol * scale elementwise, where ``scale`` is the
    dot's absolute-value accumulation |x| @ |w| (+1) -- the natural f32
    error unit: kernel and oracle round identical products, only the
    summation tree differs, so the pin is tol in THAT unit."""
    err = np.abs(np.asarray(got) - np.asarray(want))
    bad = err > tol * scale
    assert not bad.any(), (
        f"{bad.sum()} elements beyond {tol} x accumulation scale; worst "
        f"normalized {np.max(err / scale):.3e}")


@pytest.mark.parametrize("fmt", QFMTS, ids=lambda f: f.name)
@pytest.mark.parametrize("mkn", [(1, 512, 1408), (8, 512, 1408),
                                 (3, 100, 70)], ids=str)
def test_qmm_gemv_matches_dequantize_oracle(fmt, mkn):
    """The skinny-M path (packed weights as the moving operand) pinned
    <= 1e-6 against the XLA dequantize path, all four paper formats."""
    m, k, n = mkn
    assert m <= GEMV_MAX_M  # exercises the GEMV block heuristic
    rng = np.random.default_rng(fmt.bits * m)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    wp = encode(jnp.asarray(rng.normal(size=(k, n)), jnp.float32), fmt)
    got = qmatmul(x, wp, None, fmt)
    want = ref.qmatmul_ref(x, wp, None, fmt)
    scale = np.abs(np.asarray(x)) @ np.abs(np.asarray(decode(wp, fmt))) + 1.0
    _assert_oracle(got, want, scale)


@pytest.mark.parametrize("fmt", QFMTS, ids=lambda f: f.name)
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_qmm_ffn_fused_epilogue_matches_oracle(fmt, gated):
    """One kernel for the gated-FFN pair: act(x @ w_in + b) * (x @ w_gate),
    pinned <= 1e-6 (in accumulation units) against the XLA dequantize
    path; the fused output-quantize is bit-exact vs quantizing outside."""
    from repro.core.flexfloat import quantize

    m, k, n = 8, 384, 512
    rng = np.random.default_rng(fmt.bits)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    wp = encode(jnp.asarray(rng.normal(size=(k, n)), jnp.float32), fmt)
    gp = encode(jnp.asarray(rng.normal(size=(k, n)), jnp.float32), fmt) \
        if gated else None
    b = jnp.asarray(rng.normal(size=(n,)), jnp.float32)

    got = qmm_ffn(x, wp, gp, fmt, bias=b, act="silu", out_fmt=None)
    want = ref.qmatmul_ref(x, wp, None, fmt, gate_payload=gp, bias=b,
                           act="silu")
    xa = np.abs(np.asarray(x))
    sh = xa @ np.abs(np.asarray(decode(wp, fmt))) + np.abs(np.asarray(b)) + 1
    sg = (xa @ np.abs(np.asarray(decode(gp, fmt))) + 1.0) if gated else 1.0
    _assert_oracle(got, want, sh * sg)

    got_q = qmm_ffn(x, wp, gp, fmt, bias=b, act="silu", out_fmt=BINARY16ALT)
    _bits_equal(got_q, quantize(got, BINARY16ALT), msg="fused out-quantize")


def test_qmatmul_rounds_ragged_blocks_to_hardware_tiles():
    """Regression: min(bm, M) alone handed Mosaic unaligned tiles for
    small/ragged dims -- M=3, K=100 must round up to sublane/lane
    multiples, pad, and still match the oracle exactly at the edges."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(3, 100)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(100, 130)), jnp.float32)
    wp = encode(w, BINARY8)
    got = qmatmul(x, wp, None, BINARY8)
    assert got.shape == (3, 130)
    want = ref.qmatmul_ref(x, wp, None, BINARY8)
    scale = np.abs(np.asarray(x)) @ np.abs(
        np.asarray(decode(wp, BINARY8))) + 1.0
    _assert_oracle(got, want, scale)
    # ... and explicitly-passed ragged blocks are rounded too (the bug was
    # in the clamping, not the defaults)
    got2 = qmatmul(x, wp, None, BINARY8, blocks=(3, 100, 100))
    _assert_oracle(got2, want, scale)


def test_gemv_block_heuristic_and_byte_model():
    """Skinny M selects the weight-streaming blocks; the byte model
    reports the container ratio on the weight stream (the acceptance
    number: 4x binary8, 2x binary16/16alt vs the f32 XLA path)."""
    assert default_blocks(8, 4096, 14336) != default_blocks(256, 4096, 14336)
    f32 = qmm_weight_bytes(1024, 2816, None)
    assert f32 / qmm_weight_bytes(1024, 2816, BINARY8) == 4.0
    assert f32 / qmm_weight_bytes(1024, 2816, BINARY16) == 2.0
    assert f32 / qmm_weight_bytes(1024, 2816, BINARY16ALT) == 2.0
    assert f32 / qmm_weight_bytes(1024, 2816, BINARY32) == 1.0
    # gated pair streams both matrices; totals add x/out/bias terms
    assert qmm_weight_bytes(64, 128, BINARY8, gated=True) == 2 * 64 * 128
    assert qmm_hbm_bytes(8, 64, 128, BINARY8, gated=True, bias=True) == (
        2 * 64 * 128 + 8 * 64 * 4 + 8 * 128 * 4 + 128 * 4)


@pytest.mark.parametrize("fmt", [BINARY8, BINARY16ALT, BINARY32],
                         ids=lambda f: f.name)
@pytest.mark.parametrize("M,K,N,sizes", [
    pytest.param(96, 256, 384, (10, 0, 30, 5, 0, 0, 20, 3), id="gaps-tail"),
    pytest.param(600, 200, 300, (300, 1, 250), id="groups-share-tiles"),
    pytest.param(16, 128, 256, (2,) * 8, id="every-group-one-tile"),
    pytest.param(40, 128, 128, (0, 0, 0, 0), id="nothing-routed"),
])
def test_qmatmul_ragged_matches_dequantized_ragged_dot(fmt, M, K, N, sizes):
    """The dropless expert kernel: rows sorted by group against each
    group's packed block, vs ``lax.ragged_dot`` over the dequantized
    stack; rows past the groups read 0."""
    from repro.kernels.qmatmul import qmatmul_ragged
    rng = np.random.default_rng(M + K)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    wp = encode(jnp.asarray(rng.normal(size=(len(sizes), K, N)),
                            jnp.float32), fmt)
    gs = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(qmatmul_ragged(x, wp, gs, fmt))
    wd = decode(wp, fmt)
    want = np.asarray(jax.lax.ragged_dot(x, wd, gs, precision="highest"))
    n = sum(sizes)
    scale = np.asarray(jax.lax.ragged_dot(jnp.abs(x), jnp.abs(wd), gs,
                                          precision="highest")) + 1.0
    _assert_oracle(got[:n], want[:n], scale[:n])
    assert not got[n:].any()
