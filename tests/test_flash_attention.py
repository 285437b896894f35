"""Kernel-specific tests for the fused packed-KV flash-attention kernels.

The cross-backend oracle pins (every registry spelling vs the XLA
dequantize reference, all formats, ragged lengths, ring-buffer wrap,
1-/2-device meshes) live in ``tests/test_conformance.py``, parametrized
from ``dispatch.legal_impls()``.  This file keeps only what is specific
to the flash kernels themselves: agreement to the f32 summation-order
bound when one KV tile covers the cache, masking of garbage beyond the valid
length, length clamping past capacity, zero-length rows, prefill mask
variants and gradients, and the model/serve-level wiring.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import FpFormat, PAPER_FORMATS
from repro.core.policy import binary32_policy, transprecision_policy
from repro.core.qtensor import decode, encode
from repro.kernels import flash_attention as fa
from repro.models import attention as att
from repro.models.base import ModelConfig

FMTS = list(PAPER_FORMATS) + [None]
FMT_IDS = [f.name if f is not None else "f32-unpacked" for f in FMTS]


def _mk(B=3, S=160, H=2, G=4, dh=32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, H, G, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    return q, k, v


def _pack(k, v, fmt):
    if fmt is None:
        return k, v
    return encode(k, fmt), encode(v, fmt)


# ---------------------------------------------------------------- decode
# (the registry-level ragged oracle pins live in tests/test_conformance.py
# for EVERY spelling; what stays here is kernel-level behavior the sweep
# cannot express -- block_kv is a kernel parameter, not registry-visible,
# so the cross-tile online-softmax carry must be pinned right here)

@pytest.mark.parametrize("fmt", FMTS, ids=FMT_IDS)
def test_flash_decode_multi_tile_matches_dequantize_oracle(fmt):
    """block_kv < S forces the online softmax across KV tiles; the
    cross-tile (max, sum, acc) carry must reproduce the one-shot oracle."""
    q, k, v = _mk()
    kp, vp = _pack(k, v, fmt)
    lengths = jnp.asarray([160, 7, 93], jnp.int32)  # ragged batch
    got = fa.flash_decode(q, kp, vp, fmt, lengths, block_kv=64)
    want = fa.flash_decode_reference(q, kp, vp, fmt, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def _f32_contract_bound(q, k, v, n_valid):
    """Largest |kernel - oracle| the compute contract allows when both
    accumulate in f32 but may sum in different orders (XLA picks the
    order per backend and per fusion, so a bit-exact pin holds on one
    compiler build only).  Standard forward bounds with u = 2^-24:

    * a score is a dh-term dot, so its error is at most
      e_s = dh * u * scale * max sum|q k|;
    * exp turns that into a relative weight error of at most 2 e_s + u
      (the running max shifts both sides identically);
    * the normalized PV is a convex combination of at most ``n_valid``
      rows, each term and the normalizing sum adding about n_valid * u.

    Hence |delta| <= (4 e_s + (2 n_valid + 4) u) * max|v|.
    """
    u = 2.0 ** -24
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    dh = q.shape[-1]
    qk = np.einsum("bhgd,bshd->bhgs", np.abs(q), np.abs(k)).max()
    e_s = dh * u * qk / np.sqrt(dh)
    return (4 * e_s + (2 * n_valid + 4) * u) * np.abs(v).max()


@pytest.mark.parametrize("fmt", FMTS, ids=FMT_IDS)
def test_flash_decode_single_tile_bit_exact(fmt):
    """One KV tile covering the cache == the oracle's op sequence, up to
    the f32 summation order the compiler is free to choose."""
    q, k, v = _mk(S=96)
    kp, vp = _pack(k, v, fmt)
    lengths = jnp.asarray([96, 5, 64], jnp.int32)
    got = fa.flash_decode(q, kp, vp, fmt, lengths, block_kv=128)
    want = fa.flash_decode_reference(q, kp, vp, fmt, lengths)
    kd, vd = (x if fmt is None else decode(x, fmt) for x in (kp, vp))
    bound = _f32_contract_bound(q, kd, vd, n_valid=96)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= bound


def test_flash_decode_ignores_invalid_slots():
    """Slots at index >= length must not influence the output at all."""
    fmt = PAPER_FORMATS[0]  # binary8
    q, k, v = _mk(S=64)
    lengths = jnp.asarray([40, 7, 64], jnp.int32)
    kp, vp = _pack(k, v, fmt)
    out1 = np.asarray(fa.flash_decode(q, kp, vp, fmt, lengths, block_kv=32))
    # corrupt everything beyond each row's length with huge garbage
    mask = (np.arange(64)[None, :, None, None]
            >= np.asarray(lengths)[:, None, None, None])
    garbage = np.full(kp.shape, 0x7B, kp.dtype)  # large finite binary8
    kp2 = jnp.asarray(np.where(mask, garbage, np.asarray(kp)))
    vp2 = jnp.asarray(np.where(mask, garbage, np.asarray(vp)))
    out2 = np.asarray(fa.flash_decode(q, kp2, vp2, fmt, lengths, block_kv=32))
    np.testing.assert_array_equal(out1.view(np.uint32), out2.view(np.uint32))


def test_flash_decode_clamps_lengths_beyond_capacity():
    """mha passes pos+1 unclamped when decoding past a full cache; padded
    KV-block slots must never enter the softmax denominator."""
    fmt = PAPER_FORMATS[0]
    q, k, v = _mk(S=10)  # S not a multiple of block_kv => padding exists
    kp, vp = _pack(k, v, fmt)
    over = jnp.asarray([12, 300, 10], jnp.int32)    # all >= S
    full = jnp.asarray([10, 10, 10], jnp.int32)
    got = np.asarray(fa.flash_decode(q, kp, vp, fmt, over, block_kv=8))
    want = np.asarray(fa.flash_decode(q, kp, vp, fmt, full, block_kv=8))
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_flash_decode_zero_length_row_is_zero():
    q, k, v = _mk(S=32)
    kp, vp = _pack(k, v, PAPER_FORMATS[0])
    lengths = jnp.asarray([0, 32, 1], jnp.int32)
    out = np.asarray(fa.flash_decode(q, kp, vp, PAPER_FORMATS[0], lengths))
    assert np.all(out[0] == 0.0)
    assert np.all(np.isfinite(out))


# ------------------------------------------------------- mha integration

def _cfg(**kw):
    base = dict(arch="t", family="dense", n_layers=1, d_model=64, n_heads=4,
                n_kv=2, d_ff=128, vocab=64)
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("fmt", PAPER_FORMATS, ids=[f.name for f in
                                                    PAPER_FORMATS])
def test_mha_decode_flash_vs_xla_native(fmt):
    """decode_impl="flash_pallas" vs the XLA path for every paper format
    (native mode; the XLA path computes in bf16, hence the loose bound)."""
    cfg = _cfg()
    pol = transprecision_policy(kv_fmt=fmt)
    p = att.attn_init(jax.random.PRNGKey(0), cfg, pol.dtype("attn_w"))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64),
                          pol.dtype("act")) * 0.5
    xt = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 64),
                           pol.dtype("act")) * 0.5
    _, cache = att.prefill_to_cache(p, x, cfg, pol, capacity=32)
    o_xla, c_xla = att.mha(p, xt, cfg, pol, cache=cache)
    cfg_f = dataclasses.replace(cfg, decode_impl="flash_pallas")
    o_fl, c_fl = att.mha(p, xt, cfg_f, pol, cache=cache)
    np.testing.assert_allclose(np.asarray(o_xla, np.float32),
                               np.asarray(o_fl, np.float32),
                               rtol=5e-2, atol=5e-2)
    # the cache update is backend-independent
    np.testing.assert_array_equal(np.asarray(c_xla.k), np.asarray(c_fl.k))
    assert int(c_xla.pos) == int(c_fl.pos)


@pytest.mark.parametrize("fmt", list(PAPER_FORMATS) + [FpFormat(3, 4)],
                         ids=[f.name for f in PAPER_FORMATS] + ["flexfloat"])
def test_mha_decode_flash_vs_xla_emulated(fmt):
    """Emulated mode: the cache holds sanitized f32 values (any (e, m),
    not just the native four); flash reads them unpacked."""
    cfg = _cfg()
    pol = transprecision_policy(mode="emulated", kv_fmt=fmt)
    p = att.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64),
                          jnp.float32) * 0.5
    xt = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 64),
                           jnp.float32) * 0.5
    _, cache = att.prefill_to_cache(p, x, cfg, pol, capacity=32)
    o_xla, _ = att.mha(p, xt, cfg, pol, cache=cache)
    cfg_f = dataclasses.replace(cfg, decode_impl="flash_pallas")
    o_fl, _ = att.mha(p, xt, cfg_f, pol, cache=cache)
    np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_fl),
                               rtol=5e-2, atol=5e-2)


def test_mha_decode_flash_vs_xla_binary32_tight():
    """With a binary32 policy both backends run the same f32 math: the only
    divergence is reduction order, so the bound is a few ulp."""
    cfg = _cfg()
    pol = binary32_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64),
                          jnp.float32) * 0.5
    xt = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 64),
                           jnp.float32) * 0.5
    _, cache = att.prefill_to_cache(p, x, cfg, pol, capacity=32)
    o_xla, _ = att.mha(p, xt, cfg, pol, cache=cache)
    cfg_f = dataclasses.replace(cfg, decode_impl="flash_pallas")
    o_fl, _ = att.mha(p, xt, cfg_f, pol, cache=cache)
    np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_fl),
                               rtol=1e-5, atol=1e-6)


def test_mha_decode_policy_override_wins():
    cfg = _cfg()  # decode_impl defaults to "xla"
    pol = dataclasses.replace(binary32_policy(), decode_impl="flash_pallas")
    p = att.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64), jnp.float32)
    xt = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 64), jnp.float32)
    _, cache = att.prefill_to_cache(p, x, cfg, binary32_policy(), capacity=16)
    o_ov, _ = att.mha(p, xt, cfg, pol, cache=cache)
    cfg_f = dataclasses.replace(cfg, decode_impl="flash_pallas")
    o_cfg, _ = att.mha(p, xt, cfg_f, binary32_policy(), cache=cache)
    np.testing.assert_array_equal(np.asarray(o_ov), np.asarray(o_cfg))


# (the sliding-window ring-buffer wrap pin moved to
# tests/test_conformance.py::test_conformance_ring_buffer_wrap, which runs
# it for every registry spelling)


# ------------------------------------------------------------- prefill

@pytest.mark.parametrize("window,prefix", [(None, 0), (8, 0), (None, 5),
                                           (16, 5)],
                         ids=["causal", "window", "prefix", "window+prefix"])
def test_flash_prefill_matches_xla(window, prefix):
    cfg = _cfg(window=window)
    pol = binary32_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64),
                          jnp.float32) * 0.5
    o_xla, _ = att.mha(p, x, cfg, pol, causal=True, prefix_len=prefix)
    cfg_f = dataclasses.replace(cfg, decode_impl="flash_pallas")
    o_fl, _ = att.mha(p, x, cfg_f, pol, causal=True, prefix_len=prefix)
    np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_fl),
                               rtol=1e-5, atol=1e-6)


def test_flash_prefill_vs_xla_transprecision():
    """Transprecision policy: the fused path honors operand storage formats
    but keeps probs in f32 (they never leave VMEM, so the attn_probs
    narrowing of materialized probabilities does not apply) -- it may only
    be *wider* than the XLA path, within act-format resolution."""
    cfg = _cfg()
    pol = transprecision_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg, pol.dtype("attn_w"))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64),
                          pol.dtype("act")) * 0.5
    o_xla, _ = att.mha(p, x, cfg, pol, causal=True)
    cfg_f = dataclasses.replace(cfg, decode_impl="flash_pallas")
    o_fl, _ = att.mha(p, x, cfg_f, pol, causal=True)
    assert o_fl.dtype == o_xla.dtype  # both re-cast to the act format
    np.testing.assert_allclose(np.asarray(o_xla, np.float32),
                               np.asarray(o_fl, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_flash_prefill_matches_chunked_xla():
    """flash subsumes the unrolled q-chunk loop (chunk -> block_q)."""
    cfg = _cfg()
    pol = binary32_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64),
                          jnp.float32) * 0.5
    o_xla, _ = att.mha(p, x, cfg, pol, causal=True, chunk=16)
    cfg_f = dataclasses.replace(cfg, decode_impl="flash_pallas")
    o_fl, _ = att.mha(p, x, cfg_f, pol, causal=True, chunk=16)
    np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_fl),
                               rtol=1e-5, atol=1e-6)


def test_flash_prefill_packed_kv_oracle():
    """Prefill straight from packed payloads (cache re-use scenarios)."""
    fmt = PAPER_FORMATS[0]
    rng = np.random.default_rng(3)
    B, S, H, G, dh = 2, 48, 2, 3, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, G, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    kp, vp = encode(k, fmt), encode(v, fmt)
    got = fa.flash_prefill(q, kp, vp, fmt, block_q=16, block_kv=16)
    # oracle: XLA dequantize + full masked softmax
    from repro.core.qtensor import decode
    kd, vd = decode(kp, fmt), decode(vp, fmt)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, kd,
                   preferred_element_type=jnp.float32) / np.sqrt(dh)
    m = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(m[None, None, None], s.astype(jnp.float32), att.NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    want = jnp.einsum("bhgqk,bkhd->bqhgd", pr, vd,
                      preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("window,prefix", [(None, 0), (8, 0), (None, 5)],
                         ids=["causal", "window", "prefix"])
def test_flash_prefill_gradients_match_xla(window, prefix):
    """Training with decode_impl="flash_pallas" must work: the kernel's
    custom backward (XLA-reference recompute) has to agree with
    differentiating the XLA path directly."""
    cfg = _cfg(window=window)
    cfg_f = dataclasses.replace(cfg, decode_impl="flash_pallas")
    pol = binary32_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64),
                          jnp.float32) * 0.5

    def loss(params, c):
        out, _ = att.mha(params, x, c, pol, causal=True, prefix_len=prefix)
        return jnp.sum(out * out)

    l_x, g_x = jax.value_and_grad(loss)(p, cfg)
    l_f, g_f = jax.value_and_grad(loss)(p, cfg_f)
    np.testing.assert_allclose(float(l_x), float(l_f), rtol=1e-5)
    for key in g_x:
        np.testing.assert_allclose(np.asarray(g_x[key]),
                                   np.asarray(g_f[key]),
                                   rtol=1e-4, atol=1e-5, err_msg=key)


# --------------------------------------------------------------- serving

def test_serve_end_to_end_flash_decode():
    from repro.launch.serve import main
    reqs = main(["--arch", "llama3-8b", "--reduced", "--requests", "2",
                 "--slots", "2", "--max-new", "3", "--prompt-len", "4",
                 "--capacity", "16", "--decode-impl", "flash_pallas"])
    assert all(r.done for r in reqs)
    assert all(len(r.generated) >= 3 for r in reqs)
