"""Attention-backend registry tests: spelling validation at construction
time, wrapper composition, and the composed ``flash_shmap+flash_pallas``
path against the XLA oracle on a 2-device host-platform mesh (the
olmax/HomebrewNLP ``--xla_force_host_platform_device_count`` harness
idiom)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import BINARY8
from repro.core.policy import (DECODE_IMPLS, PrecisionPolicy, binary32_policy,
                               transprecision_policy)
from repro.kernels import dispatch
from repro.models import attention as att
from repro.models.base import ModelConfig


# ------------------------------------------------------------- spellings

def test_legal_impls_include_composed():
    legal = dispatch.legal_impls()
    assert "flash_shmap+flash_pallas" in legal
    assert "flash_shmap+xla" in legal
    assert "flash_shmap+paged" in legal
    assert "ring+flash_pallas" in legal
    assert "ring+xla" in legal
    assert "ring+paged" in legal
    assert set(("xla", "flash_pallas", "paged", "flash_shmap",
                "ring")) <= set(legal)
    assert DECODE_IMPLS == (None,) + legal


# the legal-spelling list grows with every backend; pin each *class* of
# rejection (unknown base, wrapper in base position, base in wrapper
# position, duplicate wrapper, empties/typos) and the actionable error
@pytest.mark.parametrize("bad", [
    "flashpallas",                    # unknown base, close typo
    "flash_shmap+nope",               # wrapper + unknown base
    "xla+flash_shmap",                # wrapper last (order matters)
    "paged+flash_shmap",              # wrapper last, paged base
    "flash_pallas+xla",               # base used as wrapper
    "paged+xla",                      # base used as wrapper (paged)
    "flash_shmap+",                   # empty base
    "flash_shmap+flash_shmap",        # duplicate wrapper as base
    "flash_shmap+flash_shmap+xla",    # duplicate wrapper
    "ring+ring",                      # duplicate wrapper (ring)
    "xla+ring",                       # wrapper last (ring)
    "flash_shmap+ring+xla",           # two wrappers: both consume the
    "ring+flash_shmap+xla",           #   model axis, chains are illegal
    "ring+flash_shmap",               # wrapper as base
    "pallas",                         # unknown
])
def test_validate_impl_rejects_with_legal_list(bad):
    with pytest.raises(ValueError) as ei:
        dispatch.validate_impl(bad)
    msg = str(ei.value)
    assert "flash_shmap+flash_pallas" in msg  # actionable list
    assert "flash_shmap+paged" in msg
    assert repr(bad) in msg                   # names the offender


def test_validate_impl_none_handling():
    assert dispatch.validate_impl(None) is None
    with pytest.raises(ValueError) as ei:
        dispatch.validate_impl(None, allow_none=False, what="serve impl")
    assert "serve impl" in str(ei.value)


def test_policy_rejects_unknown_impl_at_construction():
    with pytest.raises(ValueError) as ei:
        PrecisionPolicy(formats={}, decode_impl="flash_palas")  # typo
    assert "legal spellings" in str(ei.value)


def test_model_config_rejects_unknown_impl_at_construction():
    with pytest.raises(ValueError) as ei:
        ModelConfig(arch="t", family="dense", n_layers=1, d_model=32,
                    n_heads=2, n_kv=2, d_ff=64, vocab=64,
                    decode_impl="flash")
    assert "legal spellings" in str(ei.value)


def test_shape_spec_rejects_unknown_impl():
    from repro.configs.shapes import ShapeSpec
    with pytest.raises(ValueError):
        ShapeSpec("x", "decode", 128, 1, decode_impl="fused")


def test_composed_policy_accepted():
    pol = transprecision_policy(decode_impl="flash_shmap+flash_pallas")
    assert pol.decode_impl == "flash_shmap+flash_pallas"


def test_canonicalize_wrapper_alone_gets_default_inner():
    assert dispatch.canonicalize_impl("flash_shmap") == ("flash_shmap",
                                                         "xla")
    assert dispatch.canonicalize_impl("ring") == ("ring", "xla")


# ------------------------------------------------- wrapper without a mesh

def _mk(B=2, S=64, H=2, G=2, dh=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, H, G, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("wrapper", ["flash_shmap", "ring"])
def test_wrapper_falls_back_to_inner_without_mesh(wrapper):
    """wrapper+flash_pallas outside any mesh == plain flash_pallas."""
    q, k, v = _mk()
    pol = binary32_policy()
    nv = jnp.asarray([64, 10], jnp.int32)
    composed = dispatch.resolve_decode(f"{wrapper}+flash_pallas")
    plain = dispatch.resolve_decode("flash_pallas")
    a = composed(q, k, v, nv, scale=0.25, policy=pol)
    b = plain(q, k, v, nv, scale=0.25, policy=pol)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_wrapper_sees_mesh_from_plain_with_block():
    """The flash_shmap wrapper (and default_serving_impl) must see a mesh
    activated by a classic ``with mesh:`` block, not only one set through
    jax.sharding.set_mesh -- i.e. compat.get_ambient_mesh falls back to the
    thread-local *physical* mesh.  Single-device model axis: the sharded
    branch runs (n_model=1) and must equal the unsharded inner backend."""
    from jax.sharding import Mesh

    from repro import compat
    from repro.kernels.dispatch import _shmap_decode

    q, k, v = _mk()
    pol = binary32_policy()
    nv = jnp.asarray([64, 10], jnp.int32)
    plain = dispatch.resolve_decode("xla")
    want = plain(q, k, v, nv, scale=0.25, policy=pol)
    with Mesh(np.array(jax.devices()[:1]), ("model",)) as mesh:
        assert compat.get_ambient_mesh() is not None
        assert "model" in compat.get_ambient_mesh().axis_names
        # the genuinely-sharded branch, reached through the ambient mesh
        got = _shmap_decode(plain, mesh, q, k, v, nv, scale=0.25,
                            policy=pol)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)
    assert compat.get_ambient_mesh() is None  # context exited cleanly


# (the composed-backend-vs-oracle subprocess -- all formats, ragged
# lengths, ring-buffer wrap on a 2-device mesh -- moved to
# tests/test_conformance.py, where the sweep covers EVERY registry
# spelling instead of this file's hand-picked one)


# ------------------------------------------------ prefill through dispatch

def _cfg(**kw):
    base = dict(arch="t", family="dense", n_layers=1, d_model=64, n_heads=4,
                n_kv=2, d_ff=128, vocab=64)
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("impl", ["xla", "flash_pallas"])
def test_prefill_from_cache_matches_full_prefill(impl):
    """Two-chunk continuation prefill over the cache == one-shot prefill
    (binary32 cache: identical K/V bits, so only reduction order differs)."""
    cfg = _cfg(decode_impl=impl)
    pol = binary32_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64),
                          jnp.float32) * 0.5
    full, cache_full = att.prefill_to_cache(p, x, cfg, pol, capacity=48)
    # chunk 1 builds the cache, chunk 2 continues from it
    out1, cache = att.prefill_to_cache(p, x[:, :20], cfg, pol, capacity=48)
    out2, cache = att.prefill_from_cache(p, x[:, 20:], cfg, pol, cache,
                                         q_offset=20)
    np.testing.assert_allclose(np.asarray(full[:, :20]), np.asarray(out1),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(full[:, 20:]), np.asarray(out2),
                               rtol=1e-5, atol=1e-6)
    assert int(cache.pos) == 32
    # K is recomputed per chunk: x @ wk over d_model f32 terms, whose
    # summation order the compiler may pick per row count (so bits can
    # differ between a 12- and a 32-row matmul).  Contract bound: the
    # d-term dot error gamma_d * (|x| @ |wk|), doubled by the rope
    # rotation (|cos| + |sin| <= sqrt 2), plus rope's own few ulp of |k|.
    u = 2.0 ** -24
    dot_err = cfg.d_model * u * float(jnp.max(jnp.abs(x) @ jnp.abs(p["wk"])))
    kf = np.asarray(cache_full.k[:, :32])
    bound = 2 * dot_err + 4 * u * float(np.abs(kf).max())
    assert np.abs(np.asarray(cache.k[:, :32]) - kf).max() <= bound


@pytest.mark.parametrize("composed", ["flash_shmap+flash_pallas",
                                      "ring+flash_pallas"])
def test_prefill_from_cache_packed_flash_vs_xla(composed):
    """Continuation over a *packed* (binary8) cache: the flash backend reads
    the payload in-register, the XLA backend dequantizes -- same bits, same
    dispatch, results agree to reduction-order tolerance.  Composed
    spellings (either wrapper) resolve to their base for prefill."""
    pol = binary32_policy(kv_fmt=BINARY8)
    cfg_x = _cfg(decode_impl="xla")
    cfg_f = _cfg(decode_impl=composed)  # base = flash_pallas
    p = att.attn_init(jax.random.PRNGKey(0), cfg_x, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64),
                          jnp.float32) * 0.5
    _, cache = att.prefill_to_cache(p, x[:, :16], cfg_x, pol, capacity=32)
    o_x, c_x = att.prefill_from_cache(p, x[:, 16:], cfg_x, pol, cache,
                                      q_offset=16)
    o_f, c_f = att.prefill_from_cache(p, x[:, 16:], cfg_f, pol, cache,
                                      q_offset=16)
    np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_f),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(c_x.k.astype(jnp.float32)),
        np.asarray(c_f.k.astype(jnp.float32)))


def test_prefill_from_cache_rejects_ring_buffer():
    cfg = _cfg(window=8)
    pol = binary32_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 64), jnp.float32)
    _, cache = att.prefill_to_cache(p, x, cfg, pol, capacity=64)
    with pytest.raises(ValueError):
        att.prefill_from_cache(p, x, cfg, pol, cache, q_offset=6)


def test_prefill_from_cache_rejects_overflow():
    cfg = _cfg()
    pol = binary32_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64), jnp.float32)
    _, cache = att.prefill_to_cache(p, x, cfg, pol, capacity=16)
    with pytest.raises(ValueError):
        att.prefill_from_cache(p, x, cfg, pol, cache, q_offset=12)


def test_ring_cache_slot_convention_evicts_oldest():
    """After a prefill longer than the window, the token at absolute
    position p must sit at slot p % cap -- the decode path's write
    convention (slot = pos % cap) -- so the next decode step overwrites
    the OLDEST cached token, not an arbitrary one."""
    cfg = _cfg(window=8)
    pol = binary32_policy()
    S, cap = 12, 8
    # k[:, p] == p everywhere: the slot content names its token position
    posval = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.float32)[None, :, None, None],
        (2, S, cfg.n_kv, cfg.head_dim))
    cache = att._build_cache(posval, posval, cfg, pol, capacity=64, S=S)
    assert cache.capacity == cap and int(cache.pos) == S
    got = np.asarray(cache.k[0, :, 0, 0])
    expected = np.zeros(cap)
    for p in range(S - cap, S):  # cached positions 4..11
        expected[p % cap] = p
    np.testing.assert_array_equal(got, expected)
    # the next decode write lands on slot pos % cap and evicts position 4,
    # the oldest -- exactly the token leaving the sliding window
    assert expected[int(cache.pos) % cap] == S - cap


def test_prefill_to_cache_is_mha_with_capacity():
    """prefill_to_cache == mha(cache_capacity=...): one K/V computation,
    one dispatch path, identical outputs and cache."""
    cfg = _cfg()
    pol = transprecision_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg, pol.dtype("attn_w"))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64),
                          pol.dtype("act")) * 0.5
    o1, c1 = att.prefill_to_cache(p, x, cfg, pol, capacity=32)
    o2, c2 = att.mha(p, x, cfg, pol, causal=True, cache_capacity=32)
    np.testing.assert_array_equal(np.asarray(o1, np.float32),
                                  np.asarray(o2, np.float32))
    np.testing.assert_array_equal(
        np.asarray(c1.k.astype(jnp.float32)),
        np.asarray(c2.k.astype(jnp.float32)))
    assert int(c1.pos) == int(c2.pos) == 12
