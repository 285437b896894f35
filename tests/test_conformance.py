"""Cross-backend conformance: every registry spelling vs ONE oracle.

The attention-backend registry (``kernels/dispatch.py``) now has 10+ legal
``decode_impl`` spellings, and per-backend copy-pasted oracle tests stopped
scaling: each new backend meant hand-porting the ragged/ring-buffer/paged
cases into yet another file, and nothing guaranteed the copies stayed in
sync with one reference.  This suite replaces them with a single
parametrized sweep whose spelling axis is ``dispatch.legal_impls()``
**read at collection time** -- registering a backend in the registry is
what enrolls it here; there is no hand-maintained list to extend and no
per-spelling xfail to forget (a spelling outside the registry cannot even
be named: the parametrization is the registry).

Every cell pins its spelling against the single XLA dequantize oracle
(``flash_decode_reference``: decode the packed payload to f32, masked
softmax in f32), the same golden-reference discipline FPnew applies to its
multi-format datapaths (every format/op pair verified against one
reference).  Scenario axes:

  * all four paper storage formats (binary8 / 16 / 16alt / 32),
  * ragged lengths including a zero-length row,
  * the sliding-window ring buffer wrapping past its capacity,
  * non-contiguous (shuffled) pages for pool-layout bases,
  * no mesh (wrapper fallback), a 1-device mesh (the genuinely sharded
    branch), and a 2-device mesh (subprocess -- real shards, real
    ppermute rotation for the ``ring`` wrapper).

Tolerances are derived from the *base backend's documented compute
contract*, never per-spelling: kernel bases (``flash_pallas``, ``paged``)
honor storage bits exactly and accumulate in f32, so they must match the
oracle to <= 1e-6; the ``xla`` base computes narrow-in/f32-accumulate
(operands cast to bf16, the MXU contract of ``models/layers.py``), so for
non-binary32 storage its deviation is bf16 operand rounding, bounded but
not 1e-6.  A new backend defaults to the strict bound.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from conftest import run_child
from repro.core.formats import PAPER_FORMATS
from repro.core.policy import binary32_policy
from repro.core.qtensor import encode
from repro.kernels import dispatch, paged_cache
from repro.kernels.flash_attention import flash_decode_reference
from repro.models import attention as att
from repro.models.base import ModelConfig

# ---------------------------------------------------------------------------
# collection-time registry sweep: the spelling axis IS the registry.  The
# dict comprehension resolves every spelling while the module is imported,
# so a backend registered in name only (spelling in legal_impls() without
# a decode/prefill callable) fails collection of this whole file -- it can
# never hide behind a quiet xfail.
# ---------------------------------------------------------------------------

IMPLS = dispatch.legal_impls()
_RESOLVED = {impl: (dispatch.resolve_decode(impl),
                    dispatch.resolve_prefill(impl)) for impl in IMPLS}

FMT_IDS = [f.name for f in PAPER_FORMATS]

PAGE = 16  # conformance page granule (multiple of 8; see validate_page_size)


def _base_of(impl: str) -> str:
    return dispatch.canonicalize_impl(impl)[-1]


def _tol(impl: str, fmt) -> float:
    """Conformance tolerance vs the f32 dequantize oracle, derived from the
    base backend's compute contract (structural -- never a per-spelling
    exception, so a new backend is held to the strict bound by default)."""
    if _base_of(impl) == "xla" and not fmt.is_binary32:
        # narrow-in/f32-accumulate: operands pass through bf16, so the
        # deviation is bf16 operand rounding (~2^-8 relative), not a bug
        return 2e-2
    return 1e-6


def _mk(B=4, S=96, H=2, G=4, dh=32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, H, G, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    return q, k, v


# ragged axis: full row / zero-length row / row inside the first shard /
# row straddling the 2-way shard boundary (S=96 -> shards of 48)
RAGGED = (96, 0, 7, 53)


def _native_cache(k, v, fmt):
    """Encode to the packed payload, then to the native storage dtype --
    exactly the bits a serving cache holds."""
    kp, vp = encode(k, fmt), encode(v, fmt)
    return (kp, vp,
            jax.lax.bitcast_convert_type(kp, fmt.native_dtype),
            jax.lax.bitcast_convert_type(vp, fmt.native_dtype))


def _run_spelling(impl, q, ck, cv, lengths, pol, scale, *, tables=None,
                  pools=None):
    """Invoke ``impl`` through the registry on a contiguous cache (identity
    paging for pool bases) or on explicit (pools, tables) when given."""
    fn = _RESOLVED[impl][0]
    if _base_of(impl) == "paged":
        if pools is None:
            kpg, vpg, tables = paged_cache.paged_view_of_contiguous(
                ck, cv, PAGE)
        else:
            kpg, vpg = pools
        return fn(q, kpg, vpg, lengths, scale=scale, policy=pol,
                  block_tables=tables)
    return fn(q, ck, cv, lengths, scale=scale, policy=pol)


def _check(impl, fmt, got, want):
    err = float(np.abs(np.asarray(got) - np.asarray(want)).max())
    assert not np.isnan(np.asarray(got)).any(), (impl, fmt.name)
    assert err <= _tol(impl, fmt), (
        f"{impl} x {fmt.name}: max |got - oracle| = {err:.3e} exceeds the "
        f"contract tolerance {_tol(impl, fmt):.0e}")


# ---------------------------------------------------------------------------
# registration completeness (cheap, and the failure mode is actionable)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
def test_spelling_resolves_and_validates(impl):
    assert callable(_RESOLVED[impl][0]) and callable(_RESOLVED[impl][1])
    assert dispatch.validate_impl(impl) == impl


def test_ring_shape_pin_exists():
    from repro.configs.shapes import ALL_SHAPES
    assert ALL_SHAPES["decode_32k_ring"].decode_impl == "ring+flash_pallas"


# ---------------------------------------------------------------------------
# ragged decode vs the oracle: wrapper fallback (no mesh) and the genuinely
# sharded branch (1-device mesh; ppermute-free degenerate ring)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", PAPER_FORMATS, ids=FMT_IDS)
@pytest.mark.parametrize("impl", IMPLS)
def test_conformance_ragged(impl, fmt):
    q, k, v = _mk()
    kp, vp, ck, cv = _native_cache(k, v, fmt)
    lengths = jnp.asarray(RAGGED, jnp.int32)
    pol = binary32_policy(kv_fmt=fmt)
    scale = float(1.0 / np.sqrt(q.shape[-1]))
    want = flash_decode_reference(q, kp, vp, fmt, lengths, scale=scale)
    got = _run_spelling(impl, q, ck, cv, lengths, pol, scale)
    _check(impl, fmt, got, want)
    np.testing.assert_array_equal(np.asarray(got)[1], 0.0)  # empty row


@pytest.mark.parametrize("fmt", PAPER_FORMATS, ids=FMT_IDS)
@pytest.mark.parametrize("impl", IMPLS)
def test_conformance_ragged_one_device_mesh(impl, fmt):
    q, k, v = _mk()
    kp, vp, ck, cv = _native_cache(k, v, fmt)
    lengths = jnp.asarray(RAGGED, jnp.int32)
    pol = binary32_policy(kv_fmt=fmt)
    scale = float(1.0 / np.sqrt(q.shape[-1]))
    want = flash_decode_reference(q, kp, vp, fmt, lengths, scale=scale)
    with Mesh(np.array(jax.devices()[:1]), ("model",)):
        got = _run_spelling(impl, q, ck, cv, lengths, pol, scale)
    _check(impl, fmt, got, want)


# ---------------------------------------------------------------------------
# non-contiguous pages: pool-layout bases only (the axis does not exist for
# contiguous cache layouts -- a structural property of the base, not a
# per-spelling marker)
# ---------------------------------------------------------------------------

def _scattered_pool(payload, tables, num_pages, page):
    c = np.asarray(payload)
    pool = np.zeros((num_pages, page) + c.shape[2:], dtype=c.dtype)
    B, n_pages = tables.shape
    for b in range(B):
        for p in range(n_pages):
            if tables[b, p] >= 0:
                pool[tables[b, p]] = c[b, p * page:(p + 1) * page]
    return jnp.asarray(pool)


def _shuffled_tables(B, n_pages, num_pages, needs, seed=1):
    rng = np.random.default_rng(seed)
    perm = iter(rng.permutation(num_pages).tolist())
    tables = np.full((B, n_pages), -1, np.int32)
    for b, need in enumerate(needs):
        for p in range(need):
            tables[b, p] = next(perm)
    return tables


@pytest.mark.parametrize("fmt", PAPER_FORMATS, ids=FMT_IDS)
@pytest.mark.parametrize("impl", IMPLS)
def test_conformance_noncontiguous_pages(impl, fmt):
    if _base_of(impl) != "paged":
        pytest.skip("cache layout axis exists only for pool-layout bases")
    q, k, v = _mk()
    kp, vp, _, _ = _native_cache(k, v, fmt)
    n_pages, num_pages = 96 // PAGE, 24  # pool page axis shardable by 2
    # row 0 spans 6 shuffled pages, row 1 maps nothing (zero length), row 2
    # lives in one page, row 3 straddles a partial page
    tables = _shuffled_tables(4, n_pages, num_pages, needs=[6, 0, 1, 4])
    assert (tables[0] >= 0).sum() >= 3  # genuinely non-contiguous
    pools = (jax.lax.bitcast_convert_type(
                 _scattered_pool(kp, tables, num_pages, PAGE),
                 fmt.native_dtype),
             jax.lax.bitcast_convert_type(
                 _scattered_pool(vp, tables, num_pages, PAGE),
                 fmt.native_dtype))
    lengths = jnp.asarray(RAGGED, jnp.int32)
    pol = binary32_policy(kv_fmt=fmt)
    scale = float(1.0 / np.sqrt(q.shape[-1]))
    want = flash_decode_reference(q, kp, vp, fmt, lengths, scale=scale)
    with Mesh(np.array(jax.devices()[:1]), ("model",)):
        got = _run_spelling(impl, q, None, None, lengths, pol, scale,
                            tables=jnp.asarray(tables), pools=pools)
    _check(impl, fmt, got, want)


# ---------------------------------------------------------------------------
# sliding-window ring-buffer wrap, through the full model-level decode path
# (prefill past the window, then decode until the ring wraps): every
# spelling must track the oracle spelling step for step
# ---------------------------------------------------------------------------

def _cfg(**kw):
    base = dict(arch="t", family="dense", n_layers=1, d_model=64, n_heads=4,
                n_kv=2, d_ff=128, vocab=64)
    base.update(kw)
    return ModelConfig(**base)


def _ring_wrap_trajectory(impl, steps=12):
    cfg = _cfg(window=8, decode_impl=impl)
    pol = binary32_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64),
                          jnp.float32) * 0.5
    _, cache = att.prefill_to_cache(p, x, cfg, pol, capacity=64)
    assert cache.capacity == cfg.window  # ring buffer engaged
    outs = []
    with Mesh(np.array(jax.devices()[:1]), ("model",)):
        for step in range(steps):
            xt = jax.random.normal(jax.random.PRNGKey(10 + step),
                                   (2, 1, 64), jnp.float32) * 0.5
            o, cache = att.mha(p, xt, cfg, pol, cache=cache)
            outs.append(np.asarray(o))
    return outs, np.asarray(cache.k)


@pytest.fixture(scope="module")
def ring_wrap_oracle():
    return _ring_wrap_trajectory("xla")


@pytest.mark.parametrize("impl", IMPLS)
def test_conformance_ring_buffer_wrap(impl, ring_wrap_oracle):
    want, want_k = ring_wrap_oracle
    got, got_k = _ring_wrap_trajectory(impl)
    for step, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6,
                                   err_msg=f"{impl} ring-wrap step {step}")
    np.testing.assert_array_equal(got_k, want_k)  # cache update is shared


# ---------------------------------------------------------------------------
# 2-device host mesh: real shards, real neighbor rotation.  ONE subprocess
# (device count locks at jax init) that re-derives the spelling sweep from
# legal_impls() *inside the child*, so registry growth is covered here too.
# ---------------------------------------------------------------------------

_TWO_DEVICE_CONFORMANCE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.core.formats import PAPER_FORMATS
from repro.core.policy import binary32_policy
from repro.core.qtensor import encode
from repro.kernels import dispatch
from repro.kernels.flash_attention import flash_decode_reference
import repro.models.attention as att  # registers every backend

mesh = compat.make_mesh((2,), ("model",))
IMPLS = dispatch.legal_impls()  # derived in-child: new backends sweep too
base_of = lambda impl: dispatch.canonicalize_impl(impl)[-1]

rng = np.random.default_rng(0)
B, S, H, G, dh = 4, 96, 2, 4, 32
page, n_pages, num_pages = 16, 6, 24   # pool page axis: 24 % 2 == 0
q = jnp.asarray(rng.normal(size=(B, H, G, dh)), jnp.float32)
k = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
v = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
# ragged: full row / zero-length row / row entirely inside shard 0 / row
# straddling the shard boundary (s_loc = 48)
lengths = jnp.asarray([96, 0, 7, 53], jnp.int32)
scale = float(1.0 / np.sqrt(dh))
tables = np.full((B, n_pages), -1, np.int32)
perm = iter(rng.permutation(num_pages).tolist())
for b, need in enumerate([6, 0, 1, 4]):
    for p in range(need):
        tables[b, p] = next(perm)

def scatter(payload):
    c = np.asarray(payload)
    pool = np.zeros((num_pages, page) + c.shape[2:], dtype=c.dtype)
    for b in range(B):
        for p in range(n_pages):
            if tables[b, p] >= 0:
                pool[tables[b, p]] = c[b, p*page:(p+1)*page]
    return jnp.asarray(pool)

for fmt in PAPER_FORMATS:
    kp, vp = encode(k, fmt), encode(v, fmt)
    pol = binary32_policy(kv_fmt=fmt)
    ck = jax.lax.bitcast_convert_type(kp, fmt.native_dtype)
    cv = jax.lax.bitcast_convert_type(vp, fmt.native_dtype)
    ckpool = jax.lax.bitcast_convert_type(scatter(kp), fmt.native_dtype)
    cvpool = jax.lax.bitcast_convert_type(scatter(vp), fmt.native_dtype)
    tj = jnp.asarray(tables)
    want = flash_decode_reference(q, kp, vp, fmt, lengths, scale=scale)
    for impl in IMPLS:
        fn = dispatch.resolve_decode(impl)
        with compat.use_mesh(mesh):
            if base_of(impl) == "paged":
                got = jax.jit(lambda q, a, b, n, t: fn(
                    q, a, b, n, scale=scale, policy=pol,
                    block_tables=t))(q, ckpool, cvpool, lengths, tj)
            else:
                got = jax.jit(lambda q, a, b, n: fn(
                    q, a, b, n, scale=scale,
                    policy=pol))(q, ck, cv, lengths)
        err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
        tol = 2e-2 if (base_of(impl) == "xla"
                       and not fmt.is_binary32) else 1e-6
        assert err <= tol, (impl, fmt.name, err)
        assert not np.isnan(np.asarray(got)).any(), (impl, fmt.name)

# --- ring-buffer wrap through the model-level decode path, sharded --------
from repro.models.base import ModelConfig
cfg = ModelConfig(arch="t", family="dense", n_layers=1, d_model=64,
                  n_heads=4, n_kv=2, d_ff=128, vocab=64, window=8)
pol = binary32_policy()
p = att.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64), jnp.float32) * 0.5
_, cache0 = att.prefill_to_cache(p, x, cfg, pol, capacity=64)
assert cache0.capacity == cfg.window
wrapped = [i for i in IMPLS if len(dispatch.canonicalize_impl(i)) > 1]
caches = {impl: cache0 for impl in ["xla"] + wrapped}
cfgs = {impl: dataclasses.replace(cfg, decode_impl=impl)
        for impl in ["xla"] + wrapped}
# one program steps every spelling (op-by-op shard_map dispatch would take
# minutes): they all project the same x @ wk, which XLA computes once, so
# every ring must receive the same K bits
step_all = jax.jit(lambda x, cs: {
    impl: att.mha(p, x, c, pol, cache=cs[impl]) for impl, c in cfgs.items()})
with compat.use_mesh(mesh):
    for step in range(12):  # 12 steps > window: wraps the ring
        xt = jax.random.normal(jax.random.PRNGKey(10 + step), (2, 1, 64),
                               jnp.float32) * 0.5
        outs = step_all(xt, caches)
        caches = {impl: c for impl, (_, c) in outs.items()}
        for impl in wrapped:
            np.testing.assert_allclose(
                np.asarray(outs["xla"][0]), np.asarray(outs[impl][0]),
                rtol=1e-5, atol=1e-6, err_msg=f"{impl} ring-wrap step {step}")
            np.testing.assert_array_equal(np.asarray(caches["xla"].k),
                                          np.asarray(caches[impl].k))
print("CONFORMANCE_2DEV_OK")
"""


def test_conformance_two_device_mesh_subprocess():
    run_child(_TWO_DEVICE_CONFORMANCE, "CONFORMANCE_2DEV_OK", timeout=480)
