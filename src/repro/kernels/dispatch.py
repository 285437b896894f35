"""Attention-backend registry: one dispatch point for every decode/prefill
implementation, with composable wrappers.

PR 1 bolted the fused packed-KV kernel onto ``models/attention.py`` behind a
string either/or; this module replaces that with a registry so backends
compose instead of excluding each other:

* **base backends** implement single-token decode over a KV cache
  (``"xla"`` -- the dequantize oracle/fallback; ``"flash_pallas"`` -- the
  fused packed-KV Pallas kernel over a contiguous cache; ``"paged"`` --
  the block-table kernel of ``kernels/paged_attention.py`` over a shared
  page pool, taking an extra ``block_tables`` kwarg) and causal prefill.
* **wrapper backends** transform another backend.  Both wrappers shard
  the cache's *storage* axis over the mesh's ``model`` axis (the sequence
  axis for contiguous bases, the pool's page axis for the ``paged`` base)
  and differ only in the *merge topology*:

  - ``"flash_shmap"`` keeps every shard in place and combines the
    per-shard online-softmax partials (max / sum / weighted-V) with three
    tiny all-to-one collectives (psum-style merge) -- exact softmax
    attention, so ``flash_shmap(flash_pallas)`` streams the *packed*
    payload through the fused kernel *on every chip in parallel*, the
    near-sensor-cluster win (arXiv 2008.12243) applied to serving.
  - ``"ring"`` rotates the K/V payload shards around the mesh ring via
    neighbor-only ``ppermute`` over n_model steps; each device folds
    every incoming shard into its queries' running online-softmax state
    (acc, m, l), so peak per-device live KV stays ONE shard and no
    all-to-one collective ever forms -- the transprecision-cluster
    schedule of Montagna et al. (arXiv 2008.12243: explicit data
    rotation across parallel cores instead of all-to-one reduction)
    applied to the attention merge.  The fold is associative up to f32
    rounding, so any rotation order yields the same softmax (pinned by a
    hypothesis property).

Spellings (``decode_impl`` on configs, policies, shapes and CLI flags)
are ``+``-compositions read left to right, wrapper first::

    "xla"                        # dequantize path
    "flash_pallas"               # fused packed-KV kernel
    "paged"                      # block-table kernel over the page pool
    "flash_shmap"                # == "flash_shmap+xla"
    "flash_shmap+xla"            # sequence-sharded dequantize path
    "flash_shmap+flash_pallas"   # sharded fused kernel (multi-chip serving)
    "flash_shmap+paged"          # page-pool-sharded block-table kernel
    "ring"                       # == "ring+xla"
    "ring+xla"                   # ring-rotated dequantize path (debug oracle)
    "ring+flash_pallas"          # ring-rotated fused kernel
    "ring+paged"                 # ring-rotated page pool (tables rewritten
                                 #   to the rotating owner's local ids)

``validate_impl`` is called at construction time by ``PrecisionPolicy``,
``ModelConfig`` and ``ShapeSpec`` so an unknown spelling fails loudly with
the legal list instead of silently falling through to the XLA path.
Every legal spelling is conformance-tested against the single XLA
dequantize oracle by ``tests/test_conformance.py``, whose parametrization
is ``legal_impls()`` itself -- registering a backend here is what enrolls
it in the suite.

Contracts (registered by ``models/attention.py`` at import)
-----------------------------------------------------------
decode backend::

    fn(q, ck, cv, n_valid, *, scale, policy, return_residuals=False)
      q:       (B, H, G, dh)  one query token per sequence (any float dtype)
      ck, cv:  (B, S, H, dh)  KV cache in its storage dtype
      n_valid: (B,) int32     valid cache slots per sequence
      -> out (B, H, G, dh) float, or with residuals (out, m, l) where
         m/l: (B, H, G) f32 running max / softmax sum (flash-attention
         partials; ``out`` is already normalized by ``l``).

    The ``paged`` base reinterprets the cache operands: ck/cv are the
    shared page pools (num_pages, page_size, H, dh), n_valid is per-slot
    sequence length, and a required keyword ``block_tables`` (B, n_pages)
    int32 maps logical pages to physical ones (-1 = unmapped/masked).

prefill backend::

    fn(qg, k, v, *, scale, policy, window, prefix_len, chunk, q_offset, fmt)
      qg:   (B, Sq, H, G, dh); k/v: (B, Skv, H, dh) float, or packed
      (e, m) containers when ``fmt`` is given (prefill-from-packed-cache).
      -> out (B, Sq, H, G, dh)

Wrappers apply to the decode path only; for prefill a composed spelling
resolves to its base backend (sequence-sharded prefill is an open item).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat

# ---------------------------------------------------------------------------
# spelling declarations (static: usable for validation before any backend
# module is imported)
# ---------------------------------------------------------------------------

BASE_IMPLS = ("xla", "flash_pallas", "paged")
WRAPPER_IMPLS = ("flash_shmap", "ring")
DEFAULT_INNER = "xla"  # a bare wrapper spelling means wrapper+xla

_DECODE: dict = {}
_PREFILL: dict = {}
_WRAPPERS: dict = {}


def legal_impls() -> tuple:
    """Every accepted ``decode_impl`` spelling."""
    composed = tuple(f"{w}+{b}" for w in WRAPPER_IMPLS for b in BASE_IMPLS)
    return BASE_IMPLS + WRAPPER_IMPLS + composed


def canonicalize_impl(spec: str) -> tuple:
    """``"flash_shmap"`` -> ``("flash_shmap", "xla")``; base -> ``(base,)``."""
    parts = tuple(p.strip() for p in str(spec).split("+"))
    if len(parts) == 1 and parts[0] in WRAPPER_IMPLS:
        parts = (parts[0], DEFAULT_INNER)
    return parts


def validate_impl(spec: Optional[str], *, allow_none: bool = True,
                  what: str = "decode_impl") -> Optional[str]:
    """Check a spelling against the registry; raise an actionable error.

    Returns ``spec`` unchanged so callers can validate in-line.
    """
    if spec is None:
        if allow_none:
            return None
        raise ValueError(f"{what} must be set; legal values: {legal_impls()}")
    # membership in the canonicalized legal set, not a structural check:
    # both wrappers consume the mesh's model axis, so multi-wrapper chains
    # ("flash_shmap+ring+xla") are meaningless and must be rejected too --
    # this also keeps legal_impls() and validation in lockstep, which is
    # what lets tests/test_conformance.py derive its sweep from the
    # registry alone
    parts = canonicalize_impl(spec)
    legal = {canonicalize_impl(s) for s in legal_impls()}
    if parts not in legal:
        raise ValueError(
            f"unknown {what} {spec!r}; legal spellings are "
            f"{list(legal_impls())} (one wrapper composes with one base, "
            f"e.g. 'flash_shmap+flash_pallas' = sequence-sharded fused "
            f"kernel, 'ring+paged' = page pool rotated around the mesh "
            f"ring)")
    return spec


def default_serving_impl() -> Optional[str]:
    """The serving default when no ``--decode-impl`` is given: the fused
    packed-KV path whenever a TPU backend is present (where the Pallas
    kernel is compiled, not interpreted), composed with sequence sharding
    when the ambient mesh has a model axis.  ``None`` (model-config
    default) elsewhere -- on CPU the XLA path is the honest baseline.

    The mesh probe uses :func:`compat.get_ambient_mesh`, which also sees a
    mesh activated by a classic ``with mesh:`` block (thread-local
    *physical* mesh) -- consulting only the abstract mesh silently dropped
    the ``flash_shmap`` composition for exactly that common TPU idiom."""
    if jax.default_backend() != "tpu":
        return None
    mesh = compat.get_ambient_mesh()
    if mesh is not None and "model" in (mesh.axis_names or ()):
        return "flash_shmap+flash_pallas"
    return "flash_pallas"


# ---------------------------------------------------------------------------
# matmul-backend registry (the weight side of decode bandwidth)
# ---------------------------------------------------------------------------
#
# Mirrors the attention registry above for the model's GEMMs: every
# parameter-consuming contraction in ``models/layers.py`` (``pdot`` /
# ``peinsum`` / ``pgrouped_dot``) resolves its implementation here.
#
#   "xla"         -- jnp.dot / jnp.einsum; packed (QTensor) weights are
#                    dequantized through XLA first (the oracle and the
#                    honest CPU baseline).
#   "qmm_pallas"  -- the fused transprecision GEMV/GEMM kernel
#                    (kernels/qmatmul.py): packed weight tiles stream from
#                    HBM as the grid's moving operand, are decoded
#                    in-register via the shared codec, multiplied with f32
#                    accumulation, with bias + nonlinearity + gate + output
#                    quantize fused into the epilogue.  Plain-array weights
#                    fall back to "xla" (only a packed store shrinks bytes).
#
# Spellings ride ``matmul_impl`` on PrecisionPolicy (serving-time override),
# ModelConfig, ShapeSpec, and the --matmul-impl CLI flags; all validate at
# construction time against ``legal_matmul_impls()``.

MATMUL_IMPLS = ("xla", "qmm_pallas")

_MATMUL: dict = {}


def legal_matmul_impls() -> tuple:
    """Every accepted ``matmul_impl`` spelling."""
    return MATMUL_IMPLS


def validate_matmul_impl(spec: Optional[str], *, allow_none: bool = True,
                         what: str = "matmul_impl") -> Optional[str]:
    """Check a matmul spelling; raise with the legal list (in-line usable)."""
    if spec is None:
        if allow_none:
            return None
        raise ValueError(
            f"{what} must be set; legal values: {legal_matmul_impls()}")
    if spec not in MATMUL_IMPLS:
        raise ValueError(
            f"unknown {what} {spec!r}; legal spellings are "
            f"{list(legal_matmul_impls())} ('qmm_pallas' streams packed "
            f"weights through the fused transprecision GEMV kernel)")
    return spec


def register_matmul(name: str) -> Callable:
    assert name in MATMUL_IMPLS, name

    def deco(backend):
        _MATMUL[name] = backend
        return backend
    return deco


def resolve_matmul(spec: Optional[str]):
    """Spelling -> matmul backend (an object with ``dot`` / ``einsum`` /
    ``grouped`` callables; contracts documented in ``models/layers.py``,
    which registers both backends at import)."""
    spec = validate_matmul_impl(spec, allow_none=False)
    return _MATMUL[spec]


# ---------------------------------------------------------------------------
# registration (decorators used by models/attention.py)
# ---------------------------------------------------------------------------

def register_decode(name: str) -> Callable:
    assert name in BASE_IMPLS, name

    def deco(fn):
        _DECODE[name] = fn
        return fn
    return deco


def register_prefill(name: str) -> Callable:
    assert name in BASE_IMPLS, name

    def deco(fn):
        _PREFILL[name] = fn
        return fn
    return deco


def register_wrapper(name: str) -> Callable:
    assert name in WRAPPER_IMPLS, name

    def deco(factory):
        _WRAPPERS[name] = factory
        return factory
    return deco


def resolve_decode(spec: str) -> Callable:
    """Spelling -> decode callable (wrappers applied left to right).

    Wrapper factories receive the *base* backend name alongside the inner
    callable: how a wrapper shards depends on the cache layout the base
    reads (sequence axis for contiguous bases, page axis for ``paged``).
    """
    parts = canonicalize_impl(validate_impl(spec, allow_none=False))
    fn = _DECODE[parts[-1]]
    for w in reversed(parts[:-1]):
        fn = _WRAPPERS[w](fn, base=parts[-1])
    return fn


def resolve_prefill(spec: str) -> Callable:
    """Spelling -> prefill callable (base backend of the composition)."""
    parts = canonicalize_impl(validate_impl(spec, allow_none=False))
    return _PREFILL[parts[-1]]


# ---------------------------------------------------------------------------
# the sharded wrappers: flash_shmap and ring share ALL of their gating (mesh
# probe, model-axis presence, storage-axis divisibility, inner fallback) and
# differ only in the sharded decode they dispatch to -- one factory keeps
# the two from ever disagreeing about *when* they shard
# ---------------------------------------------------------------------------

def _sharded_wrapper_factory(sharded: Callable, sharded_paged: Callable
                             ) -> Callable:
    """Build a wrapper factory around a (contiguous, paged) pair of sharded
    decode implementations.  The returned factory is what
    :func:`register_wrapper` stores; both registered wrappers come from
    here (see the registrations at the bottom of this module)."""

    def factory(inner: Callable, base: str = DEFAULT_INNER) -> Callable:
        if base == "paged":
            def wrapped(q, ck, cv, n_valid, *, scale, policy, block_tables,
                        return_residuals: bool = False):
                # ck/cv are the page pools; shard their *page* axis (axis 0)
                mesh = compat.get_ambient_mesh()
                usable = (not return_residuals
                          and mesh is not None
                          and "model" in (mesh.axis_names or ())
                          and ck.shape[0] % mesh.shape["model"] == 0)
                if not usable:
                    return inner(q, ck, cv, n_valid, scale=scale,
                                 policy=policy, block_tables=block_tables,
                                 return_residuals=return_residuals)
                return sharded_paged(inner, mesh, q, ck, cv, n_valid,
                                     block_tables, scale=scale,
                                     policy=policy)
            return wrapped

        def wrapped(q, ck, cv, n_valid, *, scale, policy,
                    return_residuals: bool = False):
            mesh = compat.get_ambient_mesh()
            usable = (not return_residuals
                      and mesh is not None
                      and "model" in (mesh.axis_names or ())
                      and ck.shape[1] % mesh.shape["model"] == 0)
            if not usable:
                # no mesh (single host / tests), indivisible cache, or
                # nested wrapping: run the inner backend unsharded
                return inner(q, ck, cv, n_valid, scale=scale, policy=policy,
                             return_residuals=return_residuals)
            return sharded(inner, mesh, q, ck, cv, n_valid, scale=scale,
                           policy=policy)

        return wrapped

    return factory


def _batch_pspec(mesh, batch: int):
    """Partition entry for the batch axis: the mesh's data axes when they
    divide the batch, else replicated."""
    dp = tuple(a for a in mesh.axis_names if a != "model")
    n_dp = max(int(np.prod([mesh.shape[a] for a in dp])), 1)
    return dp if batch % n_dp == 0 else None


def _merge_partials(o, m, l):
    """Exact flash-attention merge of normalized per-shard partials over
    the ``model`` axis: with w_i = exp(m_i - max_j m_j) * l_i the exact
    softmax output is sum_i w_i o_i / sum_i w_i (empty shards have
    l_i = 0).  One definition shared by every sharded wrapper branch so
    the numerics can never diverge between cache layouts."""
    o = o.astype(jnp.float32)
    gm = jax.lax.pmax(m, "model")
    w = jnp.exp(m - gm) * l
    num = jax.lax.psum(o * w[..., None], "model")
    den = jax.lax.psum(w, "model")
    # explicit zero guard (a subnormal epsilon would be FTZ-flushed)
    den = jnp.where(den > 0, den, 1.0)[..., None]
    return num / den


def _shmap_decode(inner, mesh, q, ck, cv, n_valid, *, scale, policy):
    """The genuinely sharded branch of the flash_shmap wrapper (module-level
    so tests can assert it was taken, not silently skipped by the mesh
    fallback)."""
    from jax.sharding import PartitionSpec as P

    n_model = mesh.shape["model"]
    s_loc = ck.shape[1] // n_model
    bspec = _batch_pspec(mesh, q.shape[0])

    def local(q_b, k_b, v_b, nv_b):
        # shard i owns cache slots [i*s_loc, (i+1)*s_loc): its local
        # valid count under the global per-sequence prefix length
        idx = jax.lax.axis_index("model")
        local_n = jnp.clip(nv_b - idx * s_loc, 0, s_loc)
        o, m, l = inner(q_b, k_b, v_b, local_n, scale=scale,
                        policy=policy, return_residuals=True)
        return _merge_partials(o, m, l)

    return compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None, None),
                  P(bspec, "model", None, None),
                  P(bspec, "model", None, None),
                  P(bspec)),
        out_specs=P(bspec, None, None, None),
        # pallas_call has no replication rule; the collectives above
        # make the output replicated by construction
        check_vma=False,
    )(q, ck, cv, n_valid)


def _shmap_decode_paged(inner, mesh, q, ck, cv, n_valid, block_tables, *,
                        scale, policy):
    """Pool-sharded paged decode: device ``i`` holds physical pages
    [i*p_loc, (i+1)*p_loc) of the K/V pools and rewrites the (replicated)
    block table so entries it owns become pool-local ids and every other
    entry is -1 (masked by the kernel).  Every token lives on exactly one
    device, so the per-shard flash partials merge with the same
    max/sum-correction collectives as the contiguous case."""
    from jax.sharding import PartitionSpec as P

    n_model = mesh.shape["model"]
    p_loc = ck.shape[0] // n_model
    bspec = _batch_pspec(mesh, q.shape[0])

    def local(q_b, kp_l, vp_l, nv_b, tbl_b):
        idx = jax.lax.axis_index("model")
        first = idx * p_loc
        owned = (tbl_b >= first) & (tbl_b < first + p_loc)
        ltbl = jnp.where(owned, tbl_b - first, -1)
        o, m, l = inner(q_b, kp_l, vp_l, nv_b, scale=scale, policy=policy,
                        block_tables=ltbl, return_residuals=True)
        return _merge_partials(o, m, l)

    return compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None, None),
                  P("model", None, None, None),   # pool page axis
                  P("model", None, None, None),
                  P(bspec),
                  P(bspec, None)),                # tables replicated/model
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(q, ck, cv, n_valid, block_tables)


# ---------------------------------------------------------------------------
# the ring wrapper: rotate K/V shards around the mesh ring (neighbor-only
# ppermute) and fold each incoming shard into the running online-softmax
# state -- peak per-device live KV is one shard, no all-to-one collective
# ---------------------------------------------------------------------------

def _ring_fold(acc, m_run, l_run, o, m, l):
    """Fold one shard's *normalized* flash partials (o, m, l) into the
    running (acc, m, l) online-softmax state.

    ``o * l`` recovers the shard's unnormalized weighted-V sum, so this is
    the standard flash-attention combine: rescale both sides to the new
    running max and add.  The fold is associative and commutative up to
    f32 rounding -- any rotation order yields the same softmax (pinned by
    a hypothesis property in tests/test_properties.py), which is what
    makes the neighbor-only ring schedule exact.  An empty shard arrives
    as (0, NEG_INF, 0) -- the backends' shared finite sentinel -- and
    folds to a no-op.
    """
    m_new = jnp.maximum(m_run, m)
    a_run = jnp.exp(m_run - m_new)
    a_in = jnp.exp(m - m_new)
    acc = (acc * a_run[..., None]
           + o.astype(jnp.float32) * (l * a_in)[..., None])
    return acc, m_new, l_run * a_run + l * a_in


def _ring_finalize(acc, l_run):
    """(acc, l) -> normalized output with an explicit zero guard (a
    subnormal epsilon would be FTZ-flushed on XLA CPU and divide 0/0)."""
    pos = l_run > 0
    den = jnp.where(pos, l_run, 1.0)[..., None]
    return jnp.where(pos[..., None], acc / den, 0.0)


def _ring_state(q_b):
    """Fresh per-device (acc, m, l) running state for ``q_b``'s queries.

    The running max starts at the SAME finite sentinel the backends
    return as ``m`` for an empty shard (``flash_attention.NEG_INF``, a
    lazy import so validation-only users of this module never pull in
    Pallas): exp(m - m_new) stays well-defined and an empty shard folds
    to an exact no-op.  A diverging private sentinel here would give
    empty shards a nonzero weight."""
    from .flash_attention import NEG_INF
    return (jnp.zeros(q_b.shape, jnp.float32),
            jnp.full(q_b.shape[:-1], NEG_INF, jnp.float32),
            jnp.zeros(q_b.shape[:-1], jnp.float32))


def _ring_decode(inner, mesh, q, ck, cv, n_valid, *, scale, policy):
    """Ring-rotated decode over a contiguous cache's sequence axis.

    Device ``i`` starts with cache slots [i*s_loc, (i+1)*s_loc); at step
    ``s`` it holds the shard originally owned by device ``(i - s) % n``
    (``ppermute`` shifts shards one hop per step), attends its (replicated)
    queries over it with the shard owner's local valid count, folds the
    partials into the running state, then passes the shard on.  After
    n_model steps every device has folded every shard exactly once, so the
    output is replicated by construction -- no merge collective at all,
    and the only communication is the neighbor-only rotation.
    """
    from jax.sharding import PartitionSpec as P

    n_model = mesh.shape["model"]
    s_loc = ck.shape[1] // n_model
    bspec = _batch_pspec(mesh, q.shape[0])
    perm = [(i, (i + 1) % n_model) for i in range(n_model)]

    def local(q_b, k_b, v_b, nv_b):
        idx = jax.lax.axis_index("model")
        acc, m_run, l_run = _ring_state(q_b)
        k_cur, v_cur = k_b, v_b
        for step in range(n_model):
            owner = (idx - step) % n_model
            local_n = jnp.clip(nv_b - owner * s_loc, 0, s_loc)
            o, m, l = inner(q_b, k_cur, v_cur, local_n, scale=scale,
                            policy=policy, return_residuals=True)
            acc, m_run, l_run = _ring_fold(acc, m_run, l_run, o, m, l)
            if step != n_model - 1:  # the last shard is not passed on
                k_cur = jax.lax.ppermute(k_cur, "model", perm)
                v_cur = jax.lax.ppermute(v_cur, "model", perm)
        return _ring_finalize(acc, l_run)

    return compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None, None),
                  P(bspec, "model", None, None),
                  P(bspec, "model", None, None),
                  P(bspec)),
        out_specs=P(bspec, None, None, None),
        # pallas_call has no replication rule; after n_model folds the
        # output is replicated by construction
        check_vma=False,
    )(q, ck, cv, n_valid)


def _ring_decode_paged(inner, mesh, q, ck, cv, n_valid, block_tables, *,
                       scale, policy):
    """Ring-rotated paged decode: the pool's page axis is sharded and the
    pool shards rotate; the block table stays replicated, and at each step
    every device rewrites it to the *rotating owner's* pool-local page ids
    (entries the current shard does not hold become -1, masked by the
    kernel).  Every token is folded exactly once over the full rotation --
    same exactness argument as the contiguous ring."""
    from jax.sharding import PartitionSpec as P

    n_model = mesh.shape["model"]
    p_loc = ck.shape[0] // n_model
    bspec = _batch_pspec(mesh, q.shape[0])
    perm = [(i, (i + 1) % n_model) for i in range(n_model)]

    def local(q_b, kp_l, vp_l, nv_b, tbl_b):
        idx = jax.lax.axis_index("model")
        acc, m_run, l_run = _ring_state(q_b)
        k_cur, v_cur = kp_l, vp_l
        for step in range(n_model):
            owner = (idx - step) % n_model
            first = owner * p_loc
            owned = (tbl_b >= first) & (tbl_b < first + p_loc)
            ltbl = jnp.where(owned, tbl_b - first, -1)
            o, m, l = inner(q_b, k_cur, v_cur, nv_b, scale=scale,
                            policy=policy, block_tables=ltbl,
                            return_residuals=True)
            acc, m_run, l_run = _ring_fold(acc, m_run, l_run, o, m, l)
            if step != n_model - 1:
                k_cur = jax.lax.ppermute(k_cur, "model", perm)
                v_cur = jax.lax.ppermute(v_cur, "model", perm)
        return _ring_finalize(acc, l_run)

    return compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None, None),
                  P("model", None, None, None),   # pool page axis
                  P("model", None, None, None),
                  P(bspec),
                  P(bspec, None)),                # tables replicated
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(q, ck, cv, n_valid, block_tables)


# ---------------------------------------------------------------------------
# wrapper registrations: one shared factory, two merge topologies.  The
# lambdas keep the module globals LATE-bound, so tests can monkeypatch the
# sharded branch (test_perf_variants spies on _shmap_decode to prove the
# wrapper genuinely sharded instead of silently taking the mesh fallback).
# ---------------------------------------------------------------------------

register_wrapper("flash_shmap")(_sharded_wrapper_factory(
    lambda *a, **k: _shmap_decode(*a, **k),
    lambda *a, **k: _shmap_decode_paged(*a, **k)))
register_wrapper("ring")(_sharded_wrapper_factory(
    lambda *a, **k: _ring_decode(*a, **k),
    lambda *a, **k: _ring_decode_paged(*a, **k)))
