"""Paged (block-table) packed-KV backend tests: behavior specific to the
page-pool layout.

The cross-backend oracle pins (every ``paged``-base spelling vs the XLA
dequantize reference, all formats, ragged lengths, shuffled non-contiguous
pages, 1-/2-device meshes) live in ``tests/test_conformance.py``; this
file keeps what the generic sweep cannot express -- page reuse after
free/realloc (stale bytes must be invisible, including under pool
sharding), the device cache ops, the host allocator's bookkeeping, and
the model/serve-level PagedKVCache wiring.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_child
from repro.core.formats import PAPER_FORMATS
from repro.core.policy import binary32_policy, transprecision_policy
from repro.core.qtensor import encode
from repro.kernels import dispatch, paged_cache
from repro.kernels.flash_attention import flash_decode_reference
from repro.kernels.paged_attention import (paged_decode,
                                           paged_decode_reference,
                                           paged_hbm_bytes)
from repro.models import attention as att
from repro.models.base import ModelConfig


def _scatter_to_pool(payload, tables, num_pages, page):
    """Contiguous per-sequence payload (B, S, H, dh) -> pool via tables."""
    c = np.asarray(payload)
    pool = np.zeros((num_pages, page) + c.shape[2:], dtype=c.dtype)
    B, n_pages = tables.shape
    for b in range(B):
        for p in range(n_pages):
            t = tables[b, p]
            if t >= 0:
                pool[t] = c[b, p * page:(p + 1) * page]
    return jnp.asarray(pool)


def _mk(B=3, S=80, H=2, G=4, dh=32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, H, G, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    return q, k, v


# ---------------------------------------------- kernel-specific behavior
# (the ragged + shuffled-non-contiguous-pages oracle pin moved to
# tests/test_conformance.py::test_conformance_noncontiguous_pages, which
# runs it for every paged-base spelling)

def test_paged_reference_matches_contiguous_oracle():
    """paged_decode_reference == the contiguous dequantize oracle on the
    gathered view: the paged reference introduces no math of its own, so
    the conformance suite may pin everything to the one contiguous
    oracle."""
    fmt = PAPER_FORMATS[0]
    page, n_pages, num_pages = 16, 5, 20
    B, S = 3, n_pages * page
    q, k, v = _mk(B=B, S=S)
    lengths = jnp.asarray([80, 7, 53], jnp.int32)
    tables = np.asarray([[2, 7, 11, 3, 19], [5, -1, -1, -1, -1],
                         [8, 0, 14, 9, -1]], np.int32)
    kp, vp = encode(k, fmt), encode(v, fmt)
    kpool = _scatter_to_pool(kp, tables, num_pages, page)
    vpool = _scatter_to_pool(vp, tables, num_pages, page)
    tj = jnp.asarray(tables)
    ref = paged_decode_reference(q, kpool, vpool, fmt, lengths, tj)
    want = flash_decode_reference(q, kp, vp, fmt, lengths)
    assert float(np.abs(np.asarray(ref) - np.asarray(want)).max()) <= 1e-6


def test_paged_decode_residuals_match_plain():
    fmt = PAPER_FORMATS[0]
    page, n_pages = 16, 3
    B, S = 2, n_pages * page
    q, k, v = _mk(B=B, S=S)
    kp, vp = encode(k, fmt), encode(v, fmt)
    tables = np.asarray([[2, 0, 4], [5, 1, -1]], np.int32)
    kpool = _scatter_to_pool(kp, tables, 6, page)
    vpool = _scatter_to_pool(vp, tables, 6, page)
    lengths = jnp.asarray([48, 20], jnp.int32)
    tj = jnp.asarray(tables)
    o = paged_decode(q, kpool, vpool, fmt, lengths, tj)
    o2, m, l = paged_decode(q, kpool, vpool, fmt, lengths, tj,
                            return_residuals=True)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o2))
    _, mr, lr = paged_decode_reference(q, kpool, vpool, fmt, lengths, tj,
                                       return_residuals=True)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(l), np.asarray(lr), rtol=1e-6)


def test_paged_decode_page_reuse_after_free_realloc():
    """Free a sequence, hand its physical pages to a different sequence,
    and decode: the stale payload bytes must be invisible (no pool
    zeroing happens on free -- masking and overwrite are the guarantee)."""
    fmt = PAPER_FORMATS[0]
    page, n_pages, num_pages = 8, 4, 6
    B, S = 1, n_pages * page
    q, k0, v0 = _mk(B=B, S=S, seed=2)
    _, k1, v1 = _mk(B=B, S=S, seed=3)

    pool = paged_cache.PagePool(num_pages, page, n_slots=1, pages_per_seq=4)
    assert pool.allocate(0, 29)
    first_pages = list(pool.owned[0])
    cache = paged_cache.init_paged_cache(1, num_pages, page, n_pages, 2, 32,
                                         jnp.float8_e5m2)
    cache = paged_cache.set_block_tables(cache, pool.tables)
    bc = lambda x: jax.lax.bitcast_convert_type(x, jnp.float8_e5m2)  # noqa
    cache = paged_cache.write_prefill(
        cache, 0, bc(encode(k0, fmt)[0, :29]), bc(encode(v0, fmt)[0, :29]))
    # free, then realloc for a different sequence: same physical pages LIFO
    pool.free_slot(0)
    assert pool.allocate(0, 21)
    assert set(pool.owned[0]) <= set(first_pages)  # pages really reused
    cache = paged_cache.set_block_tables(cache, pool.tables)
    cache = paged_cache.write_prefill(
        cache, 0, bc(encode(k1, fmt)[0, :21]), bc(encode(v1, fmt)[0, :21]))

    lengths = jnp.asarray([21], jnp.int32)
    kp1, vp1 = encode(k1, fmt), encode(v1, fmt)
    got = paged_decode(
        q, jax.lax.bitcast_convert_type(cache.k_pool, jnp.uint8),
        jax.lax.bitcast_convert_type(cache.v_pool, jnp.uint8),
        fmt, lengths, cache.block_tables)
    want = flash_decode_reference(q, kp1, vp1, fmt, lengths)
    assert float(np.abs(np.asarray(got) - np.asarray(want)).max()) <= 1e-6


def test_paged_hbm_bytes_counts_whole_pages():
    b = paged_hbm_bytes(2, [65, 1], 2, 64, PAPER_FORMATS[0], page_size=64,
                        g=1)
    # 3 pages (2 + 1) x 64 tok x 2 heads x 64 dh x 1 B x {K, V} + tables + q
    assert b == 2 * 3 * 64 * 2 * 64 + 3 * 4 + 2 * 2 * 64 * 4


# ------------------------------------------------------- device cache ops

def test_append_decode_skips_unmapped_slots():
    cache = paged_cache.init_paged_cache(2, 4, 8, 2, 1, 8, jnp.float32)
    pool = paged_cache.PagePool(4, 8, n_slots=2, pages_per_seq=2)
    assert pool.allocate(0, 3)  # slot 1 left unmapped
    cache = paged_cache.set_block_tables(cache, pool.tables)
    cache = cache._replace(seq_lens=jnp.asarray([3, 0], jnp.int32))
    k = jnp.ones((2, 1, 1, 8), jnp.float32)
    cache = paged_cache.append_decode(cache, k, k)
    np.testing.assert_array_equal(np.asarray(cache.seq_lens), [4, 0])
    # the mapped slot's token landed at page 0 (physical tables[0,0]) off 3
    phys = int(np.asarray(cache.block_tables)[0, 0])
    assert float(cache.k_pool[phys, 3, 0, 0]) == 1.0
    # release: table unmapped, lens zeroed, next append is a no-op
    cache = paged_cache.release_slot(cache, 0)
    cache = paged_cache.append_decode(cache, k, k)
    np.testing.assert_array_equal(np.asarray(cache.seq_lens), [0, 0])


def _bits(x):
    """An array's raw bits (float8/16/32 pools compare bit for bit)."""
    n = np.dtype(x.dtype).itemsize * 8
    return np.asarray(jax.lax.bitcast_convert_type(
        x, getattr(jnp, f"uint{n}")))


@pytest.mark.parametrize("chunk", [5, 7, 8, 12])
@pytest.mark.parametrize("fmt", PAPER_FORMATS, ids=lambda f: f.name)
def test_write_chunk_bit_identical_to_write_prefill(fmt, chunk):
    """Page-granular chunked prefill writes: scattering a 20-token prompt
    in chunks of 5 (< page, straddles a page boundary mid-chunk), 7
    (ragged final chunk), 8 (== page) and 12 (> page) must leave the pool
    bytes and seq_lens bit-identical to the one-shot whole-prompt
    write_prefill, for every paper format."""
    rng = np.random.default_rng(0)
    S, page, pages_per_seq, num_pages = 20, 8, 3, 7
    H, dh = 2, 16
    kf = jnp.asarray(rng.normal(size=(1, S, H, dh)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(1, S, H, dh)), jnp.float32)
    k = jax.lax.bitcast_convert_type(encode(kf, fmt), fmt.native_dtype)[0]
    v = jax.lax.bitcast_convert_type(encode(vf, fmt), fmt.native_dtype)[0]

    pool = paged_cache.PagePool(num_pages, page, 1, pages_per_seq)
    assert pool.allocate(0, S)
    cache = paged_cache.init_paged_cache(1, num_pages, page, pages_per_seq,
                                         H, dh, fmt.native_dtype)
    cache = paged_cache.set_block_tables(cache, pool.tables)

    whole = paged_cache.write_prefill(cache, 0, k, v)
    chunked = cache
    for off in range(0, S, chunk):
        c = min(chunk, S - off)
        chunked = paged_cache.write_chunk(chunked, 0, k[off:off + c],
                                          v[off:off + c], off)

    np.testing.assert_array_equal(_bits(chunked.k_pool), _bits(whole.k_pool))
    np.testing.assert_array_equal(_bits(chunked.v_pool), _bits(whole.v_pool))
    np.testing.assert_array_equal(np.asarray(chunked.seq_lens),
                                  np.asarray(whole.seq_lens))
    assert int(chunked.seq_lens[0]) == S


def test_write_chunk_respects_table_mask_and_capacity():
    """Chunk positions past the slot's mapped pages (or past capacity) are
    dropped and do not advance seq_lens -- the same drop-mode contract
    append_decode obeys."""
    page, pages_per_seq, num_pages = 8, 2, 4
    pool = paged_cache.PagePool(num_pages, page, 1, pages_per_seq)
    assert pool.allocate(0, 8)  # one mapped page only
    cache = paged_cache.init_paged_cache(1, num_pages, page, pages_per_seq,
                                         1, 8, jnp.float32)
    cache = paged_cache.set_block_tables(cache, pool.tables)
    k = jnp.ones((12, 1, 8), jnp.float32)
    out = paged_cache.write_chunk(cache, 0, k, k, 0)
    assert int(out.seq_lens[0]) == 8  # tokens 8..11 hit an unmapped page
    # an explicit length override (streamed-transport handoff publishes
    # the final length after page copies)
    out = paged_cache.set_seq_len(out, 0, 5)
    assert int(out.seq_lens[0]) == 5


def _assert_caches_identical(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        if w is None:
            assert g is None
            continue
        for a, b in zip(g, w):
            np.testing.assert_array_equal(_bits(a), _bits(b))


def _stale_pools(rng, n_layers, fmt, *, num_pages, page, per_seq, n_slots,
                 H, dh, pool):
    """Per-layer caches whose pools hold random stale bytes, behind the
    allocator's tables."""
    caches = []
    for _ in range(n_layers):
        c = paged_cache.init_paged_cache(n_slots, num_pages, page, per_seq,
                                         H, dh, fmt.native_dtype)
        junk = [jax.lax.bitcast_convert_type(
            encode(jnp.asarray(rng.normal(size=c.k_pool.shape),
                               jnp.float32), fmt), fmt.native_dtype)
            for _ in range(2)]
        caches.append(paged_cache.set_block_tables(
            c._replace(k_pool=junk[0], v_pool=junk[1]), pool.tables))
    return caches


def _prompt_kv(rng, n_layers, S, H, dh):
    return [jnp.asarray(rng.normal(size=(1, S, H, dh)), jnp.float32)
            for _ in range(2 * n_layers)]


WRITE_FORMATS = [f for f in PAPER_FORMATS if f.name != "binary32"]


@pytest.mark.parametrize("S,mapped,length", [
    (13, 13, 13),   # ragged last page: rows 13..15 keep their stale bytes
    (20, 12, 16),   # tail past the mapped pages: tokens 16..19 dropped
    (28, 24, 24),   # past capacity (3 pages of 8): tokens 24..27 dropped
], ids=["ragged", "unmapped-tail", "over-capacity"])
@pytest.mark.parametrize("fmt", WRITE_FORMATS, ids=lambda f: f.name)
def test_write_prefill_layers_bit_identical_to_write_prefill(fmt, S, mapped,
                                                             length):
    """The fused all-layer write, jitted with the slot traced and the pools
    donated (as the prefill worker runs it), leaves every layer's pools,
    tables and lengths bit-identical to the eager per-layer write_prefill,
    in slot 2, into pages reused from a freed slot that hold stale
    bytes."""
    rng = np.random.default_rng(S)
    page, per_seq, num_pages, n_slots, H, dh, L = 8, 3, 7, 3, 2, 16, 3
    pool = paged_cache.PagePool(num_pages, page, n_slots, per_seq)
    assert pool.allocate(0, 24) and pool.allocate(1, 8)
    pool.free_slot(0)
    assert pool.allocate(2, mapped)
    caches = _stale_pools(rng, L, fmt, num_pages=num_pages, page=page,
                          per_seq=per_seq, n_slots=n_slots, H=H, dh=dh,
                          pool=pool)
    kv = _prompt_kv(rng, L, S, H, dh)
    ks, vs = kv[:L], kv[L:]
    want = [paged_cache.write_prefill(c, 2, k[0], v[0])
            for c, k, v in zip(caches, ks, vs)]
    want = jax.tree.map(np.asarray, want)

    def fused(k_pools, v_pools, rest, slot):
        cs = [paged_cache.PagedKVCache(k, v, *r)
              for k, v, r in zip(k_pools, v_pools, rest)]
        return paged_cache.write_prefill_layers(
            cs, slot, [k[0] for k in ks], [v[0] for v in vs])

    got = jax.jit(fused, donate_argnums=(0, 1))(
        [c.k_pool for c in caches], [c.v_pool for c in caches],
        [(c.block_tables, c.seq_lens) for c in caches], jnp.int32(2))
    _assert_caches_identical(got, want)
    assert [int(c.seq_lens[2]) for c in got] == [length] * L


class _Transport:
    params = None

    def to_prefill(self, tree):
        return tree


@pytest.fixture(scope="module")
def mixed_worker():
    """The prefill worker over recurrentgemma's rglru/attention pattern."""
    from repro.engine.stats import EngineStats
    from repro.engine.worker import PrefillWorker
    from repro.models.registry import build
    model, cfg = build("recurrentgemma-2b", reduced=True)
    assert {"attn"} < set(cfg.attn_pattern)
    pol = binary32_policy()
    params = model.init_params(jax.random.PRNGKey(0), pol)
    tr = _Transport()
    tr.params = params
    return cfg, PrefillWorker(model, cfg, pol, tr, EngineStats(),
                              chunk_tokens=0)


def test_prefill_worker_writes_only_attention_layers(mixed_worker):
    """A whole-prompt step writes the attention layers' pools exactly as
    the eager per-layer loop did, hands the recurrent layers' states to
    the task, leaves the recurrent layers' view entries alone, and counts
    one fused call; a second slot at the same prompt length reuses the
    compiled writer."""
    from repro.engine.worker import PrefillTask, make_batch
    cfg, worker = mixed_worker
    rng = np.random.default_rng(2)
    page, per_seq, n_slots, S = 8, 4, 3, 13
    pool = paged_cache.PagePool(n_slots * per_seq, page, n_slots, per_seq)
    assert pool.allocate(1, S) and pool.allocate(2, S)
    attn = [li for li, k in enumerate(cfg.attn_pattern) if k == "attn"]
    assert len(attn) < cfg.n_layers

    def view():
        return [paged_cache.set_block_tables(paged_cache.init_paged_cache(
            n_slots, n_slots * per_seq, page, per_seq, cfg.n_kv,
            cfg.head_dim, jnp.float32), pool.tables) if li in attn else None
            for li in range(cfg.n_layers)]

    request = types.SimpleNamespace(
        prompt=rng.integers(0, cfg.vocab, S).tolist())
    _, one, _ = worker._whole(worker.transport.params,
                              make_batch(cfg, request))
    for slot in (1, 2):
        states = view()
        want = [None if s is None else paged_cache.write_prefill(
            s, slot, one[li].k[0], one[li].v[0])
            for li, s in enumerate(states)]
        want = jax.tree.map(np.asarray, want)
        task = PrefillTask(request, slot, S)
        task.pstates = [None] * cfg.n_layers
        got = worker.step(task, view(), slot)
        _assert_caches_identical(got, want)
        for li, kind in enumerate(cfg.attn_pattern):
            if kind == "attn":
                assert task.pstates[li] is None
            else:
                assert got[li] is None and task.pstates[li] is not None
        assert task.done and task.offset == S
    assert worker.stats.pool_write_calls == 2
    assert worker._write_pools._cache_size() == 1


def test_pool_writer_aliases_every_pool(mixed_worker):
    """The compiled writer updates each K and V pool in place: every pool
    parameter is aliased to its own layer's output pool (a donated buffer
    paired with another output, or not reused, is copied instead)."""
    import re
    cfg, worker = mixed_worker
    attn = [li for li, k in enumerate(cfg.attn_pattern) if k == "attn"]
    c = paged_cache.init_paged_cache(2, 4, 8, 2, cfg.n_kv, cfg.head_dim,
                                     jnp.float8_e5m2)
    kv = jnp.zeros((1, 12, cfg.n_kv, cfg.head_dim), jnp.float32)
    n = len(attn)
    text = worker._write_pools.lower(
        [c.k_pool] * n, [c.v_pool] * n, [c.block_tables] * n,
        [c.seq_lens] * n, 1, [kv] * n, [kv] * n).compile().as_text()
    header = next(ln for ln in text.splitlines() if ln.startswith("HloModule"))
    pairs = {(int(o), int(p)) for o, p in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, may-alias\)", header)}
    assert pairs == {(i, i) for i in range(2 * n)}


def test_init_paged_cache_distinct_pools():
    """K and V are two buffers: a program donating both pools cannot be
    handed one buffer twice."""
    c = paged_cache.init_paged_cache(2, 4, 8, 2, 1, 8, jnp.float8_e5m2)
    assert c.k_pool is not c.v_pool
    assert (c.k_pool.unsafe_buffer_pointer()
            != c.v_pool.unsafe_buffer_pointer())


def test_validate_page_size():
    paged_cache.validate_page_size(8)
    paged_cache.validate_page_size(64)
    for bad in (0, -8, 12, 7):
        with pytest.raises(ValueError):
            paged_cache.validate_page_size(bad)


# --------------------------------------------------------- host allocator

def test_page_pool_alloc_free_reuse_and_stats():
    pool = paged_cache.PagePool(num_pages=6, page_size=8, n_slots=3,
                                pages_per_seq=3)
    assert pool.can_admit(17) and not pool.can_admit(25)  # 3 > pages_per_seq
    assert pool.allocate(0, 17)           # 3 pages
    assert pool.allocate(1, 9)            # 2 pages
    assert pool.pages_used == 5 and pool.occupancy() == 5 / 6
    # internal fragmentation: 5 pages * 8 slots hold 26 tokens
    assert abs(pool.internal_fragmentation() - (1 - 26 / 40)) < 1e-9
    assert not pool.allocate(2, 9)        # only 1 page free
    assert pool.can_admit(8)
    # growth within the table, then table exhaustion
    assert pool.ensure_capacity(1, 16)    # still 2 pages
    assert pool.ensure_capacity(1, 17)    # grows to 3
    assert not pool.ensure_capacity(1, 25)   # table full -> caller evicts
    freed = pool.free_slot(0)
    assert freed == 3 and pool.pages_used == 3
    np.testing.assert_array_equal(pool.tables[0], [-1, -1, -1])
    # LIFO reuse: the realloc gets recently-freed physical pages
    assert pool.allocate(2, 24)
    assert pool.peak_pages_used == 6
    st = pool.stats()
    assert st["pages_used"] == 6 and st["occupancy"] == 1.0


def test_pool_fragmentation_analytic():
    assert paged_cache.pool_fragmentation([64, 64], 64) == 0.0
    assert abs(paged_cache.pool_fragmentation([65, 1], 64)
               - (1 - 66 / 192)) < 1e-9
    assert paged_cache.pool_fragmentation([], 64) == 0.0


# ----------------------------------------------------- model-level wiring

def _cfg(**kw):
    base = dict(arch="t", family="dense", n_layers=1, d_model=64, n_heads=4,
                n_kv=2, d_ff=128, vocab=64)
    base.update(kw)
    return ModelConfig(**base)


def test_mha_contiguous_cache_through_paged_view_matches_xla():
    """decode_impl='paged' over an ordinary KVCache (identity block table)
    == the XLA path: paging is invisible in the math."""
    cfg_x = _cfg(decode_impl="xla")
    cfg_p = _cfg(decode_impl="paged")
    pol = binary32_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg_x, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64),
                          jnp.float32) * 0.5
    _, cache_x = att.prefill_to_cache(p, x, cfg_x, pol, capacity=32)
    cache_p = cache_x
    for step in range(3):
        xt = jax.random.normal(jax.random.PRNGKey(10 + step), (2, 1, 64),
                               jnp.float32) * 0.5
        o_x, cache_x = att.mha(p, xt, cfg_x, pol, cache=cache_x)
        o_p, cache_p = att.mha(p, xt, cfg_p, pol, cache=cache_p)
        np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_p),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"step {step}")
        np.testing.assert_array_equal(np.asarray(cache_x.k),
                                      np.asarray(cache_p.k))


def test_mha_paged_cache_decode_matches_contiguous():
    """Full PagedKVCache decode (write_prefill + per-step table growth +
    append) tracks the contiguous XLA decode, packed binary8 storage."""
    pol = binary32_policy(kv_fmt="binary8")
    cfg_x = _cfg(decode_impl="xla")
    cfg_p = _cfg(decode_impl="paged")
    p = att.attn_init(jax.random.PRNGKey(0), cfg_x, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64),
                          jnp.float32) * 0.5
    _, ccache = att.prefill_to_cache(p, x, cfg_x, pol, capacity=32)

    page, pages_per_seq, num_pages = 8, 4, 12
    pool = paged_cache.PagePool(num_pages, page, 2, pages_per_seq)
    pcache = paged_cache.init_paged_cache(2, num_pages, page, pages_per_seq,
                                          cfg_x.n_kv, cfg_x.head_dim,
                                          pol.dtype("kv_cache"))
    for s in range(2):
        assert pool.allocate(s, 12)
    pcache = paged_cache.set_block_tables(pcache, pool.tables)
    for s in range(2):
        pcache = paged_cache.write_prefill(pcache, s, ccache.k[s, :12],
                                           ccache.v[s, :12])
    np.testing.assert_array_equal(np.asarray(pcache.seq_lens), [12, 12])
    for step in range(5):
        xt = jax.random.normal(jax.random.PRNGKey(10 + step), (2, 1, 64),
                               jnp.float32) * 0.5
        for s in range(2):
            assert pool.ensure_capacity(s, 13 + step)
        pcache = paged_cache.set_block_tables(pcache, pool.tables)
        o_x, ccache = att.mha(p, xt, cfg_x, pol, cache=ccache)
        o_p, pcache = att.mha(p, xt, cfg_p, pol, cache=pcache)
        # binary8 probs-cast asymmetry (xla narrows materialized probs,
        # kernels keep f32) bounds this at ~1e-3, same as flash_pallas
        np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_p),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"step {step}")
        np.testing.assert_array_equal(np.asarray(pcache.seq_lens),
                                      [13 + step] * 2)


def test_mha_paged_view_clamps_overflowing_token_count():
    """Decode past a *full* non-window contiguous cache: the running token
    count exceeds capacity, and the paged view's page-granule zero padding
    must not count as valid (regression: unclamped lengths let padded
    slots dilute the softmax)."""
    cfg_x = _cfg(decode_impl="xla")
    cfg_p = _cfg(decode_impl="paged")
    pol = binary32_policy()
    p = att.attn_init(jax.random.PRNGKey(0), cfg_x, jnp.float32)
    # capacity 12 is NOT a page multiple -> the view pads to 16 slots
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64),
                          jnp.float32) * 0.5
    _, cache_x = att.prefill_to_cache(p, x, cfg_x, pol, capacity=12)
    cache_p = cache_x
    for step in range(3):  # pos 12..14 > capacity: cache stays full
        xt = jax.random.normal(jax.random.PRNGKey(20 + step), (2, 1, 64),
                               jnp.float32) * 0.5
        o_x, cache_x = att.mha(p, xt, cfg_x, pol, cache=cache_x)
        o_p, cache_p = att.mha(p, xt, cfg_p, pol, cache=cache_p)
        np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_p),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"step {step}")


def test_mha_contiguous_impl_reads_paged_cache_via_gather_bridge():
    """A contiguous spelling (xla) decoding over a PagedKVCache gathers the
    pool through the block tables and must match the native paged path --
    the bridge that lets every registry spelling serve out of one page
    pool (the engine's unified code path)."""
    pol = binary32_policy()
    cfg_x = _cfg(decode_impl="xla")
    cfg_p = _cfg(decode_impl="paged")
    p = att.attn_init(jax.random.PRNGKey(0), cfg_x, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64),
                          jnp.float32) * 0.5
    _, ccache = att.prefill_to_cache(p, x, cfg_x, pol, capacity=32)

    page, pages_per_seq, num_pages = 8, 4, 12
    pool = paged_cache.PagePool(num_pages, page, 2, pages_per_seq)
    pcache = paged_cache.init_paged_cache(2, num_pages, page, pages_per_seq,
                                          cfg_x.n_kv, cfg_x.head_dim,
                                          pol.dtype("kv_cache"))
    for s in range(2):
        assert pool.allocate(s, 12)
    pcache = paged_cache.set_block_tables(pcache, pool.tables)
    for s in range(2):
        pcache = paged_cache.write_prefill(pcache, s, ccache.k[s, :12],
                                           ccache.v[s, :12])
    pcache_x = pcache
    for step in range(3):
        xt = jax.random.normal(jax.random.PRNGKey(10 + step), (2, 1, 64),
                               jnp.float32) * 0.5
        for s in range(2):
            assert pool.ensure_capacity(s, 13 + step)
        pcache = paged_cache.set_block_tables(pcache, pool.tables)
        pcache_x = paged_cache.set_block_tables(pcache_x, pool.tables)
        o_p, pcache = att.mha(p, xt, cfg_p, pol, cache=pcache)
        o_x, pcache_x = att.mha(p, xt, cfg_x, pol, cache=pcache_x)
        np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_p),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"step {step}")
        np.testing.assert_array_equal(np.asarray(pcache.seq_lens),
                                      np.asarray(pcache_x.seq_lens))


def test_decode_paged_requires_block_tables():
    q, k, v = _mk(B=2, S=16)
    fn = dispatch.resolve_decode("paged")
    with pytest.raises(ValueError) as ei:
        fn(q, k, v, jnp.asarray([16, 16], jnp.int32), scale=0.25,
           policy=binary32_policy())
    assert "block_tables" in str(ei.value)


def test_paged_shape_spec_pinned():
    from repro.configs.shapes import ALL_SHAPES
    assert ALL_SHAPES["decode_32k_paged"].decode_impl == "paged"


# ------------------- page reuse under pool sharding (2-device subprocess)
# (the full pool-sharded format/ragged oracle sweep moved to
# tests/test_conformance.py; what stays here is the page-reuse semantics
# under sharding -- a freed page re-mapped onto the OTHER shard, with its
# stale bytes still sitting in the pool -- for both merge topologies)

_SHARDED_PAGED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.core.formats import PAPER_FORMATS
from repro.core.policy import transprecision_policy
from repro.core.qtensor import encode
from repro.kernels import dispatch
from repro.kernels.paged_attention import paged_decode_reference
import repro.models.attention as att  # registers the backends

mesh = compat.make_mesh((2,), ("model",))
rng = np.random.default_rng(0)
B, H, G, dh = 3, 2, 4, 32
page, n_pages, num_pages = 16, 5, 20   # pool page axis: 20 % 2 == 0
S = n_pages * page
q = jnp.asarray(rng.normal(size=(B, H, G, dh)), jnp.float32)
k = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
v = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
lengths = jnp.asarray([80, 7, 53], jnp.int32)
perm = iter(rng.permutation(num_pages).tolist())
tables = np.full((B, n_pages), -1, np.int32)
for b, need in enumerate([5, 1, 4]):
    for p in range(need):
        tables[b, p] = next(perm)
scale = float(1.0 / np.sqrt(dh))

def scatter(payload):
    c = np.asarray(payload)
    pool = np.zeros((num_pages, page) + c.shape[2:], dtype=c.dtype)
    for b in range(B):
        for p in range(n_pages):
            if tables[b, p] >= 0:
                pool[tables[b, p]] = c[b, p*page:(p+1)*page]
    return pool

# free row 1's page, realloc it on the OTHER shard (boundary: p_loc = 10)
# and write fresh payload; the stale bytes of the old page stay in the
# pool and must be invisible under both merge topologies -- page reuse is
# masking + overwrite, never pool zeroing
tables2 = tables.copy()
old = tables2[1, 0]
free = sorted(set(range(num_pages)) - set(tables2[tables2 >= 0].tolist()))
other = [p for p in free if (p < 10) != (old < 10)][0]
tables2[1, 0] = other
fmt = PAPER_FORMATS[0]
kp, vp = encode(k, fmt), encode(v, fmt)
pol = transprecision_policy(kv_fmt=fmt)
kpool, vpool = scatter(kp), scatter(vp)
kpool[other] = np.asarray(kp)[1, :page]
vpool[other] = np.asarray(vp)[1, :page]
ck = jax.lax.bitcast_convert_type(jnp.asarray(kpool), fmt.native_dtype)
cv = jax.lax.bitcast_convert_type(jnp.asarray(vpool), fmt.native_dtype)
tj = jnp.asarray(tables2)
want = paged_decode_reference(q, jnp.asarray(kpool), jnp.asarray(vpool),
                              fmt, lengths, tj, scale=scale)
for impl in ("flash_shmap+paged", "ring+paged"):
    fn = dispatch.resolve_decode(impl)
    with compat.use_mesh(mesh):
        got = jax.jit(lambda q, a, b, n, t: fn(
            q, a, b, n, scale=scale, policy=pol,
            block_tables=t))(q, ck, cv, lengths, tj)
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    assert err <= 1e-6, (impl, "realloc", err)
print("SHARDED_PAGED_OK")
"""


def test_page_reuse_under_pool_sharding_subprocess():
    run_child(_SHARDED_PAGED, "SHARDED_PAGED_OK", timeout=480)


@pytest.mark.parametrize("wrapper", ["flash_shmap", "ring"])
def test_wrapped_paged_falls_back_without_mesh(wrapper):
    """wrapper+paged outside any mesh == plain paged."""
    fmt = PAPER_FORMATS[0]
    page, n_pages = 16, 3
    B, S = 2, n_pages * page
    q, k, v = _mk(B=B, S=S)
    pol = transprecision_policy(kv_fmt=fmt)
    kp, vp = encode(k, fmt), encode(v, fmt)
    tables = np.asarray([[2, 0, 4], [5, 1, 3]], np.int32)
    kpool = _scatter_to_pool(kp, tables, 6, page)
    vpool = _scatter_to_pool(vp, tables, 6, page)
    ck = jax.lax.bitcast_convert_type(kpool, fmt.native_dtype)
    cv = jax.lax.bitcast_convert_type(vpool, fmt.native_dtype)
    nv = jnp.asarray([48, 31], jnp.int32)
    tj = jnp.asarray(tables)
    composed = dispatch.resolve_decode(f"{wrapper}+paged")
    plain = dispatch.resolve_decode("paged")
    a = composed(q, ck, cv, nv, scale=0.25, policy=pol, block_tables=tj)
    b = plain(q, ck, cv, nv, scale=0.25, policy=pol, block_tables=tj)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
