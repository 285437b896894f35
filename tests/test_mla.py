"""Latent attention (MLA), the expert share and the latent paged pool
(DeepSeek-V2-Lite), at reduced size on the CPU.

* absorbed decode over the cached latent rows computes the same attention
  as the un-absorbed formulation over the whole sequence;
* the Pallas latent decode (interpret mode) matches its XLA oracle on a
  paged, packed binary8 pool with ragged lengths and unmapped pages;
* the expert shares of a layer, with the shared experts counted once, add
  up to the uncut layer, and each share holds the uncut model's weights;
* parameter counts of the published model and of the chip's share;
* the engine serves the latent pool (whole-prompt prefill, then decode)
  token for token like the single-request loop, and refuses what a latent
  pool does not support.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.policy import get_policy
from repro.engine import Engine, Request
from repro.engine.reference import synchronous_generate
from repro.engine.transport import StreamedTransport
from repro.kernels.latent_attention import (latent_decode,
                                            latent_decode_reference)
from repro.models import attention as attn
from repro.models import moe
from repro.models.layers import ffn_apply
from repro.models.registry import build, build_from_config


@pytest.fixture(scope="module")
def reduced():
    return build("deepseek-v2-lite", reduced=True)


def test_absorbed_decode_matches_unabsorbed_attention(reduced):
    """Prefill S tokens into a contiguous latent cache, decode token S+1
    absorbed (q~ = [W_UK^T q_nope, q_pe] against [c, k_pe], out = W_UV
    sum p c); the un-absorbed attention over all S+1 tokens gives the
    same last row.  binary32 throughout (f32 latent cache): the two
    associations differ by f32 summation order only, ~1e-6 relative."""
    _, cfg = reduced
    pol = get_policy("binary32")
    p = attn.mla_init(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 13, cfg.d_model))
    _, cache = attn.mla(p, x[:, :12], cfg, pol, cache_capacity=16)
    got, new = attn.mla(p, x[:, 12:], cfg, pol, cache=cache)
    want, _ = attn.mla(p, x, cfg, pol)
    assert int(new.pos) == 13
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(want[:, 12]), rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_blocked_prefill_matches_one_block(reduced, monkeypatch):
    """Prefill attention in blocks of 3 queries (the last one ragged)
    gives every row the single block gives (binary32: summation order
    only)."""
    _, cfg = reduced
    pol = get_policy("binary32")
    p = attn.mla_init(jax.random.PRNGKey(5), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 13, cfg.d_model))
    want, _ = attn.mla(p, x, cfg, pol)
    monkeypatch.setattr(attn, "MLA_Q_BLOCK", 3)
    got, _ = attn.mla(p, x, cfg, pol)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5 * float(
                                   jnp.abs(want).max()))


@pytest.mark.parametrize("fmt", ["binary8", "binary32"])
def test_latent_decode_kernel_matches_oracle(fmt):
    """Ragged lengths (one sequence empty, one ending mid-page, one whole),
    non-contiguous pages, a masked (-1) row: the kernel and the gather
    oracle agree to f32 summation order."""
    from repro.core.formats import get_format
    from repro.core.qtensor import QTensor
    B, H, W, page, n_pages, num = 3, 4, 128, 8, 4, 12
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((num, page, W)).astype(np.float32)
    f = get_format(fmt)
    if f.is_binary32:
        pool, f = jnp.asarray(rows), None
    else:
        pool = QTensor.quantize(jnp.asarray(rows), f).payload
    q = jnp.asarray(rng.standard_normal((B, H, W)), jnp.float32)
    tables = jnp.asarray([[5, 2, 9, -1], [1, 7, 3, 11], [-1] * 4],
                         jnp.int32)
    lengths = jnp.asarray([21, 32, 6], jnp.int32)
    kw = dict(scale=0.3, v_dim=96)
    got = latent_decode(q, pool, f, lengths, tables, **kw)
    want = latent_decode_reference(q, pool, f, lengths, tables, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[2]).any()


@pytest.mark.parametrize("S,bq,bkv", [(13, 8, 8), (40, 16, 8), (37, 8, 16),
                                      (64, 64, 64)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_prefill_kernel_matches_blocked(S, bq, bkv, dtype):
    """The Pallas prefill (tiles above the diagonal skipped, ragged last
    blocks padded) against the XLA blocked form in f32, at DeepSeek's
    widths (q/k 192 = 128 + 64, v 128).  f32 operands: summation order
    only.  bf16 operands: the kernel's probabilities enter the value
    product in bf16 (relative error 2^-9 each, a convex mix of values),
    so the rows agree within 2^-8 of the largest value."""
    from repro.kernels.latent_attention import latent_prefill
    B, H, dk, dv = 2, 3, 192, 128
    ks = jax.random.split(jax.random.PRNGKey(S), 3)
    q, k = (jax.random.normal(kk, (B, S, H, dk), jnp.float32)
            for kk in ks[:2])
    v = jax.random.normal(ks[2], (B, S, H, dv), jnp.float32)
    dt = jnp.dtype(dtype)
    q, k, v = (x.astype(dt).astype(jnp.float32) for x in (q, k, v))
    want = attn._latent_prefill_xla(q, k, v, scale=0.07,
                                    policy=get_policy("binary32"))
    got = latent_prefill(q.astype(dt), k.astype(dt), v.astype(dt),
                         scale=0.07, block_q=bq, block_kv=bkv)
    assert got.shape == (B, S, H, dv) and got.dtype == jnp.float32
    tol = 2e-5 if dtype == "float32" else 2.0 ** -8
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=tol * float(jnp.abs(v).max()))


def test_expert_shares_sum_to_the_uncut_layer(reduced):
    """8 shares of 2 of the 16 routed experts: each share's init is the
    uncut stack's slice, and the shares' routed parts plus the shared
    experts once equal the uncut layer's output (binary32: summation
    order only)."""
    _, cfg = reduced
    pol = get_policy("binary32")
    full = dataclasses.replace(cfg, moe_experts_held=None,
                               moe_expert_offset=0)
    assert full.moe_experts_held == 16
    key = jax.random.PRNGKey(7)
    p_full = moe.moe_init(key, full, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 9, cfg.d_model))
    want, _, counts = moe.moe_apply(p_full, x, full, pol)
    xt = x.reshape(-1, cfg.d_model)
    _, _, top_p, top_e = moe.route(p_full, xt, full, pol)
    total = ffn_apply(p_full["shared"], xt, pol, full)
    routed = hit = 0
    for i in range(8):
        share = dataclasses.replace(cfg, moe_expert_offset=2 * i,
                                    moe_experts_held=2)
        p = moe.moe_init(key, share, jnp.float32)
        for w in ("w_in", "w_gate", "w_out"):
            np.testing.assert_array_equal(
                np.asarray(p[w]), np.asarray(p_full[w][2 * i:2 * i + 2]))
        y, c = moe.experts(p, xt, top_p, top_e, share, pol, counts=True)
        total = total + y
        routed, hit = routed + int(c[0]), hit + int(c[1])
    np.testing.assert_allclose(np.asarray(total).reshape(want.shape),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    assert routed == int(counts[0]) == xt.shape[0] * cfg.moe_topk
    assert hit == int(counts[1])


def test_dropless_share_drops_nothing(reduced):
    """Every token routed to one held expert (the worst case for a
    capacity): the share computes all of them."""
    _, cfg = reduced
    pol = get_policy("binary32")
    p = moe.moe_init(jax.random.PRNGKey(1), cfg, jnp.float32)
    xt = jax.random.normal(jax.random.PRNGKey(2), (10, cfg.d_model))
    top_e = jnp.tile(jnp.asarray([[0, 1, 9]], jnp.int32), (10, 1))
    top_p = jnp.full((10, 3), 0.25, jnp.float32)
    y, c = moe.experts(p, xt, top_p, top_e, cfg, pol, counts=True)
    want = sum(0.25 * ffn_apply({k: p[k][e] for k in ("w_in", "w_gate",
                                                        "w_out")},
                                xt, pol, cfg) for e in (0, 1))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert [int(v) for v in c] == [20, 2]


@pytest.mark.parametrize("tokens", [5, 6])
def test_long_prompts_pass_the_experts_in_chunks(reduced, monkeypatch,
                                                 tokens):
    """A prompt longer than ``EXPERT_TOKENS`` passes the expert layer a
    chunk at a time (with a shorter last chunk when it does not divide):
    the same rows as one pass, and the same counters -- an expert hit in
    two chunks counts once."""
    _, cfg = reduced
    pol = get_policy("binary32")
    p = moe.moe_init(jax.random.PRNGKey(3), cfg, jnp.float32)
    xt = jax.random.normal(jax.random.PRNGKey(4), (30, cfg.d_model))
    _, _, top_p, top_e = moe.route(p, xt, cfg, pol)
    want, c_want = moe.experts(p, xt, top_p, top_e, cfg, pol, counts=True)
    monkeypatch.setattr(moe, "EXPERT_TOKENS", tokens)
    got, c_got = moe.experts(p, xt, top_p, top_e, cfg, pol, counts=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert [int(v) for v in c_got] == [int(v) for v in c_want]
    assert int(c_want[1]) <= cfg.moe_experts_held


def test_parameter_counts():
    """Counted from the published shapes: 15.71 B parameters, 2.45 B of
    them active per token (the model card: 15.7 B / 2.4 B), 3.11 B in the
    8-expert share one chip holds; the reduced config's count is its
    init's."""
    cfg = configs.get("deepseek-v2-lite")
    assert round(cfg.param_count(experts=64) / 1e9, 2) == 15.71
    assert round(cfg.active_param_count() / 1e9, 2) == 2.45
    assert round(cfg.param_count() / 1e9, 2) == 3.11
    model, small = build("deepseek-v2-lite", reduced=True)
    params = jax.eval_shape(lambda k: model.init_params(
        k, get_policy("binary32")), jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        params)) == small.param_count()


def test_latent_cache_bytes():
    """One 576-value latent row per token and layer, stored padded to
    640 lanes: 27 x 640 B of binary8 against granite's 24 x 2 x 8 x 64."""
    pol = get_policy("transprecision")
    for arch, want in (("deepseek-v2-lite", 27 * 640),
                       ("granite-moe-1b-a400m", 24 * 2 * 8 * 64)):
        model, cfg = build(arch)
        assert sum(model.cache_spec(li).bytes_per_token(
            pol.dtype("kv_cache")) for li in range(cfg.n_layers)) == want


@pytest.mark.parametrize("impl", ["xla", "flash_pallas"])
def test_engine_serves_the_latent_pool(reduced, impl):
    """Whole-prompt prefill into the paged latent pool, then decode
    through it (both registry paths: the XLA oracle and the Pallas
    kernel), over more requests than slots: under binary32 every token
    equals the single-request loop's (contiguous latent cache, prefill
    then absorbed decode).  The MoE counters reach the summary."""
    model, cfg = reduced
    pol = get_policy("binary32", decode_impl=impl)
    params = model.init_params(jax.random.PRNGKey(0), pol)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (12, 20, 7)]
    eng = Engine(model, cfg, pol, params, slots=2, capacity=48,
                 page_size=8, prefill_chunk=0)
    reqs = eng.run([Request(i, p, 10) for i, p in enumerate(prompts)])
    ref = synchronous_generate(model, cfg, pol, params, prompts,
                               max_new=10, capacity=48)
    assert [r.generated for r in reqs] == ref
    assert eng.kv_bytes_per_token == 3 * 128 * 4
    assert eng.states[0].v_pool is None
    assert eng.summary["moe_routed_here"] > 0
    assert eng.summary["moe_experts_hit"] > 0


@pytest.mark.parametrize("arch", ["deepseek-v2-lite",
                                  "granite-moe-1b-a400m"])
def test_engine_with_a_donated_decode_state(arch):
    """The decode step that donates its state (the TPU's default): the
    pools it was given are deleted, the ones it returns are served from,
    prefills into them keep landing, and every token equals the
    single-request loop's."""
    from repro.engine.worker import DecodeWorker
    model, cfg = build(arch, reduced=True)
    pol = get_policy("binary32", decode_impl="xla")
    params = model.init_params(jax.random.PRNGKey(0), pol)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (12, 20, 7)]
    eng = Engine(model, cfg, pol, params, slots=2, capacity=48,
                 page_size=8, prefill_chunk=0)
    eng.decode_worker = DecodeWorker(model, pol, donate=True)
    given = []
    step = eng.decode_worker.step

    def keep(params, tokens, states, nan_mask):
        given.append(states[0].k_pool)
        return step(params, tokens, states, nan_mask)

    eng.decode_worker.step = keep
    reqs = eng.run([Request(i, p, 10) for i, p in enumerate(prompts)])
    ref = synchronous_generate(model, cfg, pol, params, prompts,
                               max_new=10, capacity=48)
    assert [r.generated for r in reqs] == ref
    assert given and all(k.is_deleted() for k in given)


def test_latent_pool_refusals(reduced):
    model, cfg = reduced
    pol = get_policy("binary32")
    params = model.init_params(jax.random.PRNGKey(0), pol)
    kw = dict(slots=2, capacity=48, page_size=8)
    with pytest.raises(ValueError, match="chunked prefill"):
        Engine(model, cfg, pol, params, **kw)
    with pytest.raises(ValueError, match="chunked prefill"):
        Engine(model, cfg, pol, params, prefill_chunk=8, **kw)
    with pytest.raises(ValueError, match="speculative decoding"):
        Engine(model, cfg, pol, params, prefill_chunk=0,
               speculative=object(), **kw)
    with pytest.raises(ValueError, match="streamed page transport"):
        Engine(model, cfg, pol, params, prefill_chunk=0,
               transport=StreamedTransport(), **kw)
    cache = attn.init_cache(cfg, 1, 16, pol)[0]
    x = jnp.zeros((1, 4, cfg.d_model))
    with pytest.raises(ValueError, match="whole-prompt prefill"):
        attn.prefill_from_cache(params["layers"][0]["mix"], x, cfg, pol,
                                cache, 0)


def test_granite_keeps_its_renormalised_top_k():
    """Granite holds all 32 experts and renormalises its top-8; DeepSeek
    keeps the softmax weights as they come."""
    g = configs.get("granite-moe-1b-a400m")
    d = configs.get("deepseek-v2-lite")
    assert g.moe_experts_held == g.moe_experts == 32 and g.moe_norm_topk
    assert not d.moe_norm_topk and d.moe_experts_held == 8
    cfg = configs.get("granite-moe-1b-a400m", reduced=True)
    pol = get_policy("binary32")
    p = moe.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    xt = jax.random.normal(jax.random.PRNGKey(1), (5, cfg.d_model))
    _, _, top_p, _ = moe.route(p, xt, cfg, pol)
    np.testing.assert_allclose(np.asarray(top_p.sum(-1)), 1.0, rtol=1e-6)
    _, _, top_p, _ = moe.route(p, xt, dataclasses.replace(
        cfg, moe_norm_topk=False), pol)
    assert float(top_p.sum(-1).max()) < 1.0


def test_yarn_frequencies():
    """DeepSeek-V2's YaRN at factor 40 over 4096 positions and 64 rope
    dims: the fastest dims keep theta^(-2i/d), the slowest are divided by
    the factor; the softmax scale is 192^-1/2 x 1.2608^2 and cos/sin keep
    factor 1."""
    from repro.models.layers import rope_freqs
    cfg = configs.get("deepseek-v2-lite")
    f = rope_freqs(64, 10_000.0, cfg.yarn)
    base = rope_freqs(64, 10_000.0)
    np.testing.assert_allclose(f[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(f[-4:], base[-4:] / 40.0, rtol=1e-6)
    assert np.all(np.diff(f) < 0)
    assert cfg.yarn.rope_mscale == 1.0
    np.testing.assert_allclose(float(attn.mla_scale(cfg)),
                               192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1)
                               ** 2, rtol=1e-6)


def test_model_without_shared_experts_or_share():
    """The expert layer with every expert held and no shared experts is
    the plain dropless MoE: a 1-layer reduced granite through the
    engine's whole-prompt path matches the single-request loop."""
    cfg = dataclasses.replace(configs.get("granite-moe-1b-a400m",
                                          reduced=True), n_layers=1,
                              attn_pattern=("attn",))
    model = build_from_config(cfg)
    pol = get_policy("binary32")
    params = model.init_params(jax.random.PRNGKey(2), pol)
    assert "shared" not in params["layers"][0]["ffn"]
    prompts = [[3, 5, 7, 9, 11], [2, 4, 6]]
    eng = Engine(model, cfg, pol, params, slots=2, capacity=32,
                 page_size=8, prefill_chunk=0)
    reqs = eng.run([Request(i, p, 6) for i, p in enumerate(prompts)])
    assert [r.generated for r in reqs] == synchronous_generate(
        model, cfg, pol, params, prompts, max_new=6, capacity=32)
    # every row of every decode step routes its top-k, idle slots too
    assert eng.summary["moe_routed_here"] == \
        (5 + 3 + 2 * eng.stats.target_steps) * cfg.moe_topk
