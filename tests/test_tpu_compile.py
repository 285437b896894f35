"""Compile every serving Pallas kernel for a described TPU v5e, at real widths.

Interpret mode (how every other test runs the kernels) accepts block
shapes and casts that the TPU's own compiler refuses, so a kernel can pass
the whole conformance suite and still fail on the first chip run.  The TPU
compiler is installed even where no chip is attached: these tests lower
each kernel with ``interpret=False`` against a described v5e topology and
require the compiled program to hold the Mosaic kernel
(``tpu_custom_call``).  Nothing runs, so they say nothing about results;
``tests/test_conformance.py`` pins those.

Widths come from the published configs the engine serves: head_dim 64
(granite-moe-1b-a400m) and 128 (yi-9b, mistral-nemo-12b), d_model 1024 and
4096, the granite expert width 512 and the yi-9b FFN width 11008, in each
packed container (binary8 -> uint8, binary16alt -> uint16, binary32 ->
uint32).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and under a multi-worker
pytest run every worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import BINARY8, BINARY16ALT, BINARY32
from repro.kernels.flash_attention import flash_decode, flash_prefill
from repro.kernels.paged_attention import paged_decode
from repro.kernels.qmatmul import qmatmul, qmm_ffn


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a described-topology compile is written to the persistent cache but
    # cannot be read back without a chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_for_chip(fn, sharding, *shapes):
    """Lower + compile ``fn`` on (shape, dtype) pairs for the described chip
    and return the compiled HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


# (fmt, head_dim, G): granite (kv=8, G=2, dh=64) in binary8 -- the serving
# default -- and the 128-lane head of yi-9b / mistral-nemo (G=4)
ATTN_CASES = [
    pytest.param(BINARY8, 64, 2, id="binary8-dh64"),
    pytest.param(BINARY16ALT, 64, 2, id="binary16alt-dh64"),
    pytest.param(BINARY8, 128, 4, id="binary8-dh128"),
    pytest.param(BINARY32, 128, 4, id="binary32-dh128"),
]


@pytest.mark.parametrize("fmt,dh,G", ATTN_CASES)
def test_flash_decode_compiles(one_chip, fmt, dh, G):
    B, S, H = 2, 512, 8
    ct = fmt.container_dtype
    fn = functools.partial(flash_decode, fmt=fmt, interpret=False)
    _compile_for_chip(
        lambda q, k, v, n: fn(q, k, v, lengths=n), one_chip,
        ((B, H, G, dh), jnp.float32), ((B, S, H, dh), ct),
        ((B, S, H, dh), ct), ((B,), jnp.int32))


@pytest.mark.parametrize("residuals", [False, True],
                         ids=["plain", "residuals"])
@pytest.mark.parametrize("fmt,dh,G", ATTN_CASES)
def test_paged_decode_compiles(one_chip, fmt, dh, G, residuals):
    """``residuals`` is the form the flash_shmap/ring wrappers call."""
    B, pages, page, H, per_seq = 2, 8, 128, 8, 4
    ct = fmt.container_dtype
    fn = functools.partial(paged_decode, fmt=fmt, interpret=False,
                           return_residuals=residuals)
    _compile_for_chip(
        lambda q, k, v, n, t: fn(q, k, v, lengths=n, block_tables=t),
        one_chip,
        ((B, H, G, dh), jnp.float32), ((pages, page, H, dh), ct),
        ((pages, page, H, dh), ct), ((B,), jnp.int32),
        ((B, per_seq), jnp.int32))


@pytest.mark.parametrize("fmt,dh,G", ATTN_CASES + [
    pytest.param(None, 64, 2, id="float-dh64")])
def test_flash_prefill_compiles(one_chip, fmt, dh, G):
    """A 128-token chunk at offset 128 against a 512-slot packed cache
    (the engine's chunked prefill), and the float-K/V fresh-prefill form."""
    Sq, Skv, H = 128, 512, 8
    ct = jnp.float32 if fmt is None else fmt.container_dtype
    fn = functools.partial(flash_prefill, fmt=fmt, q_offset=128,
                           interpret=False)
    _compile_for_chip(fn, one_chip, ((1, Sq, H, G, dh), jnp.float32),
                      ((1, Skv, H, dh), ct), ((1, Skv, H, dh), ct))


@pytest.mark.parametrize("fmt,a_shape,b_shape", [
    pytest.param(BINARY16ALT, (8, 1024), (1024, 512), id="granite-expert-in"),
    pytest.param(BINARY16ALT, (8, 512), (512, 1024), id="granite-expert-out"),
    pytest.param(BINARY16ALT, (32, 8, 1024), (32, 1024, 512),
                 id="granite-experts-grouped"),
    pytest.param(BINARY16ALT, (32, 40, 1024), (32, 1024, 512),
                 id="granite-experts-grouped-prefill"),
    pytest.param(BINARY32, (8, 1024), (1024, 32), id="granite-router-binary32"),
    pytest.param(BINARY16ALT, (2, 1024), (1024, 49155), id="granite-lm-head"),
    pytest.param(BINARY8, (8, 4096), (4096, 11008), id="yi-ffn-binary8"),
])
def test_qmatmul_compiles(one_chip, fmt, a_shape, b_shape):
    """GEMV (M <= 32) and square-tiled (M = 40) blocks; the grouped form
    is the MoE layer's one kernel over all 32 experts."""
    fn = functools.partial(qmatmul, fmt_a=None, fmt_b=fmt, interpret=False)
    _compile_for_chip(fn, one_chip, (a_shape, jnp.float32),
                      (b_shape, fmt.container_dtype))


@pytest.mark.parametrize("fmt", [BINARY8, BINARY16ALT, BINARY32],
                         ids=lambda f: f.name)
def test_qmm_ffn_compiles(one_chip, fmt):
    """The fused gated-FFN pair at the yi-9b width (d 4096 -> ff 11008)."""
    fn = functools.partial(qmm_ffn, fmt_w=fmt, interpret=False)
    ct = fmt.container_dtype
    _compile_for_chip(fn, one_chip, ((8, 4096), jnp.float32),
                      ((4096, 11008), ct), ((4096, 11008), ct))


def test_scopes_name_the_kernel_and_change_nothing_else(one_chip,
                                                        monkeypatch):
    """The reduced granite decode step (binary8 pool, gather bridge,
    ``flash_decode``) compiled for the chip with and without the
    ``jax.named_scope`` scopes: the scopes reach the operations' metadata
    and give the Mosaic call's instruction its scope's name; nothing else
    in the program changes."""
    import contextlib
    import dataclasses
    import re

    from repro import configs
    from repro.engine.worker import DecodeWorker
    from repro.kernels import paged_cache
    from repro.models.registry import build_from_config
    from repro.tuning.artifact import load_policy

    cfg = configs.get("granite-moe-1b-a400m", reduced=True)
    pol = dataclasses.replace(load_policy("transprecision"),
                              decode_impl="flash_pallas")
    model = build_from_config(cfg)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, page, per_seq = 4, 128, 2

    def pools():
        return [paged_cache.init_paged_cache(
            slots, slots * per_seq, page, per_seq, cfg.n_kv, cfg.head_dim,
            pol.dtype("kv_cache", layer=li)) for li in range(cfg.n_layers)]

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    args = on_chip((
        jax.eval_shape(lambda k: model.init_params(k, pol),
                       jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32), jax.eval_shape(pools),
        jax.ShapeDtypeStruct((slots,), jnp.bool_)))

    def program():
        text = DecodeWorker(model, pol)._step.lower(*args).compile() \
            .as_text()
        assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
        body = text[min(text.find(k) for k in ("\n%", "\nENTRY")
                        if k in text):]
        return text, re.sub(r", metadata=\{[^}]*\}", "", body)

    scoped, scoped_body = program()
    ops = set(re.findall(r'op_name="([^"]*)"', scoped))
    for scope in ("kv_append", "gather_pages", "attn_kernel", "ffn",
                  "lm_head"):
        assert any(f"/{scope}/" in o for o in ops), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, plain_body = program()
    kernels = re.findall(r"%attn_kernel\.\d+ = ", scoped_body)
    assert len(kernels) == cfg.n_layers
    assert re.sub(r"%attn_kernel\.", "%_step.", scoped_body) == plain_body


@pytest.mark.parametrize("n_kv,dh", [
    pytest.param(8, 64, id="granite-kv8-dh64"),
    pytest.param(4, 128, id="yi-kv4-dh128"),
])
def test_pool_writer_updates_pools_in_place(one_chip, n_kv, dh):
    """The whole-prompt pool write at the chat and yi cells' size (24
    layers, 512 binary8 pages of 128 rows, a 1024-token prompt) compiled
    for the chip: every K and V pool is aliased to its own output, no
    pool-sized copy is left in the program, and its temporaries stay
    under one pool's bytes.  The chip lays these two pool shapes out
    differently, and a scatter of rows or of pages relayouts the pools of
    one of them through whole-pool copies."""
    import math
    import re
    import types

    from repro.engine.worker import PrefillWorker

    cfg = types.SimpleNamespace(attn_pattern=("attn",) * 24, prefix_len=0,
                                encoder_layers=0)
    worker = PrefillWorker(None, cfg, None, None, None, chunk_tokens=0)
    L, f8 = len(worker._attn), jnp.float8_e5m2
    pool = (512, 128, n_kv, dh)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = worker._write_pools.lower(
        [on_chip(pool, f8)] * L, [on_chip(pool, f8)] * L,
        [on_chip((32, 16), jnp.int32)] * L, [on_chip((32,), jnp.int32)] * L,
        on_chip((), jnp.int32), [on_chip((1, 1024, n_kv, dh), f8)] * L,
        [on_chip((1, 1024, n_kv, dh), f8)] * L).compile()
    text = compiled.as_text()
    pairs = {(int(o), int(p)) for o, p in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, may-alias\)", text.split("\n", 1)[0])}
    assert pairs == {(i, i) for i in range(2 * L)}
    shape = ",".join(map(str, pool))
    assert not re.search(rf"= f8e5m2\[{shape}\]\S* copy", text)
    assert compiled.memory_analysis().temp_size_in_bytes < math.prod(pool)
