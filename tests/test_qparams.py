"""The packed-weight serving substrate: parameter store, matmul-backend
registry, and the altitude guard that keeps every model layer on it.

Oracle convention: "the XLA dequantize path" is ``matmul_impl="xla"`` over
the SAME packed store -- both backends consume identical (e, m) payload
bits, so any divergence is kernel error, pinned at <= 1e-6 in units of the
dot's absolute-value accumulation (kernel and oracle round identical
products; only the f32 summation tree differs).
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.shapes import ALL_SHAPES
from repro.core.formats import BINARY8, BINARY16ALT, PAPER_FORMATS
from repro.core.policy import (MATMUL_IMPLS, PrecisionPolicy, get_policy,
                               transprecision_policy)
from repro.core.qtensor import QTensor
from repro.kernels import dispatch
from repro.models import qparams
from repro.models.layers import ffn_apply, pdot, peinsum, pgrouped_dot
from repro.models.registry import build

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")

MODES = ("native", "emulated")


def _policy_pair(mode, fmt):
    """(xla, qmm) policies with every weight role stored in ``fmt``."""
    roles = {r: fmt for r in ("embed_w", "attn_w", "ffn_w", "router_w")}
    return (PrecisionPolicy(formats=roles, mode=mode, matmul_impl="xla"),
            PrecisionPolicy(formats=roles, mode=mode,
                            matmul_impl="qmm_pallas"))


def _close(got, want, scale):
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert (err <= 1e-6 * scale).all(), np.max(err / scale)


# ------------------------------------------------------------- packed store

def test_encode_params_packs_exactly_the_matmul_weights():
    model, cfg = build("llama3-8b", reduced=True)
    policy = transprecision_policy()
    params = model.init_params(jax.random.PRNGKey(0), policy)
    packed = qparams.encode_params(params, policy)
    layer = packed["layers"][0]
    for name in ("wq", "wk", "wv", "wo"):
        assert isinstance(layer["mix"][name], QTensor)
        assert layer["mix"][name].fmt == policy.fmt("attn_w")
    for name in ("w_in", "w_gate", "w_out"):
        assert isinstance(layer["ffn"][name], QTensor)
    assert isinstance(packed["head"], QTensor)
    assert packed["head"].fmt == policy.fmt("embed_w")
    # the embedding TABLE is consumed by gather, never packed; norms stay
    assert not isinstance(packed["embed"], QTensor)
    assert not isinstance(packed["final_norm"]["gamma"], QTensor)


def test_native_mode_packing_is_lossless():
    """In native mode a weight leaf already holds exact members of its
    role's format: the payload must be the bitcast of the native dtype and
    dequantize must reproduce the values bit-for-bit."""
    model, cfg = build("llama3-8b", reduced=True)
    policy = transprecision_policy(mode="native")
    params = model.init_params(jax.random.PRNGKey(1), policy)
    packed = qparams.encode_params(params, policy)
    w = params["layers"][0]["ffn"]["w_in"]          # bfloat16
    qt = packed["layers"][0]["ffn"]["w_in"]
    np.testing.assert_array_equal(
        np.asarray(qt.payload),
        np.asarray(QTensor.from_native(w).payload))
    np.testing.assert_array_equal(np.asarray(qt.dequantize()),
                                  np.asarray(w, np.float32))


def test_decode_params_round_trip_and_bytes():
    model, cfg = build("llama3-8b", reduced=True)
    policy = transprecision_policy(mode="native")
    params = model.init_params(jax.random.PRNGKey(2), policy)
    packed = qparams.encode_params(params, policy)
    dec = qparams.decode_params(packed)
    np.testing.assert_array_equal(
        np.asarray(dec["layers"][0]["mix"]["wq"]),
        np.asarray(params["layers"][0]["mix"]["wq"], np.float32))
    assert qparams.packed_bytes(packed) <= qparams.packed_bytes(params) \
        + 4  # u16 containers == bf16 leaves in native mode
    assert "packed weight store" in qparams.describe_packing(params, packed)


def test_packed_store_emulated_f32_shrinks_by_container_ratio():
    """Emulated-mode params are f32; packing ffn_w to binary8 must cut
    those leaves 4x (the paper's byte win on the weight stream)."""
    model, cfg = build("llama3-8b", reduced=True)
    policy = transprecision_policy(mode="emulated", matmul_impl="qmm_pallas")
    params = model.init_params(jax.random.PRNGKey(3), policy)
    w = params["layers"][0]["ffn"]["w_in"]
    assert w.dtype == jnp.float32
    packed = qparams.encode_params(params, policy.with_overrides(
        ffn_w=BINARY8))
    qt = packed["layers"][0]["ffn"]["w_in"]
    assert qt.payload.dtype == jnp.uint8
    assert qt.nbytes * 4 == w.nbytes


def test_packed_tree_jits_and_checkpoints(tmp_path):
    """QTensor leaves ride jit boundaries and the checkpoint manager."""
    from repro.checkpoint.manager import CheckpointManager

    model, cfg = build("llama3-8b", reduced=True)
    policy = transprecision_policy(mode="native", matmul_impl="qmm_pallas")
    params = model.init_params(jax.random.PRNGKey(4), policy)
    packed = qparams.encode_params(params, policy)

    states = model.init_state(2, 16, policy)
    tokens = jnp.zeros((2, 1), jnp.int32)
    step = jax.jit(lambda p, t, s: model.decode_step(p, t, s, policy))
    logits, _ = step(packed, tokens, states)          # packed tree through jit
    assert logits.shape == (2, 1, cfg.vocab)

    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, packed)
    restored, meta = mgr.restore(1, packed)
    r = restored["layers"][0]["ffn"]["w_in"]
    assert isinstance(r, QTensor) and r.fmt == policy.fmt("ffn_w")
    np.testing.assert_array_equal(
        np.asarray(r.payload),
        np.asarray(packed["layers"][0]["ffn"]["w_in"].payload))


def test_packed_tree_shards_with_the_param_rules():
    """tree_param_shardings keys on the same path names, so a packed tree
    gets the same Megatron column/row rules as the dense one (2-device
    child process, the repo's multi-device test idiom)."""
    from conftest import run_child

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
from repro import compat
from repro.core.policy import transprecision_policy
from repro.launch.sharding import tree_param_shardings
from repro.models import qparams
from repro.models.registry import build

mesh = compat.make_mesh((1, 2), ("data", "model"))
model, cfg = build("llama3-8b", reduced=True)
policy = transprecision_policy(mode="native")
params = jax.eval_shape(
    lambda: model.init_params(jax.random.PRNGKey(0), policy))
packed = jax.eval_shape(lambda p: qparams.encode_params(p, policy), params)
dense_sh = tree_param_shardings(params, mesh)
packed_sh = tree_param_shardings(packed, mesh)
for name in ("wq", "wo"):
    d = dense_sh["layers"][0]["mix"][name]
    p = jax.tree.leaves(packed_sh["layers"][0]["mix"][name])[0]
    assert d.spec == p.spec, (name, d.spec, p.spec)
print("PACKED_SHARDING_OK")
"""
    run_child(code, "PACKED_SHARDING_OK", timeout=240)


# ------------------------------------------------ layer-level oracle pins

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("fmt", PAPER_FORMATS, ids=lambda f: f.name)
def test_pdot_qmm_matches_xla_dequantize_path(mode, fmt):
    """pdot over the packed store: qmm_pallas vs the XLA dequantize path,
    <= 1e-6 (accumulation units), all four formats, both policy modes."""
    pol_x, pol_q = _policy_pair(mode, fmt)
    rng = np.random.default_rng(fmt.bits)
    x = jnp.asarray(rng.normal(size=(4, 1, 192)), pol_x.dtype("act"))
    w = QTensor.quantize(jnp.asarray(rng.normal(size=(192, 256)),
                                     jnp.float32), fmt)
    got = pdot(x, w, pol_q, "ffn_w", out_act=False)
    want = pdot(x, w, pol_x, "ffn_w", out_act=False)
    scale = np.abs(np.asarray(x, np.float32).reshape(4, 192)) @ np.abs(
        np.asarray(w.dequantize())) + 1.0
    _close(got, want, scale[:, None, :].reshape(4, 1, 256))
    # the sanitized output edge: quantize/cast of near-equal f32 values
    got_a = pdot(x, w, pol_q, "ffn_w", out_act=True)
    assert got_a.dtype == pol_q.dtype("act")


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("fmt", PAPER_FORMATS, ids=lambda f: f.name)
def test_ffn_fused_matches_xla_dequantize_path(mode, fmt):
    """The fused gated-FFN kernel at the layer level (bias epilogue
    included) against the XLA path over the same packed leaves."""
    import dataclasses as dc

    from repro.models.layers import ffn_init

    model, cfg = build("llama3-8b", reduced=True)
    cfg = dc.replace(cfg, use_bias=True)
    pol_x, pol_q = _policy_pair(mode, fmt)
    p = ffn_init(jax.random.PRNGKey(6), cfg.d_model, cfg.d_ff, True, True,
                 pol_x.dtype("ffn_w"))
    packed = qparams.encode_params({"ffn": p}, pol_x)["ffn"]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 1, cfg.d_model)), pol_x.dtype("act"))
    got = ffn_apply(packed, x, pol_q, cfg)
    want = ffn_apply(packed, x, pol_x, cfg)
    # error propagates through two GEMMs + gate; generous analytic scale
    xa = np.abs(np.asarray(x, np.float32).reshape(4, -1))
    win = np.abs(np.asarray(packed["w_in"].dequantize()))
    wo = np.abs(np.asarray(packed["w_out"].dequantize()))
    scale = ((xa @ win + 1.0) ** 2 @ wo + 1.0).reshape(4, 1, -1)
    _close(got, want, 4.0 * scale)
    assert got.dtype == want.dtype


@pytest.mark.parametrize("mode", MODES)
def test_pgrouped_dot_qmm_matches_xla(mode):
    """MoE expert blocks: per-expert fused kernels vs the grouped einsum
    over the same packed 3-D leaf."""
    fmt = BINARY16ALT
    pol_x, pol_q = _policy_pair(mode, fmt)
    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.normal(size=(2, 16, 96)), pol_x.dtype("act"))
    w = QTensor.quantize(jnp.asarray(rng.normal(size=(2, 96, 128)),
                                     jnp.float32), fmt)
    got = pgrouped_dot(a, w, pol_q, "ffn_w")
    want = pgrouped_dot(a, w, pol_x, "ffn_w")
    wd = np.abs(np.asarray(w.dequantize()))
    scale = np.einsum("eck,ekn->ecn",
                      np.abs(np.asarray(a, np.float32)), wd) + 1.0
    _close(got, want, scale)


def test_peinsum_activations_identical_across_backends():
    """Attention's einsums carry no weight operand: both backends must
    produce bit-identical results (qmm falls through to the XLA math)."""
    pol_x, pol_q = _policy_pair("native", BINARY16ALT)
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(2, 3, 2, 2, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 5, 2, 16)), jnp.bfloat16)
    a = peinsum("bqhgd,bkhd->bhgqk", q, k, pol_q, "attn_w", out_act=False)
    b = peinsum("bqhgd,bkhd->bhgqk", q, k, pol_x, "attn_w", out_act=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["llama3-8b", "granite-moe-1b-a400m",
                                  "rwkv6-1.6b"])
def test_decode_step_qmm_matches_xla_over_packed_store(arch):
    """Model-level: one decode step on the packed store, fused kernels vs
    the XLA dequantize path -- logits near-equal, greedy tokens equal.
    Covers dense (fused gated FFN), MoE (grouped experts) and rwkv6 (fused
    token-shift projections use a dequantized derived weight)."""
    model, cfg = build(arch, reduced=True)
    pol_x = transprecision_policy(mode="native", matmul_impl="xla")
    pol_q = transprecision_policy(mode="native", matmul_impl="qmm_pallas")
    params = model.init_params(jax.random.PRNGKey(0), pol_x)
    packed = qparams.encode_params(params, pol_x)
    states = model.init_state(2, 16, pol_x)
    tokens = jnp.asarray([[3], [5]], jnp.int32)
    lx, _ = model.decode_step(packed, tokens, states, pol_x)
    lq, _ = model.decode_step(packed, tokens,
                              model.init_state(2, 16, pol_q), pol_q)
    lx32 = np.asarray(lx, np.float32)
    lq32 = np.asarray(lq, np.float32)
    np.testing.assert_allclose(lq32, lx32, rtol=5e-2,
                               atol=1e-4 + 1e-3 * np.abs(lx32).max())
    np.testing.assert_array_equal(lq32.argmax(-1), lx32.argmax(-1))


def test_packed_decode_cell_lowers_on_sharded_mesh():
    """The dry-run integration: a decode cell with matmul_impl=qmm_pallas
    lowers and compiles against the PACKED parameter-store structs on a
    (data, model) host mesh -- what `dryrun.py --shape decode_32k_qweights`
    does at production scale."""
    from conftest import run_child

    code = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           "--xla_backend_optimization_level=0")
import dataclasses as dc
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from repro import compat
from repro.core.policy import get_policy
from repro.launch.sharding import (tree_param_shardings,
                                   tree_state_shardings, batch_spec)
from repro.models import qparams
from repro.models.registry import build, build_from_config

mesh = compat.make_mesh((2, 4), ("data", "model"))
policy = get_policy("transprecision")
_, cfg = build("llama3-8b", reduced=True)
model = build_from_config(dc.replace(cfg, matmul_impl="qmm_pallas"))
with mesh:
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), policy))
    params = jax.eval_shape(
        lambda p: qparams.encode_params(p, policy), params)
    p_sh = tree_param_shardings(params, mesh)
    params = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params, p_sh)
    states = jax.eval_shape(lambda: model.init_state(8, 64, policy))
    s_sh = tree_state_shardings(states, mesh, 8)
    states = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        states, s_sh)
    tokens = jax.ShapeDtypeStruct(
        (8, 1), jnp.int32,
        sharding=NamedSharding(mesh, batch_spec(8, mesh)))
    compiled = jax.jit(
        lambda p, t, s: model.decode_step(p, t, s, policy),
        donate_argnums=(2,)).lower(params, tokens, states).compile()
    assert compiled.cost_analysis().get("flops", 0) > 0
    print("QWEIGHTS_CELL_OK")
"""
    run_child(code, "QWEIGHTS_CELL_OK", timeout=420)


# --------------------------------------------------- knobs and validation

def test_matmul_impl_validation_everywhere():
    import dataclasses as dc

    with pytest.raises(ValueError, match="matmul_impl"):
        PrecisionPolicy(formats={}, matmul_impl="qmm_palas")  # typo
    with pytest.raises(ValueError, match="matmul_impl"):
        build("llama3-8b", reduced=True)[1].__class__(
            **{**dc.asdict(build("llama3-8b", reduced=True)[1]),
               "matmul_impl": "pallas"})
    from repro.configs.shapes import ShapeSpec
    with pytest.raises(ValueError, match="matmul_impl"):
        ShapeSpec("x", "decode", 128, 1, matmul_impl="qmm")
    assert dispatch.validate_matmul_impl(None) is None
    with pytest.raises(ValueError):
        dispatch.validate_matmul_impl(None, allow_none=False)
    assert set(MATMUL_IMPLS) == {None, "xla", "qmm_pallas"}


def test_shape_pin_decode_32k_qweights():
    spec = ALL_SHAPES["decode_32k_qweights"]
    assert spec.kind == "decode" and spec.matmul_impl == "qmm_pallas"
    assert spec.cfg_overrides() == {"matmul_impl": "qmm_pallas"}


def test_describe_prints_both_impl_knobs():
    pol = get_policy("transprecision", decode_impl="flash_pallas",
                     matmul_impl="qmm_pallas")
    out = pol.describe()
    assert re.search(r"decode_impl\s+-> flash_pallas", out), out
    assert re.search(r"matmul_impl\s+-> qmm_pallas", out), out
    dflt = get_policy("transprecision").describe()
    assert re.search(r"decode_impl\s+-> \(model default\)", dflt), dflt
    assert re.search(r"matmul_impl\s+-> \(model default\)", dflt), dflt


# ----------------------------------------------------------- altitude guard

_DIRECT_MM = re.compile(r"jnp\.(dot|einsum)\s*\(")


def test_layers_is_the_only_model_module_with_direct_matmuls():
    """Grep-level altitude guard (the mask-guard idiom of test_codec.py):
    ``jnp.dot``/``jnp.einsum`` may appear under ``src/repro/models/`` ONLY
    in ``layers.py`` -- every other module must use pdot/peinsum/
    pgrouped_dot/aeinsum, so each new layer inherits the matmul-backend
    registry (and the packed store) for free."""
    models_dir = os.path.join(SRC, "repro", "models")
    offenders = {}
    for fn in glob.glob(os.path.join(models_dir, "**", "*.py"),
                        recursive=True):
        if os.path.basename(fn) == "layers.py":
            continue
        with open(fn) as f:
            hits = _DIRECT_MM.findall(f.read())
        if hits:
            offenders[os.path.relpath(fn, models_dir)] = hits
    assert not offenders, (
        f"direct jnp.dot/jnp.einsum outside models/layers.py: {offenders} "
        "-- route through pdot/peinsum/pgrouped_dot (registry) or aeinsum "
        "(activation-only)")
    # the guard must keep seeing the real spellings in layers.py itself
    with open(os.path.join(models_dir, "layers.py")) as f:
        assert _DIRECT_MM.search(f.read())
