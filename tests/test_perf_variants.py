"""Equivalence tests for the §Perf optimization variants: every hillclimb
change must be numerically identical to its baseline path."""
import jax
import jax.numpy as jnp
import numpy as np

from conftest import run_child
from repro.core.policy import binary32_policy
from repro.models import rwkv6 as rw
from repro.models.base import ModelConfig

POLICY = binary32_policy()


def test_rwkv_fused_projections_exact():
    """Perf #2: the lerp identity y_i = x@W_i + (xx-x)@(m_i*W_i) is exact."""
    cfg = ModelConfig(arch="t", family="ssm", n_layers=1, d_model=32,
                      n_heads=2, n_kv=2, d_ff=48, vocab=64, rwkv_head_dim=16,
                      rwkv_chunk=8, rope_theta=0.0, norm="layernorm",
                      act_fn="relu2", gated_ffn=False)
    p = rw.rwkv_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    pf = {
        "mu": p["mu"],
        "wrkvg": jnp.concatenate([p["wr"], p["wk"], p["wv"], p["wg"],
                                  p["wd1"]], axis=1),
        "wo": p["wo"], "w0": p["w0"], "wd2": p["wd2"], "u": p["u"],
        "ln_g": p["ln_g"], "ln_b": p["ln_b"], "cm_mu": p["cm_mu"],
        "cm_kr": jnp.concatenate([p["cm_k"], p["cm_r"]], axis=1),
        "cm_v": p["cm_v"],
    }
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32),
                          jnp.float32) * 0.5
    o1, _ = rw.time_mix(p, x, cfg, POLICY)
    o2, _ = rw.time_mix(pf, x, cfg, POLICY)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-5, atol=1e-5)
    c1, _ = rw.channel_mix(p, x, cfg, POLICY)
    c2, _ = rw.channel_mix(pf, x, cfg, POLICY)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                               rtol=1e-5, atol=1e-5)


def test_rwkv_fused_smoke_train():
    """A fused-config model trains without NaNs."""
    from repro.core.policy import transprecision_policy
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models.registry import build_from_config
    import dataclasses
    from repro.configs import get
    cfg = dataclasses.replace(get("rwkv6-1.6b", reduced=True), rwkv_fused=1)
    model = build_from_config(cfg)
    pol = transprecision_policy()
    params = model.init_params(jax.random.PRNGKey(0), pol)
    data = SyntheticLM(DataConfig(global_batch=2, seq_len=32), cfg)
    loss = jax.jit(lambda p, b: model.train_loss(p, b, pol))(
        params, data.batch_at(0))
    assert np.isfinite(float(loss))


_SUBPROCESS_EQ = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core.policy import binary32_policy
from repro.models import moe
from repro.models.base import ModelConfig
from repro.models.registry import build_from_config
from repro.configs import get

from repro import compat

# Auto axis semantics on every JAX version (compat drops axis_types where
# the explicit-sharding API does not exist yet).
mesh = compat.make_mesh((2, 4), ("data", "model"))
pol = binary32_policy()

# --- MoE: shard_map dispatch == dense dispatch (high capacity: no drops) ---
cfg = ModelConfig(arch="t", family="moe", n_layers=1, d_model=32, n_heads=4,
                  n_kv=2, d_ff=16, vocab=64, moe_experts=8, moe_topk=2,
                  capacity_factor=8.0)
p = moe.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32)
import repro.models.moe as mm
taken = []
orig = mm.moe_apply_sharded
mm.moe_apply_sharded = lambda *a, **k: (taken.append(1), orig(*a, **k))[1]
with compat.use_mesh(mesh):
    y_d, _, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg, pol))(p, x)
    cfg2 = dataclasses.replace(cfg, moe_impl="shard_map")
    y_s, _, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg2, pol))(p, x)
assert taken, "shard_map path not taken"
np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_d),
                           rtol=2e-5, atol=2e-5)

# --- flash-decode (shmap wrapper backend) == xla decode --------------------
cfg = dataclasses.replace(get("llama3-8b", reduced=True), n_layers=2)
model = build_from_config(cfg)
params = model.init_params(jax.random.PRNGKey(0), pol)
toks = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab)
# spy on the wrapper's sharded branch specifically: the flash_shmap
# wrapper must genuinely shard (its mesh-availability fallback would
# silently run the inner backend, and other components' shard_map calls
# must not count)
import repro.kernels.dispatch as disp
fd = []
orig_shmap_decode = disp._shmap_decode
def spy_shmap_decode(*a, **k):
    fd.append(1)
    return orig_shmap_decode(*a, **k)
disp._shmap_decode = spy_shmap_decode
with compat.use_mesh(mesh):
    _, states = jax.jit(lambda p, b: model.prefill(p, b, pol, 32))(
        params, {"tokens": toks})
    nxt = jnp.zeros((4, 1), jnp.int32)
    l1, _ = jax.jit(lambda p, t, s: model.decode_step(p, t, s, pol))(
        params, nxt, states)
    m2 = build_from_config(dataclasses.replace(cfg,
                                               decode_impl="flash_shmap"))
    l2, _ = jax.jit(lambda p, t, s: m2.decode_step(p, t, s, pol))(
        params, nxt, states)
assert fd, "flash_shmap wrapper did not shard_map"
np.testing.assert_allclose(np.asarray(l2), np.asarray(l1),
                           rtol=2e-5, atol=2e-5)
print("PERF_VARIANTS_OK")
"""


def test_shard_map_variants_subprocess():
    run_child(_SUBPROCESS_EQ, "PERF_VARIANTS_OK", timeout=480)
