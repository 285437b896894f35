"""``chip_smoke.py`` on the CPU: it refuses to run anywhere but a TPU, and
its sublayer check chains the model's own sublayers (so it reproduces
``Model.prefill_chunk`` / ``Model.decode_step``) and rejects a wrong
output."""
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import ROOT, run_child

sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

from repro import configs  # noqa: E402
from repro.core.policy import get_policy  # noqa: E402
from repro.models import qparams  # noqa: E402


def _run(script, cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_cpu():
    r = _run(os.path.join(ROOT, "chip_smoke.py"), ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_refuses_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run(str(tmp_path / "chip_smoke.py"), tmp_path)
    assert r.returncode != 0
    assert "src/" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.fixture(scope="module")
def check():
    cfg = configs.get("granite-moe-1b-a400m", reduced=True)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, chip_smoke.PROMPT).tolist()
    forced = rng.integers(0, cfg.vocab, 2).tolist()
    ref = get_policy("transprecision", decode_impl="xla", matmul_impl="xla")
    return chip_smoke.SublayerCheck(jax, cfg, prompt, forced, ref), ref


_CHAIN_CHILD = r"""
import sys
import jax, numpy as np
sys.path.insert(0, %r)
import chip_smoke
from repro import configs
from repro.core.policy import get_policy
cfg = configs.get("granite-moe-1b-a400m", reduced=True)
rng = np.random.default_rng(0)
prompt = rng.integers(0, cfg.vocab, chip_smoke.PROMPT).tolist()
forced = rng.integers(0, cfg.vocab, 2).tolist()
ref = get_policy("transprecision", decode_impl="xla", matmul_impl="xla")
sc = chip_smoke.SublayerCheck(jax, cfg, prompt, forced, ref)
n = cfg.n_layers
chunk = jax.jit(lambda p, t, s, off: sc.model.prefill_chunk(
    p, t, s, [None] * n, ref, slot=0, q_offset=off)[0], static_argnums=3)
step = jax.jit(lambda p, t, s: sc.model.decode_step(p, t, s, ref)[0])
assert [k for k, _, _ in sc.calls] == ["prefill"] * 2 + ["decode"] * 2
for (kind, toks, off), rec in zip(sc.calls, sc.records):
    states = [r["state"] for r in rec["layers"]]
    want = (chunk(sc.params, toks, states, off) if kind == "prefill"
            else step(sc.params, toks, states))
    np.testing.assert_allclose(np.asarray(rec["logits"]), np.asarray(want),
                               rtol=1e-5, atol=1e-5, err_msg=f"{kind}@{off}")
print("CHAIN_OK")
"""


def test_reference_chain_is_the_model():
    """The recorded reference logits are the model's own.  XLA may keep
    excess precision across the sublayers fused in one program, which a
    chain of separate programs rounds at each boundary; with that turned
    off the two agree up to summation order."""
    run_child(_CHAIN_CHILD % ROOT, "CHAIN_OK", timeout=300,
              env={"XLA_FLAGS": "--xla_allow_excess_precision=false"})


@pytest.mark.parametrize("decode,matmul", [("flash_pallas", "xla"),
                                           ("paged", "xla"),
                                           ("paged", "qmm_pallas")])
def test_kernel_spelling_within_bound(check, decode, matmul):
    sc, _ = check
    pol = get_policy("transprecision", decode_impl=decode,
                     matmul_impl=matmul)
    params = (qparams.encode_params(sc.params, pol) if matmul != "xla"
              else sc.params)
    assert np.isfinite(sc.delta(pol, params, label=f"{decode}+{matmul}"))


def test_wrong_output_is_caught(check):
    """A 10% error in one layer's attention projection is far outside the
    bound."""
    sc, ref = check
    params = jax.tree_util.tree_map(lambda a: a, sc.params)
    mix = dict(params["layers"][1]["mix"])
    mix["wo"] = (mix["wo"] * 1.1).astype(mix["wo"].dtype)
    params["layers"][1] = {**params["layers"][1], "mix": mix}
    with pytest.raises(chip_smoke.SmokeFailure, match="attention"):
        sc.delta(ref, params, label="perturbed")
