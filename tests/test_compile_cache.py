"""The persistent compilation cache goes where the entry points say:
``JAX_COMPILATION_CACHE_DIR`` when set (and nowhere else), otherwise the
fixed ``<repo root>/.jax_cache``."""
import os

import jax

from conftest import ROOT, run_child
from repro.launch import compile_cache


def test_unset_env_uses_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        want = os.path.join(ROOT, ".jax_cache")
        assert got == want == jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


_ENV_DIR_CHILD = r"""
import os
import jax, jax.numpy as jnp
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
want = os.environ["JAX_COMPILATION_CACHE_DIR"]
before = set(os.listdir(REPO_CACHE_DIR)) if REPO_CACHE_DIR.exists() else set()
assert enable_compile_cache() == want
assert jax.config.jax_compilation_cache_dir == want
jax.jit(lambda x: jnp.cos(x) * 3.0)(jnp.ones(16)).block_until_ready()
assert os.listdir(want), "no cache entry in JAX_COMPILATION_CACHE_DIR"
after = set(os.listdir(REPO_CACHE_DIR)) if REPO_CACHE_DIR.exists() else set()
assert after == before, "an entry landed in the repo cache too"
print("CACHE_ENV_OK")
"""


def test_env_dir_receives_every_entry(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
           "JAX_PLATFORMS": "cpu"}
    run_child(_ENV_DIR_CHILD, "CACHE_ENV_OK", timeout=300, env=env)
