"""Where JAX keeps its persistent compilation cache.

A cold chip run compiles many programs (one per prefill chunk offset, the
decode step, every kernel), so the entry points keep compiled programs on
disk.  The rule, applied once per process by :func:`enable_compile_cache`
from ``serve.cli_main``, ``launch/train.py`` and ``chip_smoke.py`` (never
at import):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else is
  set in code, so every entry lands there.
* unset: the fixed directory ``<repo root>/.jax_cache`` (gitignored).  A
  path is part of the cache key's reach, so it must not move between runs:
  never a temp name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
