"""Shared test helpers."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(code, marker, timeout, env=None):
    """Run ``code`` in a fresh interpreter and require ``marker`` on stdout.

    Failure dumps the child's full stdout/stderr -- a bare exit-status assert
    swallows the child traceback and makes regressions undiagnosable (the
    JAX-0.4.37 API-drift failures hid behind exactly that; CHANGES.md PR 1).
    """
    env = {**os.environ, **(env or {})}
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    if marker not in r.stdout:
        pytest.fail(
            f"child never printed {marker!r} (exit {r.returncode})\n"
            f"---- child stdout ----\n{r.stdout}\n"
            f"---- child stderr ----\n{r.stderr}")
