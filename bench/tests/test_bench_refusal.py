"""The benchmark measures the chip only: it refuses a CPU, an unknown
device kind, too few chips, and a checkout without the system."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests.tiny import tiny_cell

ROOT = Path(__file__).resolve().parents[2]


def run_py(cwd, *args, env=None):
    env = dict(os.environ if env is None else env)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite.chat_batch",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0",
         *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def printed_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return isinstance(json.loads(lines[-1]), dict)
    except ValueError:
        return False


def test_the_command_refuses_the_cpu():
    proc = run_py(ROOT)
    assert proc.returncode != 0
    assert not printed_result(proc)
    assert "no TPU" in proc.stderr


def test_the_command_refuses_a_checkout_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = run_py(tmp_path, env=env)
    assert proc.returncode != 0
    assert not printed_result(proc)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.peaks_for("TPU v99 imaginary")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_run_cell_refuses_a_non_tpu_platform():
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.run_cell(tiny_cell({"max_logit_gap": 1.0}), seed=1, seconds=1, trace=False,
                         t_start=0.0)


def test_run_cell_refuses_too_few_chips():
    cell = tiny_cell({"max_logit_gap": 1.0})
    cell.chips = 4
    with pytest.raises(harness.BenchError, match="asks for 4 chips"):
        harness.run_cell(cell, seed=1, seconds=1, trace=False, t_start=0.0,
                         require_tpu=False)
