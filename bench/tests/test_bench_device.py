"""The measurement path refuses anything but a TPU, and a checkout that
holds only the benchmark prints no result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from benchutil import ROOT

from bench import harness


def test_check_devices_refuses_a_cpu():
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.check_devices(1)


def _run(root, workload="paper_selfish.sweep_fig12"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         workload, "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_print_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
