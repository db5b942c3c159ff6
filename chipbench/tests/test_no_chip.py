"""A measurement path with no TPU fails; it never falls back to the CPU."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "torus32k.part-noise", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(proc) -> bool:
    return proc.returncode != 0 and "{" not in proc.stdout


def test_cpu_backend_gives_no_result():
    proc = _run(harness.ROOT)
    assert _no_result(proc), proc.stdout
    assert "no TPU" in proc.stderr


def test_benchmark_alone_gives_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.PKG, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert _no_result(proc), proc.stdout


def test_chips_refuses_the_cpu():
    from chipbench import run
    with pytest.raises(run.NoChip):
        run.chips(1)


def test_unknown_device_kind_has_no_peaks():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")
