"""``bench/run.py`` as a check starts it: with no TPU, or without the
program beside it, it exits non-zero and prints no result."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "mv_n12_k9.b8", "--seed", "3000000000",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
    assert "no TPU found" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    bm = spec.load_benchmark()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in bm["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)


def test_peaks_are_known_by_device_kind():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v99")
