"""The command refuses to measure where it cannot: without a TPU, and in
a directory that holds only the benchmark's own files."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
CHECKOUT = CHIP.parents[1]
ARGS = ["--workload", "mamba2-370m.fleet", "--seed", "3", "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_a_cpu():
    out = _run(CHECKOUT)
    assert out.returncode == 1, out.stderr[-2000:]
    assert out.stdout == ""
    assert "not a TPU" in out.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip", ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
