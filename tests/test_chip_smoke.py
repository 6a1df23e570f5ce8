"""chip_smoke.py off the chip: it refuses to run without a TPU or outside
a checkout, and its serve and train phases pass at a tiny size on the CPU
(the chip runs them at published widths)."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(script: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script)],
        capture_output=True, text=True, env=env, timeout=300,
    )


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_tpu_or_checkout(where, tmp_path):
    script = SCRIPT
    if where == "alone":
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    proc = _run(script)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_serve_phase_tiny(chip_smoke):
    from repro.configs import get_config
    from repro.configs.base import reduced

    chip_smoke.serve_phase(
        reduced(get_config("gemma3-1b")),
        seed=0, batch=2, prompt_len=8, new_tokens=8, max_len=64,
    )


def test_train_phase_tiny(chip_smoke):
    from repro.configs import get_config
    from repro.configs.base import reduced

    chip_smoke.train_phase(reduced(get_config("mamba2-370m")), seed=0, seq=32)
