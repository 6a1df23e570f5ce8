"""Shared set-up of the benchmark's CPU tests: the harness on the path, a
Pilot-Data root and a compile cache of the test session's own, and cells
cut to sizes a CPU runs in seconds.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import os
import sys
import tempfile
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
_TMP = tempfile.mkdtemp(prefix="bench-tests-")
# read by the program's file-backed Pilot-Data when it is first imported
os.environ.setdefault("REPRO_STORAGE_ROOT", os.path.join(_TMP, "storage"))
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import harness  # noqa: E402

#: published keys cut to the widths of ``repro.configs.base.reduced()``, kept
#: in bf16 as served (these runs check control flow and arithmetic, never
#: speed)
SMALL = {
    "h2o-danube-1.8b": {
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "num_hidden_layers": 2,
        "vocab_size": 256,
        "head_dim": 16,
    },
    "mamba2-370m": {
        "d_model": 64,
        "n_layer": 2,
        "vocab_size": 250,
        "padded_vocab_size": 256,
        "ssm_cfg": {
            "layer": "Mamba2",
            "d_state": 16,
            "d_conv": 4,
            "expand": 2,
            "headdim": 16,
            "ngroups": 1,
            "chunk_size": 16,
        },
    },
}
#: ``logit_gap`` limits at these sizes, set as on the chip between the
#: program's widest reading and the float8 control's least, over the eight
#: seeds 5, 11, 12, 13, 2**31 + 6, 2**31 + 77, 2**31 + 12345, 2**32 + 7 of
#: 2 s windows: danube program 0–0.0177, control 0.197–0.419; mamba2
#: program 0–0.00277, control 0.0179–0.0501
SMALL_GAP_LIMIT = {"h2o-danube-1.8b": 0.06, "mamba2-370m": 0.008}
#: short turns of several sizes, so that every path of the generator runs
SMALL_TRAFFIC = {"user_tokens": [4, 9, 17], "reply_tokens": [3, 6, 12]}


def small_cell(name: str) -> "harness.Cell":
    """The cell ``name`` at CPU sizes: tiny widths, batch 2, short turns,
    and the ``logit_gap`` limit of those sizes."""
    cell = harness.read_cell(name)
    cell.sizes.update(SMALL[cell.config["name"]])
    cell.config = dict(cell.config, batch=2, max_len=64)
    traffic = dict(cell.traffic, **SMALL_TRAFFIC)
    if traffic["replica"]:
        traffic["turns"] = [1, 2, 3]
    cell.traffic = traffic
    cell.limits = dict(cell.limits, logit_gap=SMALL_GAP_LIMIT[cell.config["name"]])
    return cell


@pytest.fixture(scope="session")
def counter():
    import jax

    jax.config.update("jax_compilation_cache_dir", os.path.join(_TMP, "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return harness.CompileCounter()
