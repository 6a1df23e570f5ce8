"""The cells added with Zamba2 (``zamba2-7b.chat``, ``mamba2-370m.chat``):
the step's counts against numbers worked by hand, and a short window of
each through the harness on the CPU at small sizes."""

import time

import pytest

import harness

# zamba2-7b cut to layers 0-12 (sites 6 and 11), batch 8, 1000 live positions:
#   mixer 3584·(2·7168 + 2·2·64 + 112) + 7168·3584 = 78,389,248
#   attention 2·3584·(32 + 2·32)·224 + 32·224·3584 = 179,830,784
#   site 3584·128 + 2·128·14336 + 3584² = 16,973,824; MLP 3·3584·14336
#   matmul weights 13·78,389,248 + 2·(179,830,784 + 154,140,672 + 16,973,824)
#       + 3584·32000 = 1,835,638,784
#   flops 2·8·1,835,638,784 + 8·13·(2·4·7424 + 5·112·64·64)
#       + 4·8·1000·32·224·2 = 30,073,700,352
#   weights (1,835,638,784 + 13·48,208 + 2·3·3584 + 3584 + 8·3584)·2
#       = 3,672,638,496 (the cut's 1,836,290,576 parameters and 8 rows)
#   KV 8·1000·2·2·32·224·2 = 458,752,000; state 2·8·13·1,924,096 = 400,211,968
ZAMBA2 = (30_073_700_352, 3_672_638_496 + 458_752_000 + 400_211_968)

#: published keys cut to CPU sizes (widths of ``reduced()``, head size
#: 2·hidden_size / heads as transformers derives it), served in bf16
SMALL = {
    "zamba2-7b": dict(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=4, attention_head_dim=32, vocab_size=256,
        mamba_d_state=16, mamba_headdim=16, chunk_size=16, adapter_rank=8,
    ),
    "mamba2-370m": {
        "d_model": 64, "n_layer": 2, "vocab_size": 250, "padded_vocab_size": 256,
        "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4, "expand": 2,
                    "headdim": 16, "ngroups": 1, "chunk_size": 16},
    },
}
#: ``logit_gap`` limits at these sizes, between the program's widest
#: reading and the float8 control's least over the eight seeds 5, 11, 12,
#: 13, 2**31 + 6, 2**31 + 77, 2**31 + 12345, 2**32 + 7 of 2 s and 3 s
#: windows: zamba2 program 0.0002–0.013, control 0.113–0.33; mamba2
#: program 0–0.0031, control 0.0152–0.041
SMALL_GAP_LIMIT = {"zamba2-7b": 0.05, "mamba2-370m": 0.008}


def test_zamba2_step():
    cell = harness.read_cell("zamba2-7b.chat")
    assert cell.counts.serve_step(cell.sizes, 8, 1000) == ZAMBA2


def _small(name: str) -> "harness.Cell":
    cell = harness.read_cell(name)
    cfg = cell.config["name"]
    cell.sizes.update(SMALL[cfg])
    cell.config = dict(cell.config, batch=2, max_len=64)
    cell.traffic = dict(cell.traffic, user_tokens=[4, 9, 17], reply_tokens=[3, 6, 12], turns=[1, 2, 3])
    cell.limits = dict(cell.limits, logit_gap=SMALL_GAP_LIMIT[cfg])
    return cell


@pytest.mark.parametrize("name", ["zamba2-7b.chat", "mamba2-370m.chat"])
def test_small_window(name, counter):
    """Traced on the CPU: correct, and the cache manager's metric read from
    ``engine.init``; the device metrics need a device plane and are left
    out."""
    cell = _small(name)
    out = harness.run_cell(cell, seed=2**31 + 12345, seconds=3.0, traced=True, t_proc0=time.monotonic(), counter=counter)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"cache_gb"}
    from repro.models import build_model
    from repro.serving.engine import cache_bytes

    api = build_model(harness.program_config(cell))
    import jax

    want = sum(cache_bytes(jax.eval_shape(lambda: api.init_cache(2, 64))).values()) / 1e9
    assert out["metrics"]["cache_gb"]["value"] == pytest.approx(want)
