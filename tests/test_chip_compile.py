"""Compile the chip's main-path programs for a described TPU v5e.

No chip is needed: the TPU compiler accepts a topology that is described,
not attached, and refuses what the chip would refuse — a block that breaks
the 8×128 tiling, a kernel over its VMEM budget, a program larger than the
device.  Interpret-mode tests cannot see any of that.

The topology is described only inside the ``topo`` fixture: only one
process at a time may load the TPU library, so nothing here touches it
while the module is imported (each test worker imports every test file).
All cases stay in this one file so they land on one worker.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cases import KERNEL_CASES

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # without a log dir of its own the TPU library logs under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for a described chip is written to the
        # persistent cache but cannot be read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("case", KERNEL_CASES, ids=lambda c: c.name)
def test_kernel_compiles_for_v5e(case, one_chip):
    compiled = case.kernel.lower(*case.arg_specs(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gemma3_1b_serve_step_fits_one_v5e(one_chip):
    """The decode step ``DecodeEngine`` runs, at gemma3-1b's published
    config (26 layers, bf16, vocab 262144) with an 8 × 1024 cache."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.engine import make_serve_step

    api = build_model(get_config("gemma3-1b"))

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_chip, jax.eval_shape(lambda: api.init_cache(8, 1024)))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(make_serve_step(api)).lower(params, cache, tokens, pos).compile()
    )
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert mem.argument_size_in_bytes > 1.5e9  # the weights are all there
    assert total < V5E_HBM_BYTES, mem


def test_zamba2_7b_cut_serve_step_fits_one_v5e(one_chip):
    """The decode step at the benchmark's Zamba2 cut: published widths,
    layers 0-12 (sites 6 and 11), bf16, batch 8 and 4096 slots."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.engine import make_serve_step

    full = get_config("zamba2-7b")
    cut = dataclasses.replace(full, n_layers=13, pattern=full.layer_kinds()[:13])
    api = build_model(cut)

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_chip, jax.eval_shape(lambda: api.init_cache(8, 4096)))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(make_serve_step(api)).lower(params, cache, tokens, pos).compile()
    )
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    # 3.67 GB of weights and the 2.08 GB cache in, a new cache out
    assert mem.argument_size_in_bytes > 5.7e9
    assert total < V5E_HBM_BYTES, mem
