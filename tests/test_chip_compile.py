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
import re

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


def _engine_step(api, one_chip, batch: int, slots: int):
    """``DecodeEngine``'s jitted step compiled for one v5e chip."""
    from repro.serving.engine import jit_serve_step, split_cache

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_chip, jax.eval_shape(lambda: api.init_cache(batch, slots)))
    kv, state = split_cache(cache)
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return jit_serve_step(api).lower(params, kv, state, tokens, pos).compile(), kv, state


def _fits(compiled) -> None:
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert total < V5E_HBM_BYTES, mem


@pytest.fixture(scope="module")
def zamba2_step(one_chip):
    """The decode step at the benchmark's Zamba2 cut: published widths,
    layers 0-12 (sites 6 and 11), bf16, batch 8 and 4096 slots."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import build_model

    full = get_config("zamba2-7b")
    cut = dataclasses.replace(full, n_layers=13, pattern=full.layer_kinds()[:13])
    return _engine_step(build_model(cut), one_chip, 8, 4096)


def test_gemma3_1b_serve_step_fits_one_v5e(one_chip):
    """The decode step ``DecodeEngine`` runs, at gemma3-1b's published
    config (26 layers, bf16, vocab 262144) with an 8 × 1024 cache."""
    from repro.configs import get_config
    from repro.models import build_model

    compiled, _, _ = _engine_step(build_model(get_config("gemma3-1b")), one_chip, 8, 1024)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 1.5e9  # the weights are all there
    _fits(compiled)


def test_zamba2_7b_cut_serve_step_fits_one_v5e(zamba2_step):
    """The decode step at the benchmark's Zamba2 cut: published widths,
    layers 0-12 (sites 6 and 11), bf16, batch 8 and 4096 slots."""
    compiled, _, _ = zamba2_step
    mem = compiled.memory_analysis()
    # 3.67 GB of weights and the 2.08 GB cache in, a new cache out
    assert mem.argument_size_in_bytes > 5.7e9
    _fits(compiled)


# ------------------------------------------------- the KV written in place
_INSTR = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = (\S+|\(.*?\)) ([\w\-]+)\((.*)$")
#: opcodes that only pass a buffer on
_PASS = {"parameter", "get-tuple-element", "tuple", "while", "bitcast", "opt-barrier"}


def _unfused(hlo: str):
    """(name, shapes, opcode, operands) of every instruction outside the
    bodies of fusions; shapes lists each array of a tuple."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", hlo))
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if m and comp not in fused:
            shapes = [
                tuple(int(d) for d in dims.split(","))
                for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(2))
            ]
            operands = re.findall(r"%[\w.\-]+", m.group(4).split("),")[0])
            yield m.group(1), shapes, m.group(3), operands


def _parameters(hlo: str):
    """{parameter name: number} of the entry computation's parameters."""
    entry = hlo[hlo.index("\nENTRY ") :]
    return dict(
        (m.group(1), int(m.group(2)))
        for m in re.finditer(r"(%[\w.\-]+) = \S+ parameter\((\d+)\)", entry)
    )


def _aliased(hlo: str):
    """Numbers of the parameters whose buffers the outputs reuse."""
    head = hlo[: hlo.index("\n")]
    return {int(n) for n in re.findall(r"\{[\d,]*\}: \((\d+),", head)}


@pytest.fixture(scope="module")
def danube_step(one_chip):
    """The decode step at h2o-danube-1.8b's published widths, 4 of its 24
    layers, batch 8 and 4096 slots."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_config("h2o-danube-1.8b"), n_layers=4)
    return _engine_step(build_model(cfg), one_chip, 8, 4096)


@pytest.mark.parametrize("model", ["danube_step", "zamba2_step"])
def test_serve_step_writes_kv_in_place(model, request):
    """Every KV leaf is aliased input to output, and outside fusions no
    instruction makes a whole layer's K or V but the token writes into the
    donated KV parameters: the layer scan neither copies a layer's K and V
    out of the cache nor back in."""
    compiled, kv, _ = request.getfixturevalue(model)
    hlo = compiled.as_text()
    params = _parameters(hlo)
    kv_params = {n for name, n in params.items() if name.startswith("%kv__")}
    assert len(kv_params) == len(jax.tree.leaves(kv)) > 0
    assert kv_params <= _aliased(hlo)

    layer = {leaf.shape[-4:] for leaf in jax.tree.leaves(kv)}
    writes, made = 0, []
    for name, shapes, op, operands in _unfused(hlo):
        if not any(s[-4:] in layer for s in shapes if len(s) >= 4) or op in _PASS:
            continue
        if op == "dynamic-update-slice" and params.get(operands[0]) in kv_params:
            writes += 1
        else:
            made.append(f"{name} {op} {shapes}")
    assert not made, made
    assert writes == len(kv_params)


def test_mamba2_370m_serve_step_copies_no_state(one_chip):
    """Without KV nothing is donated, and the step adds no copy of a whole
    SSM or conv state: its update writes the state once."""
    from repro.configs import get_config
    from repro.models import build_model

    compiled, kv, state = _engine_step(
        build_model(get_config("mamba2-370m")), one_chip, 8, 2048
    )
    hlo = compiled.as_text()
    assert not jax.tree.leaves(kv) and not _aliased(hlo)
    whole = {leaf.shape[1:] for leaf in jax.tree.leaves(state)}
    copies = [
        f"{name} {shapes}"
        for name, shapes, op, _ in _unfused(hlo)
        if op in ("copy", "copy-start") and any(s[-len(w):] == w for s in shapes for w in whole)
    ]
    assert not copies, copies
