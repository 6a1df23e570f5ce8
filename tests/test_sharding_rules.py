"""Sharding rules: spec derivation on a fake multi-device mesh.

Runs in a subprocess (XLA device count must be set before jax imports, and
the rest of the suite needs the real single device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.distributed.context import DistContext
    from repro.distributed.sharding_rules import (
        batch_specs, cache_specs, opt_specs, param_specs,
    )
    from repro.models import build_model
    from repro.optim import init_adamw

    mesh = jax.make_mesh(
        (2, 4), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    ctx = DistContext(mesh=mesh, batch_axes=("data",))
    out = {}

    # dense arch: Megatron column/row rules
    cfg = get_config("h2o-danube-1.8b")
    api = build_model(cfg)
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    specs = param_specs(shapes, cfg, ctx)
    out["attn_q"] = str(specs["groups"]["pos0"]["attn"]["q"]["w"])
    out["attn_o"] = str(specs["groups"]["pos0"]["attn"]["o"]["w"])
    out["mlp_gate"] = str(specs["groups"]["pos0"]["mlp"]["gate"]["w"])
    out["mlp_down"] = str(specs["groups"]["pos0"]["mlp"]["down"]["w"])
    out["embed"] = str(specs["embed"]["table"])
    out["norm"] = str(specs["final_norm"]["scale"])

    # ZeRO: opt state gains a data axis on an unsharded dim
    opt_shapes = jax.eval_shape(init_adamw, shapes)
    ospecs = opt_specs(opt_shapes, specs, cfg, ctx)
    out["opt_m_q"] = str(ospecs["m"]["groups"]["pos0"]["attn"]["q"]["w"])
    out["opt_step"] = str(ospecs["step"])

    # MoE: experts over model axis
    cfgm = get_config("qwen3-moe-30b-a3b")
    apim = build_model(cfgm, ep=4)
    shapesm = jax.eval_shape(apim.init, jax.random.PRNGKey(0))
    specsm = param_specs(shapesm, cfgm, ctx)
    out["moe_gate"] = str(specsm["groups"]["pos0"]["moe"]["gate"])
    out["moe_router"] = str(specsm["groups"]["pos0"]["moe"]["router"]["w"])

    # mamba: head-parallel projections
    cfgs = get_config("mamba2-370m")
    apis = build_model(cfgs)
    shapess = jax.eval_shape(apis.init, jax.random.PRNGKey(0))
    specss = param_specs(shapess, cfgs, ctx)
    out["mamba_x"] = str(specss["groups"]["pos0"]["mamba"]["x_proj"]["w"])
    out["mamba_bc"] = str(specss["groups"]["pos0"]["mamba"]["bc_proj"]["w"])
    out["mamba_out"] = str(specss["groups"]["pos0"]["mamba"]["out_proj"]["w"])

    # whisper: 20 heads % 4 == 0 on this mesh → sharded
    cfgw = get_config("whisper-large-v3")
    apiw = build_model(cfgw)
    shapesw = jax.eval_shape(apiw.init, jax.random.PRNGKey(0))
    specsw = param_specs(shapesw, cfgw, ctx)
    out["whisper_q"] = str(specsw["decoder"]["self_attn"]["q"]["w"])

    # batch + cache specs
    from repro.configs import ShapeConfig
    bs = batch_specs(api.batch_spec(ShapeConfig("train", 4096, 256, "train")), ctx)
    out["tokens"] = str(bs["tokens"])
    cache_shapes = jax.eval_shape(lambda: api.init_cache(128, 4096))
    cs = cache_specs(cache_shapes, cfg, ctx)
    out["cache_k"] = str(cs["groups"]["pos0"]["k"])
    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def specs():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_megatron_column_row(specs):
    # leading None = the stacked per-group dim of scanned layers
    assert specs["attn_q"] == "PartitionSpec(None, None, 'model')"
    assert specs["attn_o"] == "PartitionSpec(None, 'model', None)"
    assert specs["mlp_gate"] == "PartitionSpec(None, None, 'model')"
    assert specs["mlp_down"] == "PartitionSpec(None, 'model', None)"


def test_vocab_sharded_embedding_and_replicated_norm(specs):
    assert specs["embed"] == "PartitionSpec('model', None)"
    assert "'model'" not in specs["norm"] and "'data'" not in specs["norm"]


def test_zero_adds_data_axis(specs):
    # ZeRO picks the first unsharded divisible dim (the group-stack dim)
    assert specs["opt_m_q"] == "PartitionSpec('data', None, 'model')"
    assert specs["opt_step"] == "PartitionSpec()"


def test_moe_expert_parallel(specs):
    assert specs["moe_gate"] == "PartitionSpec(None, 'model', None, None)"
    assert "'model'" not in specs["moe_router"]


def test_mamba_head_parallel(specs):
    assert specs["mamba_x"] == "PartitionSpec(None, None, 'model')"
    assert "'model'" not in specs["mamba_bc"]  # tiny: replicated
    assert specs["mamba_out"] == "PartitionSpec(None, 'model', None)"


def test_whisper_heads_shard_when_divisible(specs):
    # 20 heads on a 4-way model axis → divisible → sharded
    assert specs["whisper_q"] == "PartitionSpec(None, None, 'model')"


def test_batch_and_cache_specs(specs):
    assert specs["tokens"] == "PartitionSpec('data', None)"
    assert "model" in specs["cache_k"]  # seq dim sharded over model
