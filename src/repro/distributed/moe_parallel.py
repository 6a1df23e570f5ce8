"""Expert-parallel MoE bridge: wraps the shard_map EP path around the local
MoE body when a distribution context is active.

Train/prefill (S divisible by the model axis) → explicit shard_map with
all-to-all over the model axis (collective bytes visible in the dry-run
HLO).  Decode (S == 1) or no mesh → the local gather/scatter path; GSPMD
partitions it automatically (the tensors are tiny at decode).
"""

from __future__ import annotations


import jax
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..models.moe import moe_mlp_ep, moe_mlp_local
from .context import current


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def moe_maybe_parallel(moe_params, x, cfg: ModelConfig):
    ctx = current()
    b, s, d = x.shape
    if ctx is None or not ctx.ep or not _div(s, ctx.model_size) or s == 1:
        return moe_mlp_local(moe_params, x, cfg)
    m = ctx.model_axis
    batch = ctx.batch_axes if _div(b, ctx.batch_size_total) else None
    # the aux pmean may only reduce over axes the value actually varies on:
    # tokens vary over the model axis (seq sharding) always, and over the
    # DP axes only when the batch dim is sharded there.
    reduce_axes = (tuple(ctx.batch_axes) + (m,)) if batch is not None else (m,)

    def pspec(path, leaf):
        names = [str(k.key) for k in path if isinstance(k, jax.tree_util.DictKey)]
        if "router" in names:
            return P(*([None] * len(leaf.shape)))
        return P(m, *([None] * (len(leaf.shape) - 1)))

    param_specs = jax.tree_util.tree_map_with_path(pspec, moe_params)
    x_spec = P(batch, m, None)

    def body(p, xl):
        return moe_mlp_ep(
            p, xl, cfg, model_axis=m, reduce_axes=reduce_axes
        )

    return jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(param_specs, x_spec),
        out_specs=(x_spec, P()),
    )(moe_params, x)
