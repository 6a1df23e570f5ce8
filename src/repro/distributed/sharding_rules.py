"""Parameter / optimizer / batch / cache sharding rules.

The layout (per DESIGN.md §4):
  * TP over ``model``: Megatron column/row split of attention + MLP,
    vocab-sharded embeddings, head-sharded Mamba projections, EP for MoE
    experts;
  * DP over ``data`` (and ``pod``): batch dims; ZeRO-1 — optimizer moments
    and fp32 masters additionally sharded over the DP axes;
  * SP: residual-stream sequence dim over ``model`` between blocks (applied
    via ``distributed.context.constrain``);
  * anything that does not divide evenly is replicated (never errors —
    whisper's 20 heads on a 16-way axis simply stay unsharded and SP
    carries the parallelism).

Rules are *path-based* over pytrees of ShapeDtypeStructs, so they apply
identically to live arrays and to ``jax.eval_shape`` trees.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from .context import DistContext

#: tree prefixes that stack per-layer params with one leading dim
_STACKED_KEYS = ("groups", "encoder", "decoder")


def _names(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            out.append(str(k.key))
        elif isinstance(k, jax.tree_util.GetAttrKey):  # pragma: no cover
            out.append(k.name)
    return tuple(out)


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def _dp(axes) -> Any:
    """PartitionSpec element for the DP axes: a single axis stays a bare
    name (P('data', …), not P(('data',), …) — the tuple form denotes
    multi-axis sharding and confuses spec comparisons downstream)."""
    axes = tuple(axes)
    return axes[0] if len(axes) == 1 else axes


def _param_rule(
    names: Tuple[str, ...], shape: Tuple[int, ...], cfg: ModelConfig, ms: int
) -> Tuple[Optional[Any], ...]:
    """Spec for the UNSTACKED shape; returns a tuple of P elements."""
    m = "model"
    last = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    gparent = names[-3] if len(names) >= 3 else ""

    if parent == "embed" or (last in ("table", "lm_head")):
        if last == "table":
            return (m, None) if _div(shape[0], ms) else (None, None)
        if last == "lm_head":
            return (None, m) if _div(shape[1], ms) else (None, None)

    if gparent in ("attn", "self_attn", "cross_attn") and last == "w":
        if parent == "q":
            ok = _div(cfg.n_heads, ms)
            return (None, m) if ok else (None, None)
        if parent in ("k", "v"):
            ok = _div(cfg.n_kv_heads, ms)
            return (None, m) if ok else (None, None)
        if parent == "o":
            ok = _div(cfg.n_heads, ms)
            return (m, None) if ok else (None, None)

    if gparent == "mlp" and last == "w":
        if parent in ("gate", "up"):
            return (None, m) if _div(shape[1], ms) else (None, None)
        if parent == "down":
            return (m, None) if _div(shape[0], ms) else (None, None)

    if parent == "moe" and last in ("gate", "up", "down"):
        # [E_pad, d_in, d_out] — expert parallelism (E_pad divides by design)
        return (m, None, None) if _div(shape[0], ms) else (None, None, None)
    if gparent == "moe" and parent == "router":
        return tuple(None for _ in shape)

    if gparent == "mamba" and last == "w":
        if parent in ("z_proj", "x_proj"):
            return (None, m) if _div(shape[1], ms) else (None, None)
        if parent == "dt_proj":
            return (None, m) if _div(shape[1], ms) else (None, None)
        if parent == "out_proj":
            return (m, None) if _div(shape[0], ms) else (None, None)
        if parent == "bc_proj":
            return (None, None)
    if parent == "mamba":
        if last == "conv_x_w":
            return (None, m) if _div(shape[1], ms) else (None, None)
        if last == "conv_x_b":
            return (m,) if _div(shape[0], ms) else (None,)
        if last in ("conv_bc_w", "conv_bc_b"):
            return tuple(None for _ in shape)
        if last in ("A_log", "D", "dt_bias"):
            return (m,) if _div(shape[0], ms) else (None,)
    if parent == "gate_norm" and last == "scale":
        return (m,) if _div(shape[0], ms) else (None,)

    # norms and anything unmatched: replicated
    return tuple(None for _ in shape)


def param_specs(params_shapes: Any, cfg: ModelConfig, ctx: DistContext) -> Any:
    ms = ctx.model_size

    def rule(path, leaf):
        names = _names(path)
        nlead = 1 if any(n in _STACKED_KEYS for n in names) else 0
        base = _param_rule(names, tuple(leaf.shape[nlead:]), cfg, ms)
        return P(*((None,) * nlead + tuple(base)))

    return jax.tree_util.tree_map_with_path(rule, params_shapes)


def opt_specs(
    opt_shapes: Any, p_specs: Any, cfg: ModelConfig, ctx: DistContext
) -> Any:
    """ZeRO-1: moments/masters get the param spec plus DP sharding on the
    first still-unsharded divisible dim."""
    bt = ctx.batch_size_total
    dp = ctx.batch_axes if len(ctx.batch_axes) > 1 else ctx.batch_axes[0]

    def zero(spec: P, leaf) -> P:
        elems = list(spec) + [None] * (len(leaf.shape) - len(spec))
        for i, (e, dim) in enumerate(zip(elems, leaf.shape)):
            if e is None and _div(dim, bt):
                elems[i] = dp
                break
        return P(*elems)

    out = {"step": P()}
    for key in ("m", "v", "master"):
        if key in opt_shapes:
            out[key] = jax.tree.map(zero, p_specs, opt_shapes[key])
    return out


def batch_specs(
    spec_dict: Dict[str, Tuple[Tuple[int, ...], Any]], ctx: DistContext
) -> Dict[str, P]:
    bt = ctx.batch_size_total
    out = {}
    for name, (shape, _) in spec_dict.items():
        batch = _dp(ctx.batch_axes) if _div(shape[0], bt) else None
        out[name] = P(batch, *([None] * (len(shape) - 1)))
    return out


def cache_specs(cache_shapes: Any, cfg: ModelConfig, ctx: DistContext) -> Any:
    """KV/SSM cache sharding for decode.

    KV: [(L,)? B, S, Hkv, D] — batch over DP; the S dim over model (and
    over DP too when the batch doesn't divide, e.g. one long sequence, B=1).
    SSM state: [(L,)? B, H, P, N] — batch over DP, heads over model.
    """
    bt = ctx.batch_size_total
    ms = ctx.model_size
    m = ctx.model_axis

    def rule(path, leaf):
        names = _names(path)
        last = names[-1]
        shape = leaf.shape
        if last in ("k_scale", "v_scale"):
            # [(L,)? B, S, Hkv] — shard like the cache minus the head dim
            lead = (None,) * (len(shape) - 3)
            b_dim, s_dim = shape[-3], shape[-2]
            batch = _dp(ctx.batch_axes) if _div(b_dim, bt) else None
            if batch is None and _div(s_dim, bt * ms):
                seq = tuple(ctx.batch_axes) + (m,)
            elif _div(s_dim, ms):
                seq = m
            else:
                seq = None
            return P(*lead, batch, seq, None)
        if last in ("k", "v"):
            lead = (None,) * (len(shape) - 4)
            b_dim, s_dim = shape[-4], shape[-3]
            batch = _dp(ctx.batch_axes) if _div(b_dim, bt) else None
            if batch is None and _div(s_dim, bt * ms):
                seq = tuple(ctx.batch_axes) + (m,)
            elif _div(s_dim, ms):
                seq = m
            else:
                seq = None
            return P(*lead, batch, seq, None, None)
        if last == "ssm":
            lead = (None,) * (len(shape) - 4)
            batch = _dp(ctx.batch_axes) if _div(shape[-4], bt) else None
            heads = m if _div(shape[-3], ms) else None
            return P(*lead, batch, heads, None, None)
        if last in ("conv_x", "conv_bc"):
            lead = (None,) * (len(shape) - 3)
            batch = _dp(ctx.batch_axes) if _div(shape[-3], bt) else None
            ch = m if _div(shape[-1], ms) else None
            return P(*lead, batch, None, ch)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(rule, cache_shapes)
