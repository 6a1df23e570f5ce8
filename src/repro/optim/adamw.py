"""AdamW from scratch (no optax), mixed-precision aware.

Design for scale:
  * bf16 model params + fp32 master copies and fp32 (m, v) moments held in
    the optimizer state;
  * the optimizer state is what gets ZeRO-sharded over the data axis (see
    ``repro.distributed.sharding_rules``): each data shard owns 1/DP of the
    master/m/v, updates it, and the bf16 params are re-formed from the
    masters (GSPMD renders this as reduce-scatter(grads) → local update →
    all-gather(params) — the ZeRO-1 schedule);
  * everything is a pure function over pytrees: ``init`` is
    eval_shape-safe, so the optimizer state's shapes and bytes are known
    with zero allocation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    #: keep fp32 master copies when params are lower precision
    mixed_precision: bool = True


def init_adamw(params: Any, cfg: AdamWConfig = AdamWConfig()) -> Dict:
    def zeros_f32(p):
        return jnp.zeros(p.shape, dtype=jnp.float32)

    state = {
        "step": jnp.zeros((), dtype=jnp.int32),
        "m": jax.tree.map(zeros_f32, params),
        "v": jax.tree.map(zeros_f32, params),
    }
    if cfg.mixed_precision:
        state["master"] = jax.tree.map(
            lambda p: p.astype(jnp.float32), params
        )
    return state


def adamw_update(
    grads: Any,
    state: Dict,
    params: Any,
    lr: jnp.ndarray,
    cfg: AdamWConfig = AdamWConfig(),
) -> Tuple[Any, Dict]:
    step = state["step"] + 1
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)
    masters = state.get("master", params)

    def upd(g, m, v, master):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / bc1
        vhat = v / bc2
        new_master = master.astype(jnp.float32) - lr * (
            mhat / (jnp.sqrt(vhat) + cfg.eps)
            + cfg.weight_decay * master.astype(jnp.float32)
        )
        return m, v, new_master

    flat_g, treedef = jax.tree.flatten(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    flat_ma = treedef.flatten_up_to(masters)
    out = [upd(g, m, v, ma) for g, m, v, ma in zip(flat_g, flat_m, flat_v, flat_ma)]
    new_m = treedef.unflatten([o[0] for o in out])
    new_v = treedef.unflatten([o[1] for o in out])
    new_master = treedef.unflatten([o[2] for o in out])
    new_params = jax.tree.map(
        lambda ma, p: ma.astype(p.dtype), new_master, params
    )
    new_state = {"step": step, "m": new_m, "v": new_v}
    if "master" in state:
        new_state["master"] = new_master
    return new_params, new_state


def global_norm(tree: Any) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def clip_by_global_norm(grads: Any, max_norm: float) -> Tuple[Any, jnp.ndarray]:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), norm
