"""Pipeline parallelism over the pod axis (GPipe-style, shard_map).

At 2+ pods, the DCN between pods is the slow link — instead of extending
data parallelism across it (all-reducing full gradients over DCN every
step), the pod axis can carry PIPELINE stages: each pod owns a contiguous
slice of layers, activations flow pod→pod via ``collective_permute``
(activation tensors are microbatch-sized — orders of magnitude smaller
than gradients), and microbatches keep every pod busy outside the fill /
drain bubbles.

Mechanics (classic shard_map GPipe schedule):
  * stage parameters are stacked on a leading ``n_stages`` dim and sharded
    over the pipeline axis — inside shard_map each device holds its own
    stage's slice;
  * the loop runs ``n_micro + n_stages − 1`` ticks; on each tick a device
    runs its stage on the activation it holds, then the ring rotates
    (``ppermute`` stage i → i+1);
  * stage 0 injects a fresh microbatch each tick (while any remain); the
    last stage's outputs are collected on the final ticks;
  * bubble fraction = (n_stages − 1) / (n_micro + n_stages − 1).

This is the substrate; wiring a full arch through it is a config choice
(the default multi-pod layout keeps the pod axis in DP — see DESIGN.md §4).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,  # leaves stacked [n_stages, ...]
    x: jnp.ndarray,  # [n_micro, mb, ...] microbatched input
    mesh: jax.sharding.Mesh,
    axis: str = "pod",
) -> jnp.ndarray:
    """Run ``x``'s microbatches through the stage pipeline; returns
    [n_micro, mb, ...] outputs (as produced by the LAST stage)."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro >= n_stages, "need ≥ n_stages microbatches to fill"

    def body(params, xs):
        # params: this device's stage slice — shard_map keeps the sharded
        # leading dim at size 1; strip it
        params = jax.tree.map(lambda p: p[0], params)
        # xs: full microbatch stream, replicated
        idx = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype)  # activation held by this stage
        outs = jnp.zeros((n_micro, *mb_shape), xs.dtype)

        def tick(carry, t):
            state, outs = carry
            # stage 0 injects microbatch t (while any remain)
            inject = jnp.where(t < n_micro, t, n_micro - 1)
            state = jnp.where(idx == 0, xs[inject], state)
            # every stage applies its slice
            y = stage_fn(params, state)
            # last stage emits microbatch (t - (n_stages-1)) when valid
            emit_t = t - (n_stages - 1)
            valid = (emit_t >= 0) & (idx == n_stages - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, y[None], jnp.maximum(emit_t, 0), axis=0
                ),
                lambda o: o,
                outs,
            )
            # rotate the ring: stage i → i+1 (last wraps to 0, ignored)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(
            tick, (state, outs), jnp.arange(n_ticks)
        )
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    in_x = P()  # microbatch stream replicated across the pipeline axis
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec, in_x),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
