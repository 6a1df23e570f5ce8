"""Seeded random weights, made on the device in one jitted call.

The benchmark, not the program, draws the weights: the program gives only
the shapes and dtypes of its parameter tree (``jax.eval_shape`` of its
``init``), and every value comes from ``--seed`` by the rules below, keyed
by the leaf's name.  The same seed gives the same tree bit for bit, so the
reference can draw the weights again after the program's state is freed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: published Mamba2 initialisation ranges (mamba_ssm ``Mamba2.__init__``)
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
DT_FLOOR = 1e-4


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _draw(key, path: tuple, shape, dtype):
    """One leaf's values; ``path`` is its tuple of dict keys."""
    name = path[-1]
    f32 = jnp.float32
    if name == "table":  # input embedding (and tied output head)
        x = jax.random.normal(key, shape, f32) * 0.02
    elif name == "scale":  # RMSNorm weight, stored as (weight - 1)
        x = jax.random.normal(key, shape, f32) * 0.1
    elif name in ("conv_x_w", "conv_bc_w"):  # [.., width, channels]
        x = jax.random.normal(key, shape, f32) * shape[-2] ** -0.5
    elif name in ("conv_x_b", "conv_bc_b"):
        x = jax.random.uniform(key, shape, f32, -0.5, 0.5)
    elif name == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, f32, *A_RANGE))
    elif name == "dt_bias":  # inverse softplus of a log-uniform dt
        lo, hi = math.log(DT_RANGE[0]), math.log(DT_RANGE[1])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, f32, lo, hi)), DT_FLOOR)
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "D":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    elif len(shape) >= 2:  # a projection [.., fan_in, fan_out]
        x = jax.random.normal(key, shape, f32) * shape[-2] ** -0.5
    else:
        raise ValueError(f"no initialisation rule for leaf {'/'.join(path)}")
    return x.astype(dtype)


def make_weights(shapes, seed: int):
    """The parameter tree of ``shapes`` (a pytree of ShapeDtypeStruct),
    filled from ``seed`` on the default device in one jitted call."""
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree_util.tree_structure(shapes)

    def build(key):
        out = []
        for i, (kp, s) in enumerate(leaves):
            path = tuple(k.key for k in kp)
            out.append(_draw(jax.random.fold_in(key, i), path, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))


def count_mismatches(got, want) -> int:
    """Elements of ``got`` that differ from ``want`` bit for bit (leaves
    compared by path; a missing leaf or a shape or dtype change counts all
    of its elements)."""
    gl = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    bad = 0
    for kp, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        g = gl.get(kp)
        if g is None or g.shape != w.shape or g.dtype != w.dtype:
            bad += int(w.size)
            continue
        bits = jnp.dtype(f"uint{8 * w.dtype.itemsize}")
        diff = jax.lax.bitcast_convert_type(g, bits) != jax.lax.bitcast_convert_type(w, bits)
        bad += int(jnp.sum(diff))
    return bad
