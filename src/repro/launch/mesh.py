"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax
import; tests see the real single device).
"""

from __future__ import annotations

from typing import Tuple


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def mesh_batch_axes(mesh) -> Tuple[str, ...]:
    """DP axes for a production mesh ('pod' participates in DP)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for unit tests (requires >= data*model fake devices)."""
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: GSPMD propagates the shardings
    that the rules in ``distributed.sharding_rules`` pin."""
    import jax

    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )
