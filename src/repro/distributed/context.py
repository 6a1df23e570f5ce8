"""Distribution context: how model code learns about the mesh.

Model code stays mesh-agnostic; a caller that shards the model activates a
:class:`DistContext` (mesh + axis roles) around tracing.  No caller in the
tree does yet, so on every path that runs ``constrain`` is a no-op.  Inside model code:

  * ``constrain(x, "residual")`` — applies a named activation sharding
    constraint if a context is active (no-op otherwise, so CPU smoke tests
    and single-device runs are untouched);
  * ``current()`` — lets the MoE layer pick the expert-parallel shard_map
    path when a mesh with a model axis is active.

The context is a *trace-time* construct (contextvar) — nothing here touches
devices.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class DistContext:
    mesh: jax.sharding.Mesh
    batch_axes: Tuple[str, ...]  # e.g. ("data",) or ("pod", "data")
    model_axis: str = "model"
    #: use the explicit expert-parallel shard_map path for MoE layers
    ep: bool = True
    #: shard the residual stream's sequence dim over the model axis (SP)
    sequence_parallel: bool = True

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def batch_size_total(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))


_CTX: contextvars.ContextVar[Optional[DistContext]] = contextvars.ContextVar(
    "repro_dist_ctx", default=None
)


def current() -> Optional[DistContext]:
    return _CTX.get()


@contextlib.contextmanager
def distribution(ctx: Optional[DistContext]):
    token = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        _CTX.reset(token)


def _divides(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def constrain(x, name: str):
    """Apply a named activation-sharding constraint (no-op without ctx)."""
    ctx = current()
    if ctx is None:
        return x
    spec = _activation_spec(name, x.shape, ctx)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.sharding(*spec))


def _activation_spec(name: str, shape, ctx: DistContext):
    bt = ctx.batch_size_total
    ms = ctx.model_size
    batch = ctx.batch_axes if _divides(shape[0], bt) else None
    if name == "residual":
        # [B, S, d]: batch over DP axes; seq over model (SP) when it divides
        seq = (
            ctx.model_axis
            if ctx.sequence_parallel and len(shape) >= 2 and _divides(shape[1], ms)
            else None
        )
        return (batch, seq, None)
    if name == "logits":
        # [B, S, V]: vocab over model
        v = ctx.model_axis if _divides(shape[-1], ms) else None
        return (batch,) + (None,) * (len(shape) - 2) + (v,)
    if name == "batch":
        return (batch,) + (None,) * (len(shape) - 1)
    return None
