"""Jit'd wrapper: model layout [B, S, H, ...] → kernel chunk layout."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .ssd_scan import ssd_scan_fwd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(
    x: jnp.ndarray,  # [B, S, H, P] (dt-weighted)
    da: jnp.ndarray,  # [B, S, H]
    b: jnp.ndarray,  # [B, S, H, N]
    c: jnp.ndarray,  # [B, S, H, N]
    *,
    chunk: int = 256,
    initial_state: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q

    def to_kernel(t, feat):
        # [B, S, H, F] -> [B, H, C, Q, F]
        return t.reshape(bsz, nc, q, h, feat).transpose(0, 3, 1, 2, 4)

    xk = to_kernel(x, p)
    dak = da.reshape(bsz, nc, q, h).transpose(0, 3, 1, 2)
    bk = to_kernel(b, n)
    ck = to_kernel(c, n)
    y, final_state = ssd_scan_fwd(
        xk, dak, bk, ck, initial_state, interpret=interpret
    )
    y = y.transpose(0, 2, 3, 1, 4).reshape(bsz, s, h, p)
    return y, final_state
