"""Mamba2 SSD (state-space duality) Pallas TPU kernel.

The SSD computation per (batch, head) is: within a chunk of length Q, a
decay-masked quadratic form (MXU-friendly — this is the "duality" with
attention); across chunks, a linear state recurrence.

TPU mapping:
  * grid = (B, H, n_chunks) with the chunk dimension innermost; TPU Pallas
    executes the grid sequentially per core, so the running state [P, N]
    lives in VMEM scratch and is carried across chunk steps — the
    recurrence costs no HBM traffic at all (on GPU this is a separate
    inter-block scan kernel); it starts from the caller's initial state;
  * each chunk step loads x[Q,P], cumsum(dA)[Q], B[Q,N], C[Q,N] into VMEM, runs
    three MXU matmuls (C·Bᵀ, (L∘S)·X, B̃ᵀ·X) and one state update;
  * everything accumulates in fp32.

The wrapper in ops.py reshapes the model's [B, S, H, ...] layout into the
kernel's head-major chunked layout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, contract):
    """fp32 matmul at full precision: Mosaic's default is one bf16 pass,
    which left the fp32 kernel 6e-3 (normalized) off the fp32 reference
    on a v5e chip."""
    return jax.lax.dot_general(
        a,
        b,
        (contract, ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _ssd_kernel(
    x_ref,  # [1, 1, 1, Q, P]
    cum_col_ref,  # [1, 1, 1, Q, 1] — within-chunk cumsum of dA, as a column
    cum_row_ref,  # [1, 1, 1, 1, Q] — the same cumsum, as a row
    b_ref,  # [1, 1, 1, Q, N]
    c_ref,  # [1, 1, 1, Q, N]
    init_ref,  # [1, 1, P, N] — state entering the first chunk
    y_ref,  # [1, 1, 1, Q, P]
    state_out_ref,  # [1, 1, P, N] — final state per (b, h)
    state_scr,  # VMEM [P, N] fp32
    *,
    chunk: int,
    num_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = init_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # [Q, P]
    cum_c = cum_col_ref[0, 0, 0].astype(jnp.float32)  # [Q, 1]
    cum_r = cum_row_ref[0, 0, 0].astype(jnp.float32)  # [1, Q]
    b = b_ref[0, 0, 0].astype(jnp.float32)  # [Q, N]
    c = c_ref[0, 0, 0].astype(jnp.float32)  # [Q, N]

    # decay matrix L[i,j] = exp(cum_i - cum_j) for i >= j else 0
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    )
    L = jnp.where(tri, jnp.exp(cum_c - cum_r), 0.0)
    # intra-chunk: y_diag = (C Bᵀ ∘ L) X
    scores = _dot(c, b, ((1,), (1,))) * L  # [Q, Q]
    y = _dot(scores, x, ((1,), (0,)))  # [Q, P]
    # carried-in state: y_off = (C state^T) ∘ exp(cum)
    state = state_scr[...]  # [P, N]
    y_off = _dot(c, state, ((1,), (1,)))  # [Q, P]
    y = y + y_off * jnp.exp(cum_c)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    # state update: state' = state * exp(cum_last) + Σ_q exp(cum_last-cum_q) x_q ⊗ b_q
    cum_last = cum_c[chunk - 1 :, :]  # [1, 1]
    xw = x * jnp.exp(cum_last - cum_c)  # [Q, P]
    chunk_state = _dot(xw, b, ((0,), (0,)))  # [P, N]
    # a 0-d value splats onto [P, N]; Mosaic cannot broadcast the [1, 1]
    # slice along sublanes and lanes at once
    total_decay = jnp.exp(jnp.sum(cum_last))
    new_state = state * total_decay + chunk_state
    state_scr[...] = new_state

    @pl.when(ic == num_chunks - 1)
    def _finish():
        state_out_ref[0, 0] = new_state


def ssd_scan_fwd(
    x: jnp.ndarray,  # [B, H, C, Q, P] (dt-weighted inputs)
    da: jnp.ndarray,  # [B, H, C, Q]   (dt * A)
    b: jnp.ndarray,  # [B, H, C, Q, N]
    c: jnp.ndarray,  # [B, H, C, Q, N]
    initial_state: Optional[jnp.ndarray] = None,  # [B, H, P, N]
    *,
    interpret: bool = False,
):
    bsz, h, nc, q, p = x.shape
    n = b.shape[-1]
    if initial_state is None:
        initial_state = jnp.zeros((bsz, h, p, n), jnp.float32)
    # the within-chunk cumsum runs in XLA; the kernel gets it in both
    # orientations so that every block's last two dims are (Q, 1) / (1, Q)
    cum = jnp.cumsum(da.astype(jnp.float32), axis=-1)
    grid = (bsz, h, nc)
    kernel = functools.partial(_ssd_kernel, chunk=q, num_chunks=nc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda i, j, k_: (i, j, k_, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, 1), lambda i, j, k_: (i, j, k_, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, q), lambda i, j, k_: (i, j, k_, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), lambda i, j, k_: (i, j, k_, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), lambda i, j, k_: (i, j, k_, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j, k_: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda i, j, k_: (i, j, k_, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j, k_: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, nc, q, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, cum[..., None], cum[..., None, :], b, c, initial_state)
