"""Single-token decode attention Pallas kernel.

Decode attention is memory-bound: one query vector per (batch, head) streams
the whole KV cache from HBM.  The kernel tiles the KV sequence into VMEM
blocks (grid innermost dim) and keeps the online-softmax state in VMEM
scratch, so each KV byte is read exactly once — the roofline-optimal
schedule for this op.

Masking supports the decode cases the model zoo needs:
  * validity: only cache positions ≤ current position contribute,
  * sliding window: positions < pos-window+1 are masked (SWA decode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30


def _decode_kernel(
    pos_ref,  # SMEM [B] int32 — current position per batch row (prefetched)
    q_ref,  # [1, 1, G, D] — the G query heads sharing one KV head
    k_ref,  # [1, bk, D]
    v_ref,  # [1, bk, D]
    o_ref,  # [1, 1, G, D]
    m_scr,  # VMEM [G, 1]
    l_scr,  # VMEM [G, 1]
    acc_scr,  # VMEM [G, D]
    *,
    scale: float,
    window: Optional[int],
    block_k: int,
    num_k_blocks: int,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[pl.program_id(0)]
    q = q_ref[0, 0].astype(jnp.float32)  # [G, D]
    k = k_ref[0].astype(jnp.float32)  # [bk, D]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, bk]
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos <= pos
    if window is not None:
        mask &= k_pos > pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype
        )


def decode_attention_fwd(
    q: jnp.ndarray,  # [B, Hq, D]
    k: jnp.ndarray,  # [B, Hkv, Sk, D]
    v: jnp.ndarray,
    positions: jnp.ndarray,  # [B] int32 current positions
    *,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    assert sk % block_k == 0, (sk, block_k)
    nk = sk // block_k
    # one grid row per KV head: its G query heads form the block's last two
    # dims (G, D), which span the whole array — legal on the 8x128 tiling
    # for any G — and each KV block is read once per group, not per head
    grid = (b, hkv, nk)
    kernel = functools.partial(
        _decode_kernel,
        scale=sm_scale if sm_scale is not None else d**-0.5,
        window=window,
        block_k=block_k,
        num_k_blocks=nk,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, ik, pos: (b_, h, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b_, h, ik, pos: (b_ * hkv + h, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda b_, h, ik, pos: (b_ * hkv + h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h, ik, pos: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    qg = q.reshape(b, hkv, g, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(positions.astype(jnp.int32), qg, kf, vf)
    return out.reshape(b, hq, d)
