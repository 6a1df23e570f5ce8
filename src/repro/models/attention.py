"""Attention: GQA with causal / sliding-window / bidirectional / cross modes,
prefill and single-token decode paths.

The jnp implementation here is the *reference semantics*; the Pallas
flash-attention kernels in ``repro.kernels`` implement the same math with
VMEM tiling and are validated against this module (tests sweep shapes &
dtypes).  Model code selects the implementation via ``impl=`` — dry-runs use
"ref" (XLA fuses it; keeps HLO compact at 512 devices), TPU runs would use
"flash".
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import apply_rope, dense, dt, init_dense

NEG_INF = -2.0**30


# ---------------------------------------------------------------- params
def init_attention(rng, cfg: ModelConfig, d_in: Optional[int] = None) -> Dict:
    """q/k/v from ``d_in`` (default d_model) and o back to d_model."""
    d, hd = cfg.d_model, cfg.head_dim_
    d_in = d_in or d
    pdt = dt(cfg.param_dtype)
    ks = jax.random.split(rng, 4)
    return {
        "q": init_dense(ks[0], d_in, cfg.n_heads * hd, pdt),
        "k": init_dense(ks[1], d_in, cfg.n_kv_heads * hd, pdt),
        "v": init_dense(ks[2], d_in, cfg.n_kv_heads * hd, pdt),
        "o": init_dense(ks[3], cfg.n_heads * hd, d, pdt),
    }


# ------------------------------------------------------------- core math
def gqa_attention(
    q: jnp.ndarray,  # [B, Sq, Hq, D]
    k: jnp.ndarray,  # [B, Sk, Hkv, D]
    v: jnp.ndarray,  # [B, Sk, Hkv, D]
    positions_q: jnp.ndarray,  # [B, Sq]
    positions_k: jnp.ndarray,  # [B, Sk]
    causal: bool = True,
    window: Optional[int] = None,
    kv_valid: Optional[jnp.ndarray] = None,  # [B, Sk] bool
    scale: Optional[float] = None,  # of the scores; default D**-0.5
) -> jnp.ndarray:
    """Grouped-query attention with fp32 softmax; returns [B, Sq, Hq, D]."""
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    q = q.reshape(b, sq, hkv, g, d)
    scale = d**-0.5 if scale is None else scale
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    mask = jnp.ones((b, sq, sk), dtype=bool)
    dpos = positions_q[:, :, None] - positions_k[:, None, :]
    if causal:
        mask &= dpos >= 0
    if window is not None:
        mask &= dpos < window
    if kv_valid is not None:
        mask &= kv_valid[:, None, :]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, hq, d)


# ------------------------------------------------------------ block apply
def attention_block(
    params: Dict,
    x: jnp.ndarray,  # [B, S, d_model]
    positions: jnp.ndarray,  # [B, S]
    cfg: ModelConfig,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    rope_theta: Optional[float] = None,
    cache: Optional[Dict] = None,
    cache_index: Optional[jnp.ndarray] = None,
    impl: str = "ref",
    scale: Optional[float] = None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Full attention sub-block: qkv proj → rope → attention → out proj.

    x is [B, S, d_in] for the projections' input width.  ``scale`` of the
    scores (default head_dim**-0.5) may be set on the reference path only.
    With ``cache``/``cache_index``: single-token decode — x is [B, 1, d_in],
    the KV cache is updated in place (functionally) at ``cache_index``.
    """
    assert scale is None or impl == "ref", f"impl {impl!r} scales by head_dim**-0.5"
    b, s, _ = x.shape
    hd = cfg.head_dim_
    theta = rope_theta if rope_theta is not None else cfg.rope_theta
    q = dense(x, params["q"]).reshape(b, s, cfg.n_heads, hd)
    k = dense(x, params["k"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = dense(x, params["v"]).reshape(b, s, cfg.n_kv_heads, hd)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)

    new_cache = None
    if cache is not None:
        assert cache_index is not None
        # decode: write k/v at cache_index (ring buffer — SWA caches are
        # allocated at window length, so the write index wraps; full-length
        # caches hit the identity case of the same formula)
        s_cache = cache["k"].shape[1]
        write_idx = cache_index % s_cache
        quantized = cache["k"].dtype == jnp.int8
        if quantized:
            # per-token-per-head symmetric int8 (scales stored alongside)
            def q8(t):
                scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1) / 127.0
                scale = jnp.maximum(scale, 1e-8)
                q = jnp.clip(
                    jnp.round(t.astype(jnp.float32) / scale[..., None]),
                    -127, 127,
                ).astype(jnp.int8)
                return q, scale

            k8, k_s = q8(k)
            v8, v_s = q8(v)
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k8, write_idx, axis=1
            )
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v8, write_idx, axis=1
            )
            cks = jax.lax.dynamic_update_slice_in_dim(
                cache["k_scale"], k_s.astype(cache["k_scale"].dtype), write_idx, axis=1
            )
            cvs = jax.lax.dynamic_update_slice_in_dim(
                cache["v_scale"], v_s.astype(cache["v_scale"].dtype), write_idx, axis=1
            )
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
            cdt = dt(cfg.compute_dtype)
            ck = (ck.astype(jnp.float32) * cks.astype(jnp.float32)[..., None]).astype(cdt)
            cv = (cv.astype(jnp.float32) * cvs.astype(jnp.float32)[..., None]).astype(cdt)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), write_idx, axis=1
            )
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), write_idx, axis=1
            )
            new_cache = {"k": ck, "v": cv}
        sk = ck.shape[1]
        # slot j holds absolute position pos - ((pos - j) mod s_cache);
        # never-written slots resolve to negative positions → masked.
        slots = jnp.arange(sk)[None, :]
        pos_now = cache_index + s - 1
        positions_k = pos_now - jnp.mod(pos_now - slots, s_cache)
        positions_k = jnp.broadcast_to(positions_k, (b, sk)).astype(jnp.int32)
        kv_valid = positions_k >= 0
        if impl == "flash" and s == 1:
            from ..kernels.decode_attention import ops as dec_ops

            out = dec_ops.decode_attention(
                q, ck, cv, positions[:, 0], window=window
            )
        else:
            out = gqa_attention(
                q,
                ck,
                cv,
                positions,
                positions_k,
                causal=causal,
                window=window,
                kv_valid=kv_valid,
                scale=scale,
            )
    else:
        if impl == "flash":
            from ..kernels.flash_attention import ops as fa_ops

            out = fa_ops.flash_attention(
                q, k, v, causal=causal, window=window
            )
        elif impl == "blocked":
            from .blocked_attention import blocked_attention

            out = blocked_attention(
                q, k, v, positions, positions, causal, window, 1024, False
            )
        else:
            out = gqa_attention(
                q, k, v, positions, positions, causal=causal, window=window,
                scale=scale,
            )
    out = dense(out.reshape(b, s, cfg.n_heads * hd), params["o"])
    return out, new_cache


def cross_attention_block(
    params: Dict,
    x: jnp.ndarray,  # [B, Sq, d]
    enc_kv: Tuple[jnp.ndarray, jnp.ndarray],  # precomputed K,V [B, Sk, Hkv, D]
    cfg: ModelConfig,
    impl: str = "ref",
) -> jnp.ndarray:
    """Encoder-decoder cross attention (whisper); enc K/V precomputed once."""
    b, s, _ = x.shape
    hd = cfg.head_dim_
    q = dense(x, params["q"]).reshape(b, s, cfg.n_heads, hd)
    k, v = enc_kv
    sk = k.shape[1]
    pos_q = jnp.zeros((b, s), dtype=jnp.int32)
    pos_k = jnp.zeros((b, sk), dtype=jnp.int32)
    if impl == "blocked" and s > 1:
        from .blocked_attention import blocked_attention

        out = blocked_attention(q, k, v, pos_q, pos_k, False, None, 1024, False)
    else:
        out = gqa_attention(q, k, v, pos_q, pos_k, causal=False, window=None)
    return dense(out.reshape(b, s, cfg.n_heads * hd), params["o"])


def precompute_cross_kv(
    params: Dict, enc_out: jnp.ndarray, cfg: ModelConfig
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, sk, _ = enc_out.shape
    hd = cfg.head_dim_
    k = dense(enc_out, params["k"]).reshape(b, sk, cfg.n_kv_heads, hd)
    v = dense(enc_out, params["v"]).reshape(b, sk, cfg.n_kv_heads, hd)
    return k, v


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_len: int, n_layers: int, dtype=None
) -> Dict:
    """Per-layer KV cache pytree: leaves [L, B, max_len, Hkv, D]."""
    dtype = dtype or dt(cfg.compute_dtype)
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim_)
    return {"k": jnp.zeros(shape, dtype=dtype), "v": jnp.zeros(shape, dtype=dtype)}
