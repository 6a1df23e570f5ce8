"""Attention: GQA with causal / sliding-window / bidirectional / cross modes,
prefill and single-token decode paths.

This jnp code is the one path the model runs, and XLA fuses it.  The
Pallas kernels in ``repro.kernels`` implement the same math with VMEM
tiling; ``tests/test_kernels.py`` checks ``flash_attention`` against
``gqa_attention`` and ``decode_attention`` against
``decode_gqa_attention``.
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


def decode_gqa_attention(
    q: jnp.ndarray,  # [B, 1, Hq, D]
    ck: jnp.ndarray,  # [B, Sk, Hkv, D]: the cache's slots, read only
    cv: jnp.ndarray,  # [B, Sk, Hkv, D]
    k: jnp.ndarray,  # [B, 1, Hkv, D]: the token's own key and value
    v: jnp.ndarray,  # [B, 1, Hkv, D]
    seen: jnp.ndarray,  # [B, Sk] bool: the slots the token attends to
    scale: Optional[float] = None,  # of the scores; default D**-0.5
) -> jnp.ndarray:
    """``gqa_attention`` of one token over the cache's ``seen`` slots and
    its own key, without writing the key into the cache first: one fp32
    softmax over both score sets, then p_cache·V_cache + p_token·v_token.
    Returns [B, 1, Hq, D]."""
    b, _, hq, d = q.shape
    hkv = ck.shape[2]
    q = q.reshape(b, 1, hkv, hq // hkv, d).astype(jnp.float32)
    scale = d**-0.5 if scale is None else scale

    def scores(keys):
        return jnp.einsum("bqhgd,bkhd->bhgqk", q, keys.astype(jnp.float32)) * scale

    old = jnp.where(seen[:, None, None, None, :], scores(ck), NEG_INF)
    new = scores(k)
    top = jnp.maximum(jnp.max(old, axis=-1, keepdims=True), new)
    e_old, e_new = jnp.exp(old - top), jnp.exp(new - top)
    total = jnp.sum(e_old, axis=-1, keepdims=True) + e_new

    def weigh(e, values):
        p = (e / total).astype(values.dtype)
        return jnp.einsum(
            "bhgqk,bkhd->bqhgd", p, values, preferred_element_type=jnp.float32
        )

    out = weigh(e_old, cv) + weigh(e_new, v)
    return out.astype(cv.dtype).reshape(b, 1, hq, d)


def seen_slots(
    positions: jnp.ndarray,  # [B, 1]: the token's position
    cache_index: jnp.ndarray,  # scalar: the step's write position
    s_cache: int,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """[B, s_cache] bool: the cache slots a decoded token attends to.

    Slot j holds absolute position pos - ((pos - j) mod s_cache);
    never-written slots resolve to negative positions, and slot
    pos % s_cache holds nothing or the position this token replaces: both
    masked."""
    slots = jnp.arange(s_cache)[None, :]
    positions_k = cache_index - jnp.mod(cache_index - slots, s_cache)
    seen = (positions_k >= 0) & (slots != cache_index % s_cache)
    if window is not None:
        seen &= positions[:, :1] - positions_k < window
    return jnp.broadcast_to(seen, (positions.shape[0], s_cache))


def _q8(t: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token-per-head symmetric int8 and its fp32 scales."""
    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(
        jnp.round(t.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _dq8(t: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (t.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]).astype(dtype)


def write_token(
    cache: jnp.ndarray, token: jnp.ndarray, cache_index: jnp.ndarray, axis: int
) -> jnp.ndarray:
    """``cache`` with ``token`` (size 1 along ``axis``, the slot axis) at slot
    ``cache_index % slots``: SWA caches are ring buffers of window length,
    full-length caches hit the identity case of the same formula."""
    slot = cache_index % cache.shape[axis]
    return jax.lax.dynamic_update_slice_in_dim(cache, token, slot, axis=axis)


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
    scale: Optional[float] = None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Full attention sub-block: qkv proj → rope → attention → out proj.

    x is [B, S, d_in] for the projections' input width; ``scale`` of the
    scores defaults to head_dim**-0.5.
    With ``cache``/``cache_index``: single-token decode — x is [B, 1, d_in]
    and the returned cache is the token's own K and V, [B, 1, Hkv, D] (int8
    and its scales on a quantized cache), which ``write_token`` puts at
    ``cache_index`` in the caller's cache.
    """
    b, s, _ = x.shape
    hd = cfg.head_dim_
    theta = rope_theta if rope_theta is not None else cfg.rope_theta
    q = dense(x, params["q"]).reshape(b, s, cfg.n_heads, hd)
    k = dense(x, params["k"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = dense(x, params["v"]).reshape(b, s, cfg.n_kv_heads, hd)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)

    new_kv = None
    if cache is not None:
        assert cache_index is not None and s == 1, "the cache path decodes one token"
        # the cache is read, never written here: the layer hands back the
        # token's K and V (as the cache holds them) for the caller to write
        # at slot cache_index % slots in place (``write_token``)
        s_cache = cache["k"].shape[1]
        if cache["k"].dtype == jnp.int8:
            k8, k_s = _q8(k)
            v8, v_s = _q8(v)
            new_kv = {
                "k": k8,
                "v": v8,
                "k_scale": k_s.astype(cache["k_scale"].dtype),
                "v_scale": v_s.astype(cache["v_scale"].dtype),
            }
            cdt = dt(cfg.compute_dtype)
            ck = _dq8(cache["k"], cache["k_scale"], cdt)
            cv = _dq8(cache["v"], cache["v_scale"], cdt)
            k = _dq8(k8, new_kv["k_scale"], cdt)
            v = _dq8(v8, new_kv["v_scale"], cdt)
        else:
            new_kv = {"k": k.astype(cache["k"].dtype), "v": v.astype(cache["v"].dtype)}
            ck, cv = cache["k"], cache["v"]
            k, v = new_kv["k"], new_kv["v"]
        seen = seen_slots(positions, cache_index, s_cache, window)
        out = decode_gqa_attention(q, ck, cv, k, v, seen, scale=scale)
    else:
        out = gqa_attention(
            q, k, v, positions, positions, causal=causal, window=window,
            scale=scale,
        )
    out = dense(out.reshape(b, s, cfg.n_heads * hd), params["o"])
    return out, new_kv


def cross_attention_block(
    params: Dict,
    x: jnp.ndarray,  # [B, Sq, d]
    enc_kv: Tuple[jnp.ndarray, jnp.ndarray],  # precomputed K,V [B, Sk, Hkv, D]
    cfg: ModelConfig,
) -> jnp.ndarray:
    """Encoder-decoder cross attention (whisper); enc K/V precomputed once."""
    b, s, _ = x.shape
    hd = cfg.head_dim_
    q = dense(x, params["q"]).reshape(b, s, cfg.n_heads, hd)
    k, v = enc_kv
    sk = k.shape[1]
    pos_q = jnp.zeros((b, s), dtype=jnp.int32)
    pos_k = jnp.zeros((b, sk), dtype=jnp.int32)
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
