"""Shared pure-JAX layer primitives (no flax — params are plain pytrees).

Conventions:
  * params are nested dicts of jnp arrays; every layer has ``init_*`` and a
    pure apply function;
  * activations compute in ``cfg.compute_dtype`` (bf16 by default), softmax
    and norm statistics in fp32;
  * init functions are cheap and `jax.eval_shape`-safe (shape checks and
    lowering never materialize full-size weights).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig


def dt(name: str):
    return jnp.dtype(name)


# ------------------------------------------------------------------- norms
def init_rmsnorm(d: int, dtype) -> Dict:
    return {"scale": jnp.zeros((d,), dtype=dtype)}


def rms_norm(
    x: jnp.ndarray, params: Dict, eps: float = 1e-6, groups: int = 1
) -> jnp.ndarray:
    """RMSNorm with (1+scale) parameterization (gemma-style, zero-init);
    with ``groups``, each of that many equal slices of the last axis is
    normalized on its own (Mamba2's gated norm with n_groups > 1)."""
    xf = x.astype(jnp.float32)
    if groups > 1:
        xf = xf.reshape(*x.shape[:-1], groups, x.shape[-1] // groups)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = (xf * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    out = normed * (1.0 + params["scale"].astype(jnp.float32))
    return out.astype(x.dtype)


# -------------------------------------------------------------------- rope
def rope_frequencies(
    head_dim: int, positions: jnp.ndarray, theta: float
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions: [...]; returns cos/sin of shape [..., head_dim//2]."""
    half = head_dim // 2
    freq = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float
) -> jnp.ndarray:
    """x: [B, S, H, D]; positions: [B, S] (or [S])."""
    b, s, h, d = x.shape
    cos, sin = rope_frequencies(d, positions, theta)  # [B, S, D/2]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ----------------------------------------------------------------- linear
def init_dense(
    rng, d_in: int, d_out: int, dtype, scale: Optional[float] = None
) -> Dict:
    scale = scale if scale is not None else d_in**-0.5
    w = jax.random.normal(rng, (d_in, d_out), dtype=jnp.float32) * scale
    return {"w": w.astype(dtype)}


def dense(x: jnp.ndarray, params: Dict) -> jnp.ndarray:
    return x @ params["w"].astype(x.dtype)


# -------------------------------------------------------------------- mlp
def init_mlp(rng, cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    pdt = dt(cfg.param_dtype)
    ks = jax.random.split(rng, 3)
    if cfg.mlp_type != "gelu":
        return {
            "gate": init_dense(ks[0], d, f, pdt),
            "up": init_dense(ks[1], d, f, pdt),
            "down": init_dense(ks[2], f, d, pdt, scale=f**-0.5),
        }
    return {
        "up": init_dense(ks[0], d, f, pdt),
        "down": init_dense(ks[1], f, d, pdt, scale=f**-0.5),
    }


def init_adapter(rng, d: int, rank: int, f: int, dtype) -> Dict:
    """A rank-``rank`` term for a gated MLP's gate and up projections:
    x → (x·down)·gate and (x·down)·up."""
    ks = jax.random.split(rng, 3)
    return {
        "down": init_dense(ks[0], d, rank, dtype),
        "gate": init_dense(ks[1], rank, f, dtype),
        "up": init_dense(ks[2], rank, f, dtype),
    }


def mlp(
    x: jnp.ndarray, params: Dict, cfg: ModelConfig, adapter: Optional[Dict] = None
) -> jnp.ndarray:
    """The MLP; ``adapter`` (gated kinds) adds its low-rank term to the gate
    and up projections (Zamba2's per-site adapter)."""
    if cfg.mlp_type == "gelu":
        return dense(jax.nn.gelu(dense(x, params["up"])), params["down"])
    low = dense(x, adapter["down"]) if adapter is not None else None

    def proj(name):
        y = dense(x, params[name])
        return y if low is None else y + dense(low, adapter[name])

    if cfg.mlp_type == "swiglu":
        act = jax.nn.silu
    else:  # geglu: exact (erf) GELU
        act = functools.partial(jax.nn.gelu, approximate=False)
    return dense(act(proj("gate")) * proj("up"), params["down"])


# -------------------------------------------------------------- embeddings
def padded_vocab(vocab_size: int, multiple: int = 256) -> int:
    """Vocab rows padded for clean sharding (SPMD rejects uneven input
    shardings) and lane alignment.  Padded logit columns are sliced off in
    ``unembed`` so the softmax never sees them."""
    return -(-vocab_size // multiple) * multiple


def init_embedding(rng, cfg: ModelConfig) -> Dict:
    pdt = dt(cfg.param_dtype)
    v_pad = padded_vocab(cfg.vocab_size)
    emb = (
        jax.random.normal(rng, (v_pad, cfg.d_model), dtype=jnp.float32) * 0.02
    )
    out = {"table": emb.astype(pdt)}
    if not cfg.tie_embeddings:
        out["lm_head"] = (
            jax.random.normal(
                jax.random.fold_in(rng, 1),
                (cfg.d_model, v_pad),
                dtype=jnp.float32,
            )
            * cfg.d_model**-0.5
        ).astype(pdt)
    return out


def embed(tokens: jnp.ndarray, params: Dict, cfg: ModelConfig) -> jnp.ndarray:
    x = params["table"].astype(dt(cfg.compute_dtype))[tokens]
    if not cfg.scale_embedding:
        return x
    # gemma-style sqrt(d) scaling keeps tied-embedding logits sane
    return x * jnp.asarray(cfg.d_model**0.5, dtype=x.dtype)


def unembed(x: jnp.ndarray, params: Dict, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        w = params["table"].astype(x.dtype)
        logits = x @ w.T
    else:
        logits = x @ params["lm_head"].astype(x.dtype)
    return logits[..., : cfg.vocab_size]  # drop sharding-pad columns


def chunked_cross_entropy(
    x: jnp.ndarray,  # [B, S, d] final-norm hidden states
    params: Dict,
    cfg: ModelConfig,
    labels: jnp.ndarray,  # [B, S]
    mask: Optional[jnp.ndarray] = None,
    chunk: int = 512,
) -> jnp.ndarray:
    """Cross-entropy without materializing [B, S, V] logits.

    Scans over sequence chunks; each chunk unembeds, reduces to per-token
    NLL, and is rematerialized in the backward pass (jax.checkpoint) — peak
    logits memory drops from S·V to chunk·V.  This is the memory-term fix
    for the big-vocab train cells (gemma's V=262k: 34 GiB → ~0.5 GiB of
    live logits per device)."""
    b, s, d = x.shape
    c = min(chunk, s)
    pad = (-s) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(
            mask if mask is not None else jnp.ones((b, s), jnp.float32),
            ((0, 0), (0, pad)),
        )
    elif mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    nc = x.shape[1] // c
    xs = x.reshape(b, nc, c, d).swapaxes(0, 1)
    ls = labels.reshape(b, nc, c).swapaxes(0, 1)
    ms = mask.reshape(b, nc, c).swapaxes(0, 1)

    def step(carry, inp):
        nll_sum, cnt = carry
        xc, lc, mc = inp
        logits = unembed(xc, params, cfg).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mc
        return (nll_sum + nll.sum(), cnt + mc.sum()), None

    step = jax.checkpoint(step)
    (nll_sum, cnt), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xs, ls, ms),
    )
    return nll_sum / jnp.maximum(cnt, 1.0)


# -------------------------------------------------------------------- loss
def softmax_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, mask: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Mean next-token CE in fp32; labels [B, S] of token ids."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
