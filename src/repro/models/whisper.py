"""Whisper-style encoder-decoder backbone (audio frontend stubbed).

Per the assignment, the conv frontend is a STUB: ``input_specs()`` provides
precomputed frame embeddings [B, n_frames, d_model].  The backbone —
bidirectional encoder, causal decoder with per-layer cross-attention — is
implemented fully, with both stacks scanned over layers.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (
    attention_block,
    cross_attention_block,
    init_attention,
    precompute_cross_kv,
    write_token,
)
from .layers import dt, embed, init_embedding, init_mlp, init_rmsnorm, mlp, rms_norm, unembed


def _enc(cfg: ModelConfig):
    assert cfg.encdec is not None, f"{cfg.name} is not enc-dec"
    return cfg.encdec


# ------------------------------------------------------------------- init
def _init_enc_layer(rng, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(rng, 2)
    pdt = dt(cfg.param_dtype)
    return {
        "ln1": init_rmsnorm(cfg.d_model, pdt),
        "attn": init_attention(ks[0], cfg),
        "ln2": init_rmsnorm(cfg.d_model, pdt),
        "mlp": init_mlp(ks[1], cfg),
    }


def _init_dec_layer(rng, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(rng, 3)
    pdt = dt(cfg.param_dtype)
    return {
        "ln1": init_rmsnorm(cfg.d_model, pdt),
        "self_attn": init_attention(ks[0], cfg),
        "ln_x": init_rmsnorm(cfg.d_model, pdt),
        "cross_attn": init_attention(ks[1], cfg),
        "ln2": init_rmsnorm(cfg.d_model, pdt),
        "mlp": init_mlp(ks[2], cfg),
    }


def init_encdec(rng, cfg: ModelConfig, ep: int = 1) -> Dict:
    e = _enc(cfg)
    enc_keys = jax.random.split(jax.random.fold_in(rng, 1), e.n_enc_layers)
    dec_keys = jax.random.split(jax.random.fold_in(rng, 2), cfg.n_layers)
    pdt = dt(cfg.param_dtype)
    return {
        "embed": init_embedding(jax.random.fold_in(rng, 0), cfg),
        "encoder": jax.vmap(lambda k: _init_enc_layer(k, cfg))(enc_keys),
        "enc_norm": init_rmsnorm(cfg.d_model, pdt),
        "decoder": jax.vmap(lambda k: _init_dec_layer(k, cfg))(dec_keys),
        "final_norm": init_rmsnorm(cfg.d_model, pdt),
    }


# ---------------------------------------------------------------- encoder
def encode(
    params: Dict, frames: jnp.ndarray, cfg: ModelConfig, remat: bool = False
) -> jnp.ndarray:
    """frames: [B, F, d_model] (stub frontend output) → enc states."""
    from ..distributed.context import constrain

    b, f, _ = frames.shape
    x = constrain(frames.astype(dt(cfg.compute_dtype)), "batch")
    positions = jnp.broadcast_to(jnp.arange(f, dtype=jnp.int32)[None], (b, f))

    def body(x, lp):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        out, _ = attention_block(
            lp["attn"], h, positions, cfg, causal=False, window=None
        )
        x = x + out
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        return x + mlp(h, lp["mlp"], cfg), None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, _ = jax.lax.scan(body, x, params["encoder"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------- decoder
def forward(
    params: Dict,
    frames: jnp.ndarray,  # [B, F, d_model]
    tokens: jnp.ndarray,  # [B, S]
    cfg: ModelConfig,
    remat: bool = False,
    last_only: bool = False,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Teacher-forced enc-dec forward → (logits, aux=0)."""
    from ..distributed.context import constrain

    enc_out = encode(params, frames, cfg, remat=remat)
    x = constrain(embed(tokens, params["embed"], cfg), "residual")
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(x, lp):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        out, _ = attention_block(lp["self_attn"], h, positions, cfg, causal=True)
        x = x + out
        h = rms_norm(x, lp["ln_x"], cfg.norm_eps)
        kv = precompute_cross_kv(lp["cross_attn"], enc_out, cfg)
        x = x + cross_attention_block(lp["cross_attn"], h, kv, cfg)
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        return constrain(x + mlp(h, lp["mlp"], cfg), "residual"), None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, _ = jax.lax.scan(body, x, params["decoder"])
    if last_only:
        x = x[:, -1:, :]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return x, jnp.zeros((), jnp.float32)
    logits = constrain(unembed(x, params["embed"], cfg), "logits")
    return logits, jnp.zeros((), jnp.float32)


def loss_fn(
    params: Dict,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    remat: bool = True,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    hidden, _ = forward(
        params, batch["frames"], batch["tokens"], cfg, remat=remat, return_hidden=True
    )
    from .layers import chunked_cross_entropy

    ce = chunked_cross_entropy(
        hidden, params["embed"], cfg, batch["labels"], batch.get("loss_mask")
    )
    return ce, {"ce": ce, "aux": jnp.zeros(()), "loss": ce}


# ----------------------------------------------------------------- decode
def init_encdec_cache(
    cfg: ModelConfig, batch: int, max_len: int
) -> Dict[str, Any]:
    e = _enc(cfg)
    cdt = dt(cfg.compute_dtype)
    l = cfg.n_layers
    kv = (l, batch, max_len, cfg.n_kv_heads, cfg.head_dim_)
    xkv = (l, batch, e.n_frames, cfg.n_kv_heads, cfg.head_dim_)
    return {
        "self": {"k": jnp.zeros(kv, cdt), "v": jnp.zeros(kv, cdt)},
        "cross": {"k": jnp.zeros(xkv, cdt), "v": jnp.zeros(xkv, cdt)},
    }


def prefill_cross_cache(
    params: Dict, frames: jnp.ndarray, cache: Dict, cfg: ModelConfig
) -> Dict:
    """Fill the cross-attention KV from encoder output (once per request)."""
    enc_out = encode(params, frames, cfg)

    def body(_, lp):
        return None, jnp.stack(precompute_cross_kv(lp["cross_attn"], enc_out, cfg))

    _, kvs = jax.lax.scan(body, None, params["decoder"])  # [L, 2, B, F, H, D]
    return {
        "self": cache["self"],
        "cross": {"k": kvs[:, 0], "v": kvs[:, 1]},
    }


def decode_step(
    params: Dict,
    cache: Dict,
    tokens: jnp.ndarray,  # [B, 1]
    pos_index: jnp.ndarray,
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Dict]:
    x = embed(tokens, params["embed"], cfg)
    b = x.shape[0]
    positions = jnp.broadcast_to(
        pos_index.astype(jnp.int32)[None, None], (b, 1)
    )

    def body(x, xs):
        lp, kc, vc, xk, xv = xs
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        out, new_kv = attention_block(
            lp["self_attn"],
            h,
            positions,
            cfg,
            causal=True,
            cache={"k": kc, "v": vc},
            cache_index=pos_index,
        )
        x = x + out
        h = rms_norm(x, lp["ln_x"], cfg.norm_eps)
        x = x + cross_attention_block(lp["cross_attn"], h, (xk, xv), cfg)
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + mlp(h, lp["mlp"], cfg)
        return x, (new_kv["k"], new_kv["v"])

    x, (tk, tv) = jax.lax.scan(
        body,
        x,
        (
            params["decoder"],
            cache["self"]["k"],
            cache["self"]["v"],
            cache["cross"]["k"],
            cache["cross"]["v"],
        ),
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params["embed"], cfg)
    self_kv = {
        "k": write_token(cache["self"]["k"], tk, pos_index, axis=2),
        "v": write_token(cache["self"]["v"], tv, pos_index, axis=2),
    }
    return logits, {"self": self_kv, "cross": cache["cross"]}
