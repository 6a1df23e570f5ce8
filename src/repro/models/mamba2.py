"""Mamba2 (SSD — state-space duality) block in pure JAX.

Implements the chunked SSD algorithm of arXiv:2405.21060: within a chunk the
computation is a masked (decay-weighted) attention-like quadratic form; the
state is carried across chunks with a linear recurrence.  This jnp code is
the one path the model runs; ``repro.kernels.ssd_scan`` is a Pallas TPU
kernel of the same math, validated against its own reference.

Decode is O(1) per token: a [B, H, P, N] state and a small conv cache.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import dt, init_dense, rms_norm


def _ssm(cfg: ModelConfig):
    assert cfg.ssm is not None, f"{cfg.name} has no SSM config"
    return cfg.ssm


def mamba_dims(cfg: ModelConfig) -> Dict[str, int]:
    s = _ssm(cfg)
    d_in = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    conv_ch = d_in + 2 * s.n_groups * s.d_state
    return {
        "d_inner": d_in,
        "n_heads": nh,
        "head_dim": s.head_dim,
        "d_state": s.d_state,
        "n_groups": s.n_groups,
        "conv_ch": conv_ch,
        "conv_width": s.conv_width,
        "in_dim": 2 * d_in + 2 * s.n_groups * s.d_state + nh,
    }


# ----------------------------------------------------------------- params
#
# NOTE on layout: the reference Mamba2 fuses z/x/B/C/dt into one in_proj and
# one depthwise conv.  We keep them as SEPARATE matrices: mathematically
# identical (depthwise conv and matmul both act per-channel/column), but the
# split projections shard cleanly under tensor parallelism — z/x/dt are
# column-parallel over heads, B/C stay replicated (tiny), out_proj is
# row-parallel.  The fused layout would straddle TP shard boundaries.
def init_mamba_block(rng, cfg: ModelConfig) -> Dict:
    dims = mamba_dims(cfg)
    pdt = dt(cfg.param_dtype)
    ks = jax.random.split(rng, 6)
    w = dims["conv_width"]
    gn2 = 2 * dims["n_groups"] * dims["d_state"]

    def conv_init(key, ch):
        return (
            jax.random.normal(key, (w, ch), jnp.float32) * w**-0.5
        ).astype(pdt)

    return {
        "z_proj": init_dense(ks[0], cfg.d_model, dims["d_inner"], pdt),
        "x_proj": init_dense(ks[1], cfg.d_model, dims["d_inner"], pdt),
        "bc_proj": init_dense(ks[2], cfg.d_model, gn2, pdt),
        "dt_proj": init_dense(ks[3], cfg.d_model, dims["n_heads"], pdt),
        "conv_x_w": conv_init(ks[4], dims["d_inner"]),
        "conv_x_b": jnp.zeros((dims["d_inner"],), dtype=pdt),
        "conv_bc_w": conv_init(ks[5], gn2),
        "conv_bc_b": jnp.zeros((gn2,), dtype=pdt),
        "A_log": jnp.zeros((dims["n_heads"],), dtype=jnp.float32),
        "D": jnp.ones((dims["n_heads"],), dtype=jnp.float32),
        "dt_bias": jnp.zeros((dims["n_heads"],), dtype=jnp.float32),
        "gate_norm": {"scale": jnp.zeros((dims["d_inner"],), dtype=pdt)},
        "out_proj": init_dense(jax.random.fold_in(ks[3], 7), dims["d_inner"], cfg.d_model, pdt),
    }


# ------------------------------------------------------------ SSD (chunked)
def segsum(x: jnp.ndarray) -> jnp.ndarray:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} x[..., k]
    (lower-triangular; -inf above the diagonal)."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), dtype=bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jnp.ndarray,  # [B, S, H, P] (already dt-weighted)
    dA: jnp.ndarray,  # [B, S, H]   (dt * A, negative)
    B_: jnp.ndarray,  # [B, S, H, N] (groups already broadcast to heads)
    C_: jnp.ndarray,  # [B, S, H, N]
    chunk: int,
    initial_state: Optional[jnp.ndarray] = None,  # [B, H, P, N]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD; returns (y [B,S,H,P], final_state [B,H,P,N])."""
    b, s, h, p = x.shape
    n = B_.shape[-1]
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    c = s // chunk
    xf = x.astype(jnp.float32).reshape(b, c, chunk, h, p)
    dAf = dA.astype(jnp.float32).reshape(b, c, chunk, h)
    Bf = B_.astype(jnp.float32).reshape(b, c, chunk, h, n)
    Cf = C_.astype(jnp.float32).reshape(b, c, chunk, h, n)

    cum = jnp.cumsum(dAf, axis=2)  # [B,C,Q,H]
    # ---- intra-chunk (the "attention-like" diagonal block) ----
    L = jnp.exp(segsum(dAf.transpose(0, 1, 3, 2)))  # [B,C,H,Q,Q]
    scores = jnp.einsum("bcqhn,bckhn->bchqk", Cf, Bf) * L
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", scores, xf)
    # ---- per-chunk final states ----
    decay_states = jnp.exp(cum[:, :, -1:, :] - cum)  # [B,C,Q,H]
    chunk_states = jnp.einsum("bcqhn,bcqh,bcqhp->bchpn", Bf, decay_states, xf)
    # ---- inter-chunk recurrence ----
    total_decay = jnp.exp(cum[:, :, -1, :])  # [B,C,H]

    def step(state, inp):
        st_c, dec_c = inp  # [B,H,P,N], [B,H]
        new = state * dec_c[:, :, None, None] + st_c
        return new, state  # emit the state *entering* this chunk

    init = (
        initial_state.astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((b, h, p, n), dtype=jnp.float32)
    )
    final_state, prev_states = jax.lax.scan(
        step,
        init,
        (
            chunk_states.transpose(1, 0, 2, 3, 4),  # [C,B,H,P,N]
            total_decay.transpose(1, 0, 2),  # [C,B,H]
        ),
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # [B,C,H,P,N]
    # ---- contribution of the carried-in state ----
    state_decay = jnp.exp(cum)  # [B,C,Q,H]
    y_off = jnp.einsum(
        "bcqhn,bchpn,bcqh->bcqhp", Cf, prev_states, state_decay
    )
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y.astype(x.dtype), final_state


# ----------------------------------------------------------- block forward
def _dt(raw: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """dt = softplus(dt_proj(u) + dt_bias), floored at ``ssm.dt_min``."""
    dt_ = jax.nn.softplus(raw)
    return jnp.maximum(dt_, _ssm(cfg).dt_min) if _ssm(cfg).dt_min else dt_


def _causal_conv(xBC: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray):
    """Depthwise causal conv over the sequence dim; xBC [B,S,Ch], w [W,Ch]."""
    width = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC, dtype=jnp.float32)
    for i in range(width):  # W is tiny (4): unrolled taps fuse well
        out = out + pad[:, i : i + xBC.shape[1], :].astype(jnp.float32) * w[
            i
        ].astype(jnp.float32)
    return out + b.astype(jnp.float32)


def mamba_block(
    params: Dict,
    u: jnp.ndarray,  # [B, S, d_model]
    cfg: ModelConfig,
    initial_state: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full Mamba2 mixer; returns (out [B,S,d_model], final_state)."""
    dims = mamba_dims(cfg)
    b, s, _ = u.shape
    h, p, n, g = (
        dims["n_heads"],
        dims["head_dim"],
        dims["d_state"],
        dims["n_groups"],
    )
    z = u @ params["z_proj"]["w"].astype(u.dtype)
    xr = u @ params["x_proj"]["w"].astype(u.dtype)
    bc = u @ params["bc_proj"]["w"].astype(u.dtype)
    dt_raw = u @ params["dt_proj"]["w"].astype(u.dtype)
    xc = jax.nn.silu(_causal_conv(xr, params["conv_x_w"], params["conv_x_b"]))
    bcc = jax.nn.silu(_causal_conv(bc, params["conv_bc_w"], params["conv_bc_b"]))
    x = xc.reshape(b, s, h, p)
    B_ = bcc[..., : g * n].reshape(b, s, g, n)
    C_ = bcc[..., g * n :].reshape(b, s, g, n)
    rep = h // g
    B_h = jnp.repeat(B_, rep, axis=2)
    C_h = jnp.repeat(C_, rep, axis=2)
    dt_ = _dt(dt_raw.astype(jnp.float32) + params["dt_bias"][None, None, :], cfg)  # [B,S,H]
    A = -jnp.exp(params["A_log"])  # [H]
    dA = dt_ * A[None, None, :]
    xdt = x.astype(jnp.float32) * dt_[..., None]
    y, final_state = ssd_chunked(
        xdt, dA, B_h, C_h, chunk=min(_ssm(cfg).chunk, s), initial_state=initial_state
    )
    y = y + params["D"][None, None, :, None] * x.astype(jnp.float32)
    y = y.reshape(b, s, dims["d_inner"]).astype(u.dtype)
    # gated RMSNorm over groups of d_inner / n_groups channels
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype),
                 params["gate_norm"], cfg.norm_eps, groups=g)
    return y @ params["out_proj"]["w"].astype(u.dtype), final_state


# ------------------------------------------------------------------ decode
def init_mamba_cache(cfg: ModelConfig, batch: int, n_layers: int) -> Dict:
    dims = mamba_dims(cfg)
    gn2 = 2 * dims["n_groups"] * dims["d_state"]
    return {
        "ssm": jnp.zeros(
            (n_layers, batch, dims["n_heads"], dims["head_dim"], dims["d_state"]),
            dtype=jnp.float32,
        ),
        "conv_x": jnp.zeros(
            (n_layers, batch, dims["conv_width"] - 1, dims["d_inner"]),
            dtype=jnp.float32,
        ),
        "conv_bc": jnp.zeros(
            (n_layers, batch, dims["conv_width"] - 1, gn2), dtype=jnp.float32
        ),
    }


def mamba_decode_step(
    params: Dict,
    u: jnp.ndarray,  # [B, 1, d_model]
    cache: Dict,  # {"ssm": [B,H,P,N], "conv": [B,W-1,Ch]} — this layer's slice
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Dict]:
    """O(1) decode: constant-size state, no KV growth."""
    dims = mamba_dims(cfg)
    b = u.shape[0]
    h, p, n, g = (
        dims["n_heads"],
        dims["head_dim"],
        dims["d_state"],
        dims["n_groups"],
    )
    u0 = u[:, 0]
    z = u0 @ params["z_proj"]["w"].astype(u.dtype)
    xr = u0 @ params["x_proj"]["w"].astype(u.dtype)
    bc = u0 @ params["bc_proj"]["w"].astype(u.dtype)
    dt_raw = u0 @ params["dt_proj"]["w"].astype(u.dtype)
    # conv caches: window = [cache | new]
    win_x = jnp.concatenate([cache["conv_x"], xr[:, None, :]], axis=1)
    win_bc = jnp.concatenate([cache["conv_bc"], bc[:, None, :]], axis=1)

    def conv1(win, w_, b_):
        return jnp.einsum(
            "bwc,wc->bc", win.astype(jnp.float32), w_.astype(jnp.float32)
        ) + b_.astype(jnp.float32)

    xc = jax.nn.silu(conv1(win_x, params["conv_x_w"], params["conv_x_b"]))
    bcc = jax.nn.silu(conv1(win_bc, params["conv_bc_w"], params["conv_bc_b"]))
    x = xc.reshape(b, h, p)
    B_ = bcc[..., : g * n].reshape(b, g, n)
    C_ = bcc[..., g * n :].reshape(b, g, n)
    rep = h // g
    B_h = jnp.repeat(B_, rep, axis=1)  # [B,H,N]
    C_h = jnp.repeat(C_, rep, axis=1)
    dt_ = _dt(dt_raw.astype(jnp.float32) + params["dt_bias"][None, :], cfg)  # [B,H]
    A = -jnp.exp(params["A_log"])
    dA = jnp.exp(dt_ * A[None, :])  # [B,H]
    state = cache["ssm"] * dA[:, :, None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dt_, x.astype(jnp.float32), B_h
    )
    y = jnp.einsum("bhpn,bhn->bhp", state, C_h) + params["D"][None, :, None] * x.astype(jnp.float32)
    y = y.reshape(b, dims["d_inner"]).astype(u.dtype)
    y = rms_norm(
        (y * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype))[:, None, :],
        params["gate_norm"],
        cfg.norm_eps,
        groups=g,
    )[:, 0]
    out = (y @ params["out_proj"]["w"].astype(u.dtype))[:, None, :]
    return out, {
        "ssm": state,
        "conv_x": win_x[:, 1:, :],
        "conv_bc": win_bc[:, 1:, :],
    }
