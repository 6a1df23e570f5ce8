"""Mixture-of-Experts MLP with top-k routing, capacity-based token dropping,
and explicit expert parallelism.

Two execution paths with identical routing math:

  * **local** — gather/scatter dispatch on one device (smoke tests, decode,
    and the per-device body of the EP path).  Dispatch is sort-based (no
    [T, E, C] one-hot einsums — those inflate HLO FLOPs by orders of
    magnitude).
  * **expert-parallel** — ``jax.shard_map`` over the (data, model) mesh:
    tokens are locally dispatched into per-expert capacity buffers, an
    all-to-all over the *model* axis moves them to their expert's shard,
    expert FFNs run as blocked einsums, and a reverse all-to-all brings
    results home.  This is the production EP pattern.

Experts whose count does not divide the model-axis size are padded with
never-routed dummy experts (router logits pinned to -inf).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import dt, init_dense


def _moe(cfg: ModelConfig):
    assert cfg.moe is not None, f"{cfg.name} has no MoE config"
    return cfg.moe


def padded_experts(cfg: ModelConfig, ep: int) -> int:
    e = _moe(cfg).n_experts
    return e if e % ep == 0 else e + (ep - e % ep)


# ----------------------------------------------------------------- params
def init_moe(rng, cfg: ModelConfig, ep: int = 1) -> Dict:
    m = _moe(cfg)
    d, f = cfg.d_model, m.d_ff_expert
    e_pad = padded_experts(cfg, ep)
    pdt = dt(cfg.param_dtype)
    ks = jax.random.split(rng, 4)

    def expert_mat(key, d_in, d_out):
        w = (
            jax.random.normal(key, (e_pad, d_in, d_out), dtype=jnp.float32)
            * d_in**-0.5
        )
        return w.astype(pdt)

    return {
        "router": init_dense(ks[0], d, m.n_experts, jnp.float32),
        "gate": expert_mat(ks[1], d, f),
        "up": expert_mat(ks[2], d, f),
        "down": expert_mat(ks[3], f, d),
    }


# ---------------------------------------------------------- local dispatch
def _route(
    x_flat: jnp.ndarray, params: Dict, cfg: ModelConfig
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Router: top-k gates (renormalized) + aux load-balance loss terms."""
    m = _moe(cfg)
    logits = (x_flat.astype(jnp.float32) @ params["router"]["w"])  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)  # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )
    # Switch-style aux loss: E * Σ_e (token_frac_e · prob_mass_e)
    top1 = expert_idx[:, 0]
    token_frac = jnp.mean(
        jax.nn.one_hot(top1, m.n_experts, dtype=jnp.float32), axis=0
    )
    prob_mass = jnp.mean(probs, axis=0)
    aux = m.n_experts * jnp.sum(token_frac * prob_mass)
    return gate_vals, expert_idx, aux


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = _moe(cfg)
    c = int(m.capacity_factor * n_tokens * m.top_k / m.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


def _dispatch_tables(
    expert_idx: jnp.ndarray,  # [T, k]
    n_tokens: int,
    e_pad: int,
    cap: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-based slot assignment.

    Returns (slot_table [e_pad*cap] of token ids (n_tokens == empty),
             token_slots [T, k] of slot ids (e_pad*cap == dropped))."""
    t, k = expert_idx.shape
    eflat = expert_idx.reshape(-1)  # [T*k]
    order = jnp.argsort(eflat, stable=True)  # token-priority within expert
    sorted_e = eflat[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e_pad))  # [e_pad]
    pos = jnp.arange(t * k) - starts[sorted_e]
    keep = pos < cap
    slot_sorted = jnp.where(keep, sorted_e * cap + pos, e_pad * cap)
    token_sorted = order // k
    slot_table = jnp.full((e_pad * cap + 1,), t, dtype=jnp.int32)
    slot_table = slot_table.at[slot_sorted].set(
        token_sorted.astype(jnp.int32), mode="drop"
    )[:-1]
    token_slots = (
        jnp.zeros((t * k,), dtype=jnp.int32)
        .at[order]
        .set(slot_sorted.astype(jnp.int32))
        .reshape(t, k)
    )
    return slot_table, token_slots


def _expert_ffn(expert_in: jnp.ndarray, params: Dict, cfg: ModelConfig):
    """expert_in: [E?, C?, d] blocked einsum FFN (SwiGLU)."""
    cdt = dt(cfg.compute_dtype)
    g = jnp.einsum("ecd,edf->ecf", expert_in, params["gate"].astype(cdt))
    u = jnp.einsum("ecd,edf->ecf", expert_in, params["up"].astype(cdt))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(cdt) * u
    return jnp.einsum("ecf,efd->ecd", h, params["down"].astype(cdt))


def moe_mlp_local(
    params: Dict, x: jnp.ndarray, cfg: ModelConfig
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-device MoE (also the EP per-shard body without collectives).

    x: [B, S, d] → ([B, S, d], aux loss scalar)."""
    b, s, d = x.shape
    t = b * s
    e_pad = params["gate"].shape[0]
    x_flat = x.reshape(t, d)
    gates, expert_idx, aux = _route(x_flat, params, cfg)
    cap = _capacity(t, cfg)
    slot_table, token_slots = _dispatch_tables(expert_idx, t, e_pad, cap)
    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, d), dtype=x.dtype)])
    expert_in = x_pad[slot_table].reshape(e_pad, cap, d)
    expert_out = _expert_ffn(expert_in, params, cfg)
    out_pad = jnp.concatenate(
        [expert_out.reshape(e_pad * cap, d), jnp.zeros((1, d), dtype=x.dtype)]
    )
    y = (out_pad[token_slots] * gates[..., None].astype(x.dtype)).sum(axis=1)
    return y.reshape(b, s, d), aux


# ------------------------------------------------------- expert parallelism
def moe_mlp_ep(
    params: Dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    model_axis: str = "model",
    reduce_axes: Tuple[str, ...] = ("data", "model"),
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """shard_map body: x is the LOCAL shard [b_l, s_l, d]; experts are
    sharded over ``model_axis``.  Performs dispatch-all_to_all-ffn-return."""
    b, s, d = x.shape
    t = b * s
    ep = jax.lax.axis_size(model_axis)
    e_pad = params["gate"].shape[0]  # local view: params sharded outside
    e_pad_global = e_pad * ep
    x_flat = x.reshape(t, d)
    # Router weights are replicated; routing happens where the tokens live.
    gates, expert_idx, aux = _route(x_flat, params, cfg)
    cap = _capacity(t, cfg)
    slot_table, token_slots = _dispatch_tables(
        expert_idx, t, e_pad_global, cap
    )
    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, d), dtype=x.dtype)])
    send = x_pad[slot_table].reshape(e_pad_global, cap, d)
    # all-to-all over the model axis: [E_glob, C, d] → [E_loc, P*C, d]
    recv = jax.lax.all_to_all(
        send, model_axis, split_axis=0, concat_axis=1, tiled=True
    )
    expert_out = _expert_ffn(recv, params, cfg)
    # reverse exchange: [E_loc, P*C, d] → [E_glob, C, d]
    back = jax.lax.all_to_all(
        expert_out, model_axis, split_axis=1, concat_axis=0, tiled=True
    )
    out_pad = jnp.concatenate(
        [back.reshape(e_pad_global * cap, d), jnp.zeros((1, d), dtype=x.dtype)]
    )
    y = (out_pad[token_slots] * gates[..., None].astype(x.dtype)).sum(axis=1)
    aux = jax.lax.pmean(aux, reduce_axes)
    return y.reshape(b, s, d), aux
