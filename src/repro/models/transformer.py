"""Generic layer-list LM covering the dense / moe / hybrid / ssm / vlm
families.

Depth is organized as the config's runs (``ModelConfig.layer_runs``): each
run repeats a period of layer kinds and is executed with ``lax.scan`` over
per-layer parameters stacked on a leading axis — one period body in the HLO
per run regardless of depth, which keeps compiles small and is also what
makes per-layer remat policies cheap.  A cycled pattern is
one run; an explicit layer list (zamba2) scans each repeated stretch.

The parameters and the cache of the layer that opens a run's period at
position ``i`` live under ``groups/pos<first layer of that position>``.
"hybrid" layers (zamba2) run one of ``params["shared"]``'s blocks, closed
over by the scan body, with their own adapter and output projection, and
keep their own KV cache beside their mixer's SSM state.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import attention_block, init_attention, write_token
from .layers import (
    chunked_cross_entropy,
    dense,
    dt,
    embed,
    init_adapter,
    init_dense,
    init_embedding,
    init_mlp,
    init_rmsnorm,
    mlp,
    rms_norm,
    unembed,
)
from .mamba2 import init_mamba_block, mamba_block, mamba_decode_step, mamba_dims
from .moe import init_moe

def _kind_window(kind: str, cfg: ModelConfig) -> Optional[int]:
    return cfg.sliding_window if kind in ("swa", "swa_moe") else None


def _kind_theta(kind: str, cfg: ModelConfig) -> float:
    if kind in ("swa", "swa_moe") and cfg.rope_theta_local:
        return cfg.rope_theta_local
    return cfg.rope_theta


def _run(tree: Dict, first: int, period: int) -> Dict:
    """The entries of ``tree`` (``pos<layer>`` keyed) of one run."""
    return {f"pos{i}": tree[f"pos{i}"] for i in range(first, first + period)}


# ------------------------------------------------------------------- init
def init_block(rng, kind: str, cfg: ModelConfig, ep: int = 1) -> Dict:
    ks = jax.random.split(rng, 4)
    pdt = dt(cfg.param_dtype)
    if kind in ("mamba", "hybrid"):
        block = {
            "ln1": init_rmsnorm(cfg.d_model, pdt),
            "mamba": init_mamba_block(ks[0], cfg),
        }
        if kind == "hybrid":  # the site's own parts of its shared block
            d = cfg.d_model
            block["adapter"] = init_adapter(ks[1], d, cfg.shared.adapter_rank, cfg.d_ff, pdt)
            block["linear"] = init_dense(ks[2], d, d, pdt)
        return block
    block = {
        "ln1": init_rmsnorm(cfg.d_model, pdt),
        "attn": init_attention(ks[0], cfg),
        "ln2": init_rmsnorm(cfg.d_model, pdt),
    }
    if kind in ("moe", "swa_moe"):
        block["moe"] = init_moe(ks[1], cfg, ep=ep)
    else:
        block["mlp"] = init_mlp(ks[1], cfg)
    return block


def init_shared_block(rng, cfg: ModelConfig) -> Dict:
    """One of zamba2's shared transformer blocks: attention over
    concat[h, e0] (width 2·d_model) and a gated MLP."""
    ks = jax.random.split(rng, 2)
    pdt = dt(cfg.param_dtype)
    return {
        "ln1": init_rmsnorm(2 * cfg.d_model, pdt),
        "attn": init_attention(ks[0], cfg, d_in=2 * cfg.d_model),
        "ln2": init_rmsnorm(cfg.d_model, pdt),
        "mlp": init_mlp(ks[1], cfg),
    }


def init_lm(rng, cfg: ModelConfig, ep: int = 1) -> Dict:
    kinds = cfg.layer_kinds()
    groups = {}
    for first, period, reps in cfg.layer_runs():
        for i in range(first, first + period):
            keys = jax.random.split(jax.random.fold_in(rng, 100 + i), reps)
            groups[f"pos{i}"] = jax.vmap(
                lambda k, kd=kinds[i]: init_block(k, kd, cfg, ep)
            )(keys)
    params: Dict[str, Any] = {
        "embed": init_embedding(jax.random.fold_in(rng, 0), cfg),
        "final_norm": init_rmsnorm(cfg.d_model, dt(cfg.param_dtype)),
        "groups": groups,
    }
    if cfg.shared is not None:
        params["shared"] = {
            f"block{b}": init_shared_block(jax.random.fold_in(rng, 900 + b), cfg)
            for b in range(cfg.shared.n_blocks)
        }
    return params


# ----------------------------------------------------------------- blocks
def shared_block(
    sp: Dict,
    bp: Dict,
    x: jnp.ndarray,
    e0: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    cache: Optional[Dict] = None,
    cache_index: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Zamba2's shared block ``sp`` at one site (``bp``: its adapter and
    output projection): norm(concat[x, e0]) → attention (rope over the
    whole head, scores scaled by (head_dim/2)^-0.5) → norm → gated MLP with
    the site's adapter → the site's projection.  No residual inside: the
    output goes to the mixer's input only.  Returns (out, new KV cache)."""
    with jax.named_scope("zamba2.shared_block"):
        h = rms_norm(jnp.concatenate([x, e0], axis=-1), sp["ln1"], cfg.norm_eps)
        a, new_cache = attention_block(
            sp["attn"],
            h,
            positions,
            cfg,
            causal=True,
            rope_theta=cfg.rope_theta,
            cache=cache,
            cache_index=cache_index,
            scale=(cfg.head_dim_ / 2) ** -0.5,
        )
        h = rms_norm(a, sp["ln2"], cfg.norm_eps)
        out = dense(mlp(h, sp["mlp"], cfg, adapter=bp["adapter"]), bp["linear"])
    return out, new_cache


def apply_block(
    kind: str,
    bp: Dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    shared: Optional[Dict] = None,
    e0: Optional[jnp.ndarray] = None,
    cache: Optional[Dict] = None,
    cache_index: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Dict]]:
    """One block; returns (x, aux_loss, new_cache_slice).  ``shared`` is the
    shared block a hybrid layer runs, and ``e0`` the input embedding."""
    aux = jnp.zeros((), dtype=jnp.float32)
    if kind in ("mamba", "hybrid"):
        inp, kv = x, None
        if kind == "hybrid":
            t, kv = shared_block(shared, bp, x, e0, positions, cfg, cache, cache_index)
            inp = x + t
        h = rms_norm(inp, bp["ln1"], cfg.norm_eps)
        with jax.named_scope("mamba2.mixer"):
            if cache is not None:
                out, new_state = mamba_decode_step(bp["mamba"], h, cache, cfg)
                return x + out, aux, new_state if kv is None else {**new_state, **kv}
            out, _ = mamba_block(bp["mamba"], h, cfg)
        return x + out, aux, None

    window = _kind_window(kind, cfg)
    theta = _kind_theta(kind, cfg)
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    attn_out, new_cache = attention_block(
        bp["attn"],
        h,
        positions,
        cfg,
        causal=True,
        window=window,
        rope_theta=theta,
        cache=cache,
        cache_index=cache_index,
    )
    x = x + attn_out
    h = rms_norm(x, bp["ln2"], cfg.norm_eps)
    if kind in ("moe", "swa_moe"):
        from ..distributed.moe_parallel import moe_maybe_parallel

        ff, aux = moe_maybe_parallel(bp["moe"], h, cfg)
    else:
        ff = mlp(h, bp["mlp"], cfg)
    return x + ff, aux, new_cache


def _apply_run(
    x: jnp.ndarray,
    gp: Dict,
    first: int,
    period: int,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    shared: Optional[Dict],
    e0: jnp.ndarray,
    caches: Optional[Dict] = None,
    cache_index: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Dict]]:
    """One period of the run that starts at layer ``first``."""
    kinds = cfg.layer_kinds()
    aux = jnp.zeros((), dtype=jnp.float32)
    new_caches = {} if caches is not None else None
    for i in range(first, first + period):
        key = f"pos{i}"
        block = shared[f"block{cfg.shared_block(i)}"] if kinds[i] == "hybrid" else None
        cache_i = caches.get(key) if caches is not None else None
        x, a, nc = apply_block(
            kinds[i],
            gp.get(key),
            x,
            positions,
            cfg,
            shared=block,
            e0=e0,
            cache=cache_i,
            cache_index=cache_index,
        )
        aux = aux + a
        if new_caches is not None:
            new_caches[key] = nc
    return x, aux, new_caches


# ---------------------------------------------------------------- forward
def forward(
    params: Dict,
    tokens: jnp.ndarray,  # [B, S_text]
    cfg: ModelConfig,
    prefix_embeds: Optional[jnp.ndarray] = None,  # [B, P, d] (vlm stub)
    remat: bool = False,
    last_only: bool = False,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Teacher-forced forward; returns (logits [B, S_total, V], aux).

    ``last_only`` (prefill): unembed only the final position — avoids
    materializing [B, S, V] logits when only the next token matters.
    ``return_hidden``: skip unembedding, return the final-norm hidden
    states (the chunked-CE loss unembeds per chunk itself)."""
    from ..distributed.context import constrain

    x = embed(tokens, params["embed"], cfg)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    x = constrain(x, "residual")
    e0 = x
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    shared = params.get("shared")
    aux_total = jnp.zeros((), dtype=jnp.float32)

    for first, period, _ in cfg.layer_runs():
        def body(carry, gp, first=first, period=period):
            x, aux = carry
            x, a, _ = _apply_run(x, gp, first, period, positions, cfg, shared, e0)
            return (constrain(x, "residual"), aux + a), None

        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable
            )
        (x, aux_total), _ = jax.lax.scan(
            body, (x, aux_total), _run(params["groups"], first, period)
        )

    if last_only:
        x = x[:, -1:, :]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return x, aux_total
    logits = constrain(unembed(x, params["embed"], cfg), "logits")
    return logits, aux_total


def loss_fn(
    params: Dict,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    remat: bool = True,
    ce_chunk: int = 512,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token CE + MoE aux; batch: tokens/labels [B, S] (+ optional
    prefix_embeds, loss_mask).

    The CE is computed CHUNKED over the sequence (never materializing
    [B, S, V] logits) — with V up to 262k this is the difference between
    fitting HBM and not."""
    hidden, aux = forward(
        params,
        batch["tokens"],
        cfg,
        prefix_embeds=batch.get("prefix_embeds"),
        remat=remat,
        return_hidden=True,
    )
    labels = batch["labels"]
    if hidden.shape[1] != labels.shape[1]:  # vlm: loss only on text positions
        hidden = hidden[:, hidden.shape[1] - labels.shape[1] :]
    ce = chunked_cross_entropy(
        hidden, params["embed"], cfg, labels, batch.get("loss_mask"), ce_chunk
    )
    coef = cfg.moe.router_aux_coef if cfg.moe is not None else 0.0
    loss = ce + coef * aux
    return loss, {"ce": ce, "aux": aux, "loss": loss}


# ----------------------------------------------------------------- decode
#: decode-cache leaves of the fp32 SSM and conv state; every other leaf is KV
STATE_LEAVES = ("ssm", "conv_x", "conv_bc")


def _ssm_cache(cfg: ModelConfig, batch: int) -> Dict:
    dims = mamba_dims(cfg)
    gn2 = 2 * dims["n_groups"] * dims["d_state"]
    return {
        "ssm": jnp.zeros(
            (batch, dims["n_heads"], dims["head_dim"], dims["d_state"]),
            dtype=jnp.float32,
        ),
        "conv_x": jnp.zeros(
            (batch, dims["conv_width"] - 1, dims["d_inner"]),
            dtype=jnp.float32,
        ),
        "conv_bc": jnp.zeros((batch, dims["conv_width"] - 1, gn2), dtype=jnp.float32),
    }


def _kv_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    cdt = dt(cfg.compute_dtype)
    # SWA blocks never attend beyond their window → ring buffer of window
    # length (5/6 of gemma3's layers: 32k → 1k cache rows)
    length = max_len
    if kind in ("swa", "swa_moe"):
        length = min(max_len, cfg.sliding_window)
    shape = (batch, length, cfg.n_kv_heads, cfg.head_dim_)
    if cfg.kv_cache_dtype == "int8":
        sshape = (batch, length, cfg.n_kv_heads)
        return {
            "k": jnp.zeros(shape, dtype=jnp.int8),
            "v": jnp.zeros(shape, dtype=jnp.int8),
            "k_scale": jnp.zeros(sshape, dtype=jnp.float32),
            "v_scale": jnp.zeros(sshape, dtype=jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype=cdt), "v": jnp.zeros(shape, dtype=cdt)}


def _init_block_cache(
    kind: str, cfg: ModelConfig, batch: int, max_len: int
) -> Dict:
    """A layer's decode state: fp32 SSM and conv state for a mixer, KV for
    attention; a hybrid layer holds both."""
    if kind == "mamba":
        return _ssm_cache(cfg, batch)
    if kind == "hybrid":
        return {**_ssm_cache(cfg, batch), **_kv_cache(kind, cfg, batch, max_len)}
    return _kv_cache(kind, cfg, batch, max_len)


def init_lm_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """One dense tree for the whole depth, shaped as the parameters' runs."""
    kinds = cfg.layer_kinds()
    groups = {}
    for first, period, reps in cfg.layer_runs():
        for i in range(first, first + period):
            one = _init_block_cache(kinds[i], cfg, batch, max_len)
            groups[f"pos{i}"] = jax.tree.map(
                lambda l: jnp.broadcast_to(l[None], (reps, *l.shape)).copy(), one
            )
    return {"groups": groups}


def decode_step(
    params: Dict,
    cache: Dict,
    tokens: jnp.ndarray,  # [B, 1]
    pos_index: jnp.ndarray,  # scalar int32: write position in the cache
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode against a KV/SSM cache; returns (logits [B,1,V],
    new cache)."""
    x = embed(tokens, params["embed"], cfg)
    e0 = x
    b = x.shape[0]
    positions = jnp.broadcast_to(
        pos_index.astype(jnp.int32)[None, None], (b, 1)
    )
    shared = params.get("shared")
    groups: Dict[str, Any] = {}

    for first, period, _ in cfg.layer_runs():
        def body(x, xs, first=first, period=period):
            gp, gc = xs
            x, _, nc = _apply_run(
                x,
                gp,
                first,
                period,
                positions,
                cfg,
                shared,
                e0,
                caches=gc,
                cache_index=pos_index,
            )
            return x, nc

        run = _run(cache["groups"], first, period)
        x, nc = jax.lax.scan(body, x, (_run(params["groups"], first, period), run))
        # the scan reads each layer's KV in place and hands back only the
        # token's; one write per KV leaf puts the run's tokens in the cache
        for key, leaves in run.items():
            groups[key] = {
                name: nc[key][name]
                if name in STATE_LEAVES
                else write_token(leaf, nc[key][name], pos_index, axis=2)
                for name, leaf in leaves.items()
            }

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params["embed"], cfg)
    return logits, {"groups": groups}
