"""Kernel substitution for the roofline memory term.

The dry-run lowers the pure-JAX blocked attention / SSD scan (the Pallas
kernels cannot lower on the CPU host platform).  The op-level HBM traffic
model then charges the scan carries (softmax accumulators, SSD states) a
full HBM round trip per tile step — but on TPU these regions run as the
``repro.kernels`` Pallas kernels, whose carries live in VMEM scratch: their
true HBM traffic is "stream q/k/v once, write out once" (attention) and
"stream x/dA/B/C once, write y once" (SSD).

This module quantifies the gap per cell:

  * the scan implementation is lowered STANDALONE at the cell's per-device
    shard shapes and passed through the same trip-count-aware analyzer —
    so the subtracted traffic is measured by the same model that produced
    the cell totals, not hand-estimated;
  * the kernel's analytic traffic replaces it (fwd: Σ operand+result bytes
    once; train: ×3 for the flash/SSD recompute backward);
  * FLOPs are substituted the same way (the kernel does the same dots, so
    the delta is ≈0 — kept for consistency).

The roofline reports both the raw (XLA-path) and kernel-substituted memory
terms; EXPERIMENTS.md §Perf logs this as iteration I7.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from .hlo_analysis import analyze_hlo

BF16 = 2
F32 = 4


def _tp_split(hq: int, hkv: int, sq: int, tp: int) -> Tuple[int, int, int]:
    """How the model axis divides one attention layer's work per device:
    heads when they divide (Megatron TP), otherwise q rows (SP — GSPMD
    shards tokens and gathers K/V)."""
    if hq % tp == 0:
        hq_l = hq // tp
        # each device's q-head group only touches its own kv heads
        hkv_l = hkv // tp if hkv % tp == 0 else max(1, min(hkv, hq_l))
        return hq_l, hkv_l, sq
    return hq, hkv, max(1, sq // tp)


def _attention_sites(
    cfg: ModelConfig, shape: ShapeConfig, dp: int, tp: int, mb: int
) -> List[Dict]:
    """Per-device attention workloads in this cell (one entry per distinct
    layer geometry; 'count' = how many layers share it)."""
    if shape.kind == "decode":
        return []  # decode attention streams the cache once: model is fair
    b_l = max(1, shape.global_batch // dp) // (mb if shape.kind == "train" else 1)
    b_l = max(1, b_l)
    hd = cfg.head_dim_
    sites = []

    def site(count, sq, skv, causal, window):
        hq_l, hkv_l, sq_l = _tp_split(cfg.n_heads, cfg.n_kv_heads, sq, tp)
        return dict(count=count, b=b_l, sq=sq_l, skv=skv, hq=hq_l,
                    hkv=hkv_l, hd=hd, causal=causal, window=window)

    if cfg.family == "encdec":
        e = cfg.encdec
        sites.append(site(e.n_enc_layers, e.n_frames, e.n_frames, False, None))
        sites.append(site(cfg.n_layers, shape.seq_len, shape.seq_len, True, None))
        sites.append(site(cfg.n_layers, shape.seq_len, e.n_frames, False, None))
        return sites
    kinds = cfg.layer_kinds()
    n_full = sum(1 for k in kinds if k in ("attn", "global", "moe", "hybrid"))
    n_swa = sum(1 for k in kinds if k in ("swa", "swa_moe"))
    s = shape.seq_len
    if n_full:
        sites.append(site(n_full, s, s, True, None))
    if n_swa:
        sites.append(site(n_swa, s, s, True, cfg.sliding_window))
    return sites


def _ssd_sites(
    cfg: ModelConfig, shape: ShapeConfig, dp: int, mb: int, tp: int = 16
) -> List[Dict]:
    if cfg.ssm is None or shape.kind == "decode":
        return []
    from ..models.mamba2 import mamba_dims

    dims = mamba_dims(cfg)
    b_l = max(1, shape.global_batch // dp) // (mb if shape.kind == "train" else 1)
    b_l = max(1, b_l)
    h = dims["n_heads"]
    h_l = h // tp if h % tp == 0 else h  # SSD heads shard over the TP axis
    n_mamba = sum(1 for k in cfg.layer_kinds() if k == "mamba")
    return [
        dict(count=n_mamba, b=b_l, s=shape.seq_len, h=h_l,
             p=dims["head_dim"], n=dims["d_state"], chunk=cfg.ssm.chunk)
    ]


@functools.lru_cache(maxsize=256)
def _measure_attention(
    b: int, sq: int, skv: int, hq: int, hkv: int, hd: int,
    causal: bool, window: Optional[int], train: bool,
) -> Tuple[float, float]:
    """(hbm_bytes, flops) of the standalone blocked-attention module under
    the same analyzer/traffic model as the full cell."""
    from ..models.blocked_attention import blocked_attention

    q = jax.ShapeDtypeStruct((b, sq, hq, hd), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, skv, hkv, hd), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, skv, hkv, hd), jnp.bfloat16)
    pq = jax.ShapeDtypeStruct((b, sq), jnp.int32)
    pk = jax.ShapeDtypeStruct((b, skv), jnp.int32)

    def fwd(q, k, v, pq, pk):
        return blocked_attention(q, k, v, pq, pk, causal, window, 1024, False)

    if train:
        def fn(q, k, v, pq, pk):
            return jax.grad(
                lambda q_, k_, v_: (fwd(q_, k_, v_, pq, pk).astype(jnp.float32) ** 2).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)
    else:
        fn = fwd
    hlo = jax.jit(fn).lower(q, k, v, pq, pk).compile().as_text()
    a = analyze_hlo(hlo)
    return a["hbm_bytes"], a["flops"]


@functools.lru_cache(maxsize=64)
def _measure_ssd(
    b: int, s: int, h: int, p: int, n: int, chunk: int, train: bool
) -> Tuple[float, float]:
    from ..models.mamba2 import ssd_chunked

    x = jax.ShapeDtypeStruct((b, s, h, p), jnp.float32)
    da = jax.ShapeDtypeStruct((b, s, h), jnp.float32)
    bb = jax.ShapeDtypeStruct((b, s, h, n), jnp.float32)
    cc = jax.ShapeDtypeStruct((b, s, h, n), jnp.float32)
    q = min(chunk, s)

    def fwd(x, da, bb, cc):
        y, _ = ssd_chunked(x, da, bb, cc, q)
        return y

    if train:
        def fn(x, da, bb, cc):
            return jax.grad(
                lambda x_, b_, c_: (fwd(x_, da, b_, c_) ** 2).sum(),
                argnums=(0, 1, 2),
            )(x, bb, cc)
    else:
        fn = fwd
    hlo = jax.jit(fn).lower(x, da, bb, cc).compile().as_text()
    a = analyze_hlo(hlo)
    return a["hbm_bytes"], a["flops"]


def _attn_kernel_analytic(site: Dict, train: bool) -> Tuple[float, float]:
    """Pallas flash kernel: stream q,k,v once, write o (fwd); backward
    re-reads q,k,v,o,do and writes dq,dk,dv (recompute P in VMEM)."""
    qb = site["b"] * site["sq"] * site["hq"] * site["hd"] * BF16
    kb = site["b"] * site["skv"] * site["hkv"] * site["hd"] * BF16
    io_fwd = qb + 2 * kb + qb  # q + k + v + o
    io = io_fwd * 3 if train else io_fwd
    skv_eff = min(site["skv"], site["window"]) if site["window"] else site["skv"]
    causal_f = 0.5 if site["causal"] and not site["window"] else 1.0
    flops = (
        4.0 * site["b"] * site["hq"] * site["sq"] * skv_eff * site["hd"] * causal_f
    )
    flops = flops * 3.5 if train else flops  # bwd ≈ 2.5× fwd dots
    return io, flops


def _ssd_kernel_analytic(site: Dict, train: bool) -> Tuple[float, float]:
    xb = site["b"] * site["s"] * site["h"] * site["p"] * F32
    bcb = site["b"] * site["s"] * site["h"] * site["n"] * F32
    dab = site["b"] * site["s"] * site["h"] * F32
    io_fwd = 2 * xb + 2 * bcb + dab  # x, y, B, C, dA
    io = io_fwd * 3 if train else io_fwd
    q = min(site["chunk"], site["s"])
    nc = site["s"] // q
    flops = (
        site["b"] * site["h"] * nc
        * (2 * q * q * site["n"] + 2 * q * q * site["p"] + 4 * q * site["p"] * site["n"])
    )
    flops = flops * 3.5 if train else flops
    return io, flops


def substitution_for_cell(
    cfg: ModelConfig, shape: ShapeConfig, dp: int, tp: int, mb: int
) -> Dict:
    """Returns the per-device traffic/flops delta of swapping the lowered
    scan implementations for the Pallas kernels."""
    train = shape.kind == "train"
    sub_bytes = 0.0
    sub_flops = 0.0
    kernel_bytes = 0.0
    kernel_flops = 0.0
    for site in _attention_sites(cfg, shape, dp, tp, mb):
        mult = site["count"] * (mb if train else 1)
        mb_, mf_ = _measure_attention(
            site["b"], site["sq"], site["skv"], site["hq"], site["hkv"],
            site["hd"], site["causal"], site["window"], train,
        )
        kb_, kf_ = _attn_kernel_analytic(site, train)
        sub_bytes += mult * mb_
        sub_flops += mult * mf_
        kernel_bytes += mult * kb_
        kernel_flops += mult * kf_
    for site in _ssd_sites(cfg, shape, dp, mb, tp):
        mult = site["count"] * (mb if train else 1)
        mb_, mf_ = _measure_ssd(
            site["b"], site["s"], site["h"], site["p"], site["n"],
            site["chunk"], train,
        )
        kb_, kf_ = _ssd_kernel_analytic(site, train)
        sub_bytes += mult * mb_
        sub_flops += mult * mf_
        kernel_bytes += mult * kb_
        kernel_flops += mult * kf_
    return {
        "measured_scan_bytes": sub_bytes,
        "measured_scan_flops": sub_flops,
        "kernel_bytes": kernel_bytes,
        "kernel_flops": kernel_flops,
        "bytes_delta": sub_bytes - kernel_bytes,
        "flops_delta": sub_flops - kernel_flops,
    }
