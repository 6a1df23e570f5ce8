"""Per-kernel validation: hypothesis sweeps over shapes/dtypes, allclose
against the pure-jnp ref oracles, and the attention kernels against the
model's own attention functions at its head widths (kernels run in
interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.kernels.cases import TOL_BF16, TOL_F32
from repro.kernels.cases import max_normalized_error as _maxerr
from repro.kernels.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref, rmsnorm_residual_ref
from repro.kernels.ssd_scan import ssd
from repro.kernels.ssd_scan.ref import ssd_chunked
from repro.models.attention import (
    decode_gqa_attention,
    gqa_attention,
    seen_slots,
    write_token,
)


def _tol(dtype):
    return TOL_BF16 if dtype == jnp.bfloat16 else TOL_F32


# ------------------------------------------------------------ flash attn
@settings(max_examples=12, deadline=None)
@given(
    b=st.integers(1, 2),
    sq=st.sampled_from([17, 64, 130, 256]),
    hkv=st.sampled_from([1, 2]),
    g=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([32, 64, 80]),
    causal=st.booleans(),
    window=st.sampled_from([None, 16, 100]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_flash_attention_matches_ref(b, sq, hkv, g, d, causal, window, dtype):
    hq = hkv * g
    rng = jax.random.PRNGKey(b * 1000 + sq)
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, sq, hkv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, sq, hkv, d), jnp.float32).astype(dtype)
    out = flash_attention(
        q, k, v, causal=causal, window=window, block_q=64, block_k=64
    )
    ref = attention_ref(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        window=window,
    ).transpose(0, 2, 1, 3)
    assert _maxerr(out, ref) < _tol(dtype)


def test_flash_attention_long_noncausal_cross_length():
    rng = jax.random.PRNGKey(7)
    q = jax.random.normal(rng, (1, 64, 4, 64))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 320, 2, 64))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (1, 320, 2, 64))
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=128)
    ref = attention_ref(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=False,
    ).transpose(0, 2, 1, 3)
    assert _maxerr(out, ref) < 2e-5


# ----------------------------------------------------------- decode attn
@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 3),
    sk=st.sampled_from([64, 257, 512]),
    hkv=st.sampled_from([1, 2]),
    g=st.sampled_from([1, 4]),
    d=st.sampled_from([64, 80]),
    window=st.sampled_from([None, 64]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_decode_attention_matches_ref(b, sk, hkv, g, d, window, dtype):
    hq = hkv * g
    rng = jax.random.PRNGKey(b * 31 + sk)
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (b, 1, hq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), jnp.float32).astype(dtype)
    pos = jax.random.randint(ks[3], (b,), 0, sk, dtype=jnp.int32)
    out = decode_attention(q, k, v, pos, window=window, block_k=128)
    ref = decode_attention_ref(
        q[:, 0],
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        pos,
        window=window,
    )
    assert _maxerr(out[:, 0], ref) < _tol(dtype)


def test_decode_matches_flash_at_last_position():
    """Cross-kernel consistency: decode at position S-1 == last row of a
    causal prefill."""
    rng = jax.random.PRNGKey(3)
    b, s, hq, hkv, d = 2, 128, 4, 2, 64
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    pre = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    pos = jnp.full((b,), s - 1, jnp.int32)
    dec = decode_attention(q[:, -1:], k, v, pos, block_k=128)
    assert _maxerr(pre[:, -1:], dec) < 2e-5


# ----------------------------------------- attention kernels vs the model
#: (config, scale of the scores the model uses): danube's 32 q / 8 kv heads
#: of 80 at head_dim**-0.5, zamba2's shared block's 32 / 32 of 224 at
#: (head_dim/2)**-0.5
MODEL_HEADS = {
    "danube": ("h2o-danube-1.8b", lambda d: d**-0.5),
    "zamba2": ("zamba2-7b", lambda d: (d / 2) ** -0.5),
}
MODEL_CASES = [
    pytest.param(model, window, id=f"{model}-{'causal' if window is None else f'window{window}'}")
    for model in MODEL_HEADS
    for window in (None, 16)
]


def _heads(model):
    name, scale = MODEL_HEADS[model]
    cfg = get_config(name)
    d = cfg.head_dim_
    # the kernels scale by head_dim**-0.5: a query scaled by the ratio
    # gives them the model's scale
    return cfg.n_heads, cfg.n_kv_heads, d, scale(d), scale(d) / d**-0.5


@pytest.mark.parametrize("model, window", MODEL_CASES)
def test_flash_attention_matches_model(model, window):
    hq, hkv, d, scale, q_ratio = _heads(model)
    b, s = 2, 64
    ks = jax.random.split(jax.random.PRNGKey(hq + d), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    ref = gqa_attention(q, k, v, pos, pos, causal=True, window=window, scale=scale)
    out = flash_attention(
        q * q_ratio, k, v, causal=True, window=window, block_q=64, block_k=64
    )
    assert _maxerr(out, ref) < TOL_F32


@pytest.mark.parametrize("model, window", MODEL_CASES)
def test_decode_attention_matches_model(model, window):
    """The kernel reads the cache with the token already written at its
    slot; the model scores the cache's ``seen_slots`` and the token's own
    key apart."""
    hq, hkv, d, scale, q_ratio = _heads(model)
    b, slots, index = 2, 64, 40
    ks = jax.random.split(jax.random.PRNGKey(hq + d), 5)
    q = jax.random.normal(ks[0], (b, 1, hq, d))
    ck = jax.random.normal(ks[1], (b, slots, hkv, d))
    cv = jax.random.normal(ks[2], (b, slots, hkv, d))
    k = jax.random.normal(ks[3], (b, 1, hkv, d))
    v = jax.random.normal(ks[4], (b, 1, hkv, d))
    cache_index = jnp.int32(index)
    positions = jnp.full((b, 1), index, jnp.int32)
    seen = seen_slots(positions, cache_index, slots, window)
    ref = decode_gqa_attention(q, ck, cv, k, v, seen, scale=scale)
    out = decode_attention(
        q * q_ratio,
        write_token(ck, k, cache_index, axis=1),
        write_token(cv, v, cache_index, axis=1),
        positions[:, 0],
        window=window,
        block_k=64,
    )
    assert _maxerr(out, ref) < TOL_F32


# -------------------------------------------------------------- ssd scan
@settings(max_examples=8, deadline=None)
@given(
    b=st.integers(1, 2),
    nc=st.integers(1, 4),
    h=st.sampled_from([1, 4]),
    p=st.sampled_from([32, 64]),
    n=st.sampled_from([16, 64]),
    chunk=st.sampled_from([16, 64]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_ssd_matches_ref(b, nc, h, p, n, chunk, dtype):
    s = nc * chunk
    rng = jax.random.PRNGKey(s + h)
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32).astype(dtype)
    da = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    B_ = (jax.random.normal(ks[2], (b, s, h, n), jnp.float32) * 0.5).astype(dtype)
    C_ = (jax.random.normal(ks[3], (b, s, h, n), jnp.float32) * 0.5).astype(dtype)
    y_k, st_k = ssd(x, da, B_, C_, chunk=chunk)
    y_r, st_r = ssd_chunked(
        x.astype(jnp.float32),
        da,
        B_.astype(jnp.float32),
        C_.astype(jnp.float32),
        chunk,
    )
    tol = 0.05 if dtype == jnp.bfloat16 else 1e-4
    assert _maxerr(y_k, y_r) < tol
    assert _maxerr(st_k, st_r) < tol


def test_ssd_state_continuity():
    """Splitting a sequence in half and passing the state must equal the
    full-sequence run (the invariant decode relies on)."""
    rng = jax.random.PRNGKey(9)
    b, s, h, p, n, chunk = 1, 128, 2, 32, 32, 32
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (b, s, h, p))
    da = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    B_ = jax.random.normal(ks[2], (b, s, h, n)) * 0.5
    C_ = jax.random.normal(ks[3], (b, s, h, n)) * 0.5
    y_full, st_full = ssd_chunked(x, da, B_, C_, chunk)
    half = s // 2
    y1, st1 = ssd_chunked(x[:, :half], da[:, :half], B_[:, :half], C_[:, :half], chunk)
    y2, st2 = ssd_chunked(
        x[:, half:], da[:, half:], B_[:, half:], C_[:, half:], chunk,
        initial_state=st1,
    )
    assert _maxerr(jnp.concatenate([y1, y2], axis=1), y_full) < 1e-4
    assert _maxerr(st2, st_full) < 1e-4


def test_ssd_kernel_takes_initial_state():
    """The kernel starts its scan from a given state (no reference
    fallback): second half from the first half's state == full run."""
    rng = jax.random.PRNGKey(11)
    b, s, h, p, n, chunk = 1, 128, 2, 32, 32, 32
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (b, s, h, p))
    da = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    B_ = jax.random.normal(ks[2], (b, s, h, n)) * 0.5
    C_ = jax.random.normal(ks[3], (b, s, h, n)) * 0.5
    y_full, st_full = ssd_chunked(x, da, B_, C_, chunk)
    half = s // 2
    _, st1 = ssd(x[:, :half], da[:, :half], B_[:, :half], C_[:, :half], chunk=chunk)
    y2, st2 = ssd(
        x[:, half:], da[:, half:], B_[:, half:], C_[:, half:], chunk=chunk,
        initial_state=st1,
    )
    assert _maxerr(y2, y_full[:, half:]) < 1e-4
    assert _maxerr(st2, st_full) < 1e-4


# --------------------------------------------------------------- rmsnorm
@settings(max_examples=10, deadline=None)
@given(
    rows=st.sampled_from([1, 7, 64, 300]),
    d=st.sampled_from([128, 256, 1024]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
    with_residual=st.booleans(),
)
def test_rmsnorm_matches_ref(rows, d, dtype, with_residual):
    rng = jax.random.PRNGKey(rows * 7 + d)
    x = jax.random.normal(rng, (rows, d), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(rng, 1), (d,), jnp.float32) * 0.1
    if with_residual:
        r = jax.random.normal(jax.random.fold_in(rng, 2), (rows, d)).astype(dtype)
        out, res = rmsnorm(x, w, residual=r)
        ref, rres = rmsnorm_residual_ref(x, r, w)
        assert _maxerr(res, rres) < _tol(dtype)
    else:
        out = rmsnorm(x, w)
        ref = rmsnorm_ref(x, w)
    assert _maxerr(out, ref) < _tol(dtype)
