"""The decode step writes its KV cache in place: each layer reads its cached
K and V as they are, attends to its own token beside them, and hands back
only that token's K and V; the step writes one token per KV leaf into a
donated cache.  Checked on the CPU at reduced widths against the
written-first formula, the token written into the cache first and one
softmax over all slots (``gqa_attention``), and against the teacher-forced
forward."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_config, reduced
from repro.models import attention, build_model
from repro.serving import DecodeEngine

_ZAMBA2 = get_config("zamba2-7b")
_DANUBE = reduced(get_config("h2o-danube-1.8b"))

#: name → (config, max_len, prompt tokens, generated tokens); 16 tokens in
#: all, whole chunks of the Mamba2 forward
CASES = {
    "h2o-danube-1.8b": (_DANUBE, 24, 6, 10),
    "zamba2-7b": (
        reduced(_ZAMBA2, n_layers=13, pattern=_ZAMBA2.layer_kinds()[:13]),
        24,
        6,
        10,
    ),
    # 5 of 6 layers keep a ring of 8 slots, which 16 tokens wrap
    "gemma3-1b": (reduced(get_config("gemma3-1b"), sliding_window=8), 24, 6, 10),
    "h2o-danube-1.8b-int8": (
        dataclasses.replace(_DANUBE, kv_cache_dtype="int8"),
        24,
        6,
        10,
    ),
}
B = 2


def _written_first(q, ck, cv, k, v, seen, scale=None):
    """The written-first formula: the token's K and V among the cache's
    slots, and one softmax over every slot it may see (``gqa_attention``)."""
    b = q.shape[0]
    keys = jnp.concatenate([ck, k], axis=1)
    values = jnp.concatenate([cv, v], axis=1)
    valid = jnp.concatenate([seen, jnp.ones((b, 1), bool)], axis=1)
    zeros = jnp.zeros((b, keys.shape[1]), jnp.int32)
    return attention.gqa_attention(
        q, keys, values, zeros[:, :1], zeros, causal=False, kv_valid=valid,
        scale=scale,
    )


def _assert_close(tree, want):
    """Cache against cache: int8 K and V may differ by one step of rounding."""

    def close(a, b):
        atol = 1 if a.dtype == jnp.int8 else 2e-2
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=atol, rtol=2e-2)

    jax.tree.map(close, tree, want)


def _run(cfg, params, max_len, tokens):
    """Logits of ``decode_step`` fed ``tokens`` one at a time, and the cache."""
    api = build_model(cfg)
    step = jax.jit(api.decode_step)
    cache = api.init_cache(tokens.shape[0], max_len)
    out = []
    for i in range(tokens.shape[1]):
        logits, cache = step(params, cache, tokens[:, i : i + 1], jnp.int32(i))
        out.append(logits[:, 0])
    return jnp.stack(out, axis=1), cache


def _tokens(cfg, n, seed=3):
    key = jax.random.PRNGKey(seed)
    return jax.random.randint(key, (B, n), 0, cfg.vocab_size, jnp.int32)


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    cfg, max_len, n_prompt, n_new = CASES[request.param]
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return request.param, cfg, params, max_len, _tokens(cfg, n_prompt), n_new


def test_engine_tokens_match_written_first(case, monkeypatch):
    """The engine's greedy tokens are the written-first formula's; its
    cache stays readable, the KV it gave each step donated and the state
    kept."""
    name, cfg, params, max_len, prompt, n_new = case
    engine = DecodeEngine(build_model(cfg), params, batch=B, max_len=max_len)
    first_kv = engine._kv
    first_state = engine._state
    got = np.asarray(engine.generate(prompt, n_new))
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(first_kv))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(first_state))
    for leaf in jax.tree.leaves(engine.cache):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()

    monkeypatch.setattr(attention, "decode_gqa_attention", _written_first)
    ref = DecodeEngine(build_model(cfg), params, batch=B, max_len=max_len)
    want = np.asarray(ref.generate(prompt, n_new))
    np.testing.assert_array_equal(got, want, err_msg=name)
    _assert_close(engine.cache, ref.cache)


def test_decode_logits_match_written_first(case, monkeypatch):
    """``decode_step``'s logits and cache are the written-first formula's
    within the tolerance of ``test_decode_matches_forward``; without int8
    KV also the forward's."""
    name, cfg, params, max_len, prompt, n_new = case
    tokens = jnp.concatenate([prompt, _tokens(cfg, n_new, seed=4)], axis=1)
    got, cache = _run(cfg, params, max_len, tokens)
    if cfg.kv_cache_dtype != "int8":
        full, _ = build_model(cfg).forward(params, tokens)
        np.testing.assert_allclose(
            np.asarray(full, np.float32), np.asarray(got, np.float32),
            atol=2e-2, rtol=2e-2, err_msg=name,
        )
    monkeypatch.setattr(attention, "decode_gqa_attention", _written_first)
    want, want_cache = _run(cfg, params, max_len, tokens)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-2, rtol=2e-2, err_msg=name,
    )
    _assert_close(cache, want_cache)


@pytest.mark.parametrize(
    "window, slots, index",
    [(None, 16, 0), (None, 16, 9), (8, 8, 5), (8, 8, 21), (None, 8, 30)],
    ids=["first", "full-length", "ring", "ring-wrapped", "full-wrapped"],
)
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_block_matches_written_cache(window, slots, index, kv_dtype):
    """The attention block's decode path against the written-first code:
    the token written at slot ``index % slots`` (quantized on an int8
    cache), every slot's position from the ring formula, one softmax; it
    returns the token as the cache holds it."""
    cfg = reduced(get_config("h2o-danube-1.8b"), kv_cache_dtype=kv_dtype)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    params = attention.init_attention(ks[0], cfg)
    x = jax.random.normal(ks[1], (B, 1, cfg.d_model))
    shape = (B, slots, cfg.n_kv_heads, cfg.head_dim_)
    if kv_dtype == "int8":
        ck, k_s = attention._q8(jax.random.normal(ks[2], shape))
        cv, v_s = attention._q8(jax.random.normal(ks[3], shape))
        cache = {"k": ck, "v": cv, "k_scale": k_s, "v_scale": v_s}
    else:
        cache = {"k": jax.random.normal(ks[2], shape), "v": jax.random.normal(ks[3], shape)}
    pos = jnp.full((B, 1), index, jnp.int32)
    got, token = attention.attention_block(
        params, x, pos, cfg, window=window, cache=cache, cache_index=jnp.int32(index)
    )

    # the written-first code, inline
    hd = cfg.head_dim_
    q = attention.apply_rope(
        attention.dense(x, params["q"]).reshape(B, 1, cfg.n_heads, hd), pos, cfg.rope_theta
    )
    k = attention.apply_rope(
        attention.dense(x, params["k"]).reshape(B, 1, cfg.n_kv_heads, hd), pos, cfg.rope_theta
    )
    v = attention.dense(x, params["v"]).reshape(B, 1, cfg.n_kv_heads, hd)
    slot = index % slots
    if kv_dtype == "int8":
        k8, ks_ = attention._q8(k)
        v8, vs_ = attention._q8(v)
        want_token = {"k": k8, "v": v8, "k_scale": ks_, "v_scale": vs_}
        full = {
            name: jax.lax.dynamic_update_slice_in_dim(cache[name], t, slot, axis=1)
            for name, t in want_token.items()
        }
        keys = attention._dq8(full["k"], full["k_scale"], jnp.float32)
        values = attention._dq8(full["v"], full["v_scale"], jnp.float32)
    else:
        want_token = {"k": k, "v": v}
        keys = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        values = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    slot_ids = jnp.arange(slots)[None, :]
    positions_k = index - jnp.mod(index - slot_ids, slots)
    positions_k = jnp.broadcast_to(positions_k, (B, slots)).astype(jnp.int32)
    out = attention.gqa_attention(
        q, keys, values, pos, positions_k, causal=True, window=window,
        kv_valid=positions_k >= 0,
    )
    want = attention.dense(out.reshape(B, 1, cfg.n_heads * hd), params["o"])

    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
    assert set(token) == set(want_token)
    for name, t in want_token.items():
        np.testing.assert_allclose(np.asarray(token[name]), np.asarray(t), atol=1e-6)


@pytest.mark.parametrize("name", [*CASES, "mamba2-370m"])
def test_engine_init_records_donated_bytes(name):
    """``donated_bytes`` is the KV's: all of it, and 0 without KV."""
    if name == "mamba2-370m":
        cfg, max_len = reduced(get_config(name)), 16
    else:
        cfg, max_len = CASES[name][:2]
    api = build_model(cfg)
    before = tracing.counters()
    DecodeEngine(api, api.init(jax.random.PRNGKey(0)), batch=B, max_len=max_len)
    rec = [r for r in tracing.spans() if r.name == "engine.init"][-1]
    kv = rec.attrs["kv_bytes"]
    assert rec.attrs["donated_bytes"] == kv
    assert (kv == 0) == (name == "mamba2-370m")
    after = tracing.counters()
    assert after["donated_bytes"] - before.get("donated_bytes", 0) == kv
