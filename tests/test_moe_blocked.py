"""MoE routing/dispatch invariants + chunked CE."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import MoEConfig, reduced
from repro.models.layers import chunked_cross_entropy, softmax_cross_entropy, unembed
from repro.models.moe import _capacity, init_moe, moe_mlp_local


def moe_cfg(n_experts=8, top_k=2, cap=4.0):
    base = reduced(get_config("granite-moe-3b-a800m"))
    import dataclasses

    return dataclasses.replace(
        base,
        moe=MoEConfig(n_experts=n_experts, top_k=top_k, d_ff_expert=32,
                      capacity_factor=cap),
    )


def test_moe_output_shape_and_aux():
    cfg = moe_cfg()
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, aux = moe_mlp_local(params, x, cfg)
    assert y.shape == x.shape
    assert jnp.isfinite(y).all()
    # switch aux loss ≥ 1 (equality at perfect balance)
    assert float(aux) >= 0.99


def test_moe_grads_flow_to_router_and_experts():
    cfg = moe_cfg()
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.d_model))

    def loss(p):
        y, aux = moe_mlp_local(p, x, cfg)
        return (y**2).mean() + 0.01 * aux

    grads = jax.grad(loss)(params)
    assert float(jnp.abs(grads["router"]["w"]).max()) > 0
    assert float(jnp.abs(grads["gate"]).max()) > 0


def test_moe_capacity_drops_tokens():
    """With capacity_factor → tiny, most tokens are dropped: output ~ 0 for
    dropped tokens but finite everywhere."""
    cfg_full = moe_cfg(cap=64.0)
    cfg_tight = moe_cfg(cap=0.01)
    params = init_moe(jax.random.PRNGKey(0), cfg_full)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg_full.d_model))
    y_full, _ = moe_mlp_local(params, x, cfg_full)
    y_tight, _ = moe_mlp_local(params, x, cfg_tight)
    assert float(jnp.abs(y_full).mean()) > float(jnp.abs(y_tight).mean())


def test_moe_expert_padding_never_routed():
    cfg = moe_cfg(n_experts=5, top_k=2)
    params = init_moe(jax.random.PRNGKey(0), cfg, ep=4)  # pads 5 → 8
    assert params["gate"].shape[0] == 8
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.d_model))
    y, _ = moe_mlp_local(params, x, cfg)
    assert jnp.isfinite(y).all()


def test_capacity_rounding():
    cfg = moe_cfg(n_experts=8, top_k=2, cap=1.25)
    c = _capacity(1024, cfg)
    assert c % 8 == 0 and c >= 1024 * 2 * 1.25 / 8


# ----------------------------------------------------------- chunked CE
def test_chunked_ce_matches_full():
    cfg = reduced(get_config("h2o-danube-1.8b"))
    from repro.models.layers import init_embedding

    params = init_embedding(jax.random.PRNGKey(0), cfg)
    b, s = 2, 48
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model)) * 0.1
    labels = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab_size)
    full = softmax_cross_entropy(unembed(x, params, cfg), labels)
    for chunk in (16, 17, 48, 100):
        ck = chunked_cross_entropy(x, params, cfg, labels, chunk=chunk)
        assert float(jnp.abs(ck - full)) < 1e-5, chunk


def test_chunked_ce_grad_matches_full():
    cfg = reduced(get_config("h2o-danube-1.8b"))
    from repro.models.layers import init_embedding

    params = init_embedding(jax.random.PRNGKey(0), cfg)
    b, s = 1, 32
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model)) * 0.1
    labels = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab_size)
    g_full = jax.grad(
        lambda x: softmax_cross_entropy(unembed(x, params, cfg), labels)
    )(x)
    g_chunk = jax.grad(
        lambda x: chunked_cross_entropy(x, params, cfg, labels, chunk=8)
    )(x)
    assert float(jnp.abs(g_full - g_chunk).max()) < 1e-5


# ------------------------------------------------- windowed ring KV cache
def test_ring_cache_wraps_and_matches_forward():
    """Decode with a window-sized ring cache must equal teacher forcing for
    an SWA model even after the ring wraps several times."""
    import dataclasses

    from repro.models import build_model

    cfg = reduced(get_config("h2o-danube-1.8b"), sliding_window=4, n_layers=2)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, s = 1, 12  # 3× wrap of the 4-slot ring
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
    full_logits, _ = api.forward(params, tokens)
    cache = api.init_cache(b, s)
    # ring allocation: swa cache length == window
    assert cache["groups"]["pos0"]["k"].shape[2] == 4
    step = jax.jit(api.decode_step)
    outs = []
    for i in range(s):
        lg, cache = step(params, cache, tokens[:, i : i + 1], jnp.int32(i))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(full_logits, np.float32),
        np.asarray(dec, np.float32),
        atol=2e-2,
        rtol=2e-2,
    )


def test_int8_kv_cache_decode_matches_forward():
    """int8-quantized ring KV cache: decode ≈ teacher forcing (quantization
    noise bounded) — the §Perf decode-memory lever."""
    import dataclasses

    from repro.models import build_model

    cfg = dataclasses.replace(
        reduced(get_config("h2o-danube-1.8b"), sliding_window=4, n_layers=2),
        kv_cache_dtype="int8",
    )
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, s = 1, 12
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
    full, _ = api.forward(params, tokens)
    cache = api.init_cache(b, s)
    assert cache["groups"]["pos0"]["k"].dtype == jnp.int8
    assert cache["groups"]["pos0"]["k"].shape[2] == 4  # ring + int8 compose
    step = jax.jit(api.decode_step)
    outs = []
    for i in range(s):
        lg, cache = step(params, cache, tokens[:, i : i + 1], jnp.int32(i))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    scale = float(jnp.abs(jnp.asarray(full, jnp.float32)).max())
    err = float(jnp.abs(jnp.asarray(full, jnp.float32) - jnp.asarray(dec, jnp.float32)).max())
    assert err / max(scale, 1.0) < 0.05
