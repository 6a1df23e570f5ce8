"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step + one decode step on CPU; asserts shapes and no NaNs."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import SMOKE_SHAPE, get_config, list_archs, reduced
from repro.models import build_model, make_fake_batch

ARCHS = list_archs()


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


def _build(name):
    cfg = get_config(name + "-smoke")
    return cfg, build_model(cfg)


@pytest.mark.parametrize("name", ["no-such-arch", "no-such-arch-smoke"])
def test_get_config_unknown_name_names_the_known(name):
    with pytest.raises(KeyError) as err:
        get_config(name)
    message = str(err.value)
    assert "no-such-arch" in message
    assert all(repr(arch) in message for arch in ARCHS)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_name_is_reduced_config_of_same_kinds(arch):
    cfg, small = get_config(arch), get_config(arch + "-smoke")
    assert small == reduced(cfg)
    assert small.name == arch + "-smoke"
    assert small.family == cfg.family
    assert small.pattern == cfg.pattern
    assert set(small.layer_kinds()) == set(cfg.layer_kinds())
    assert small.d_model < cfg.d_model and small.vocab_size < cfg.vocab_size


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch, rng):
    cfg, api = _build(arch)
    params = api.init(rng)
    batch = make_fake_batch(cfg, SMOKE_SHAPE)
    if cfg.family == "encdec":
        logits, aux = api.forward(params, batch["frames"], batch["tokens"])
    elif cfg.family == "vlm":
        logits, aux = api.forward(
            params, batch["tokens"], prefix_embeds=batch["prefix_embeds"]
        )
    else:
        logits, aux = api.forward(params, batch["tokens"])
    b, s = SMOKE_SHAPE.global_batch, SMOKE_SHAPE.seq_len
    assert logits.shape == (b, s, cfg.vocab_size)
    assert jnp.isfinite(jnp.asarray(logits, jnp.float32)).all()
    assert jnp.isfinite(aux)


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_grads_finite(arch, rng):
    cfg, api = _build(arch)
    params = api.init(rng)
    batch = make_fake_batch(cfg, SMOKE_SHAPE)

    def loss(p):
        l, _ = api.loss_fn(p, batch)
        return l

    l, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert jnp.isfinite(l), f"{arch}: loss not finite"
    # a random model over V=256 tokens should start near ln(V)
    assert 2.0 < float(l) < 12.0, f"{arch}: loss {l} implausible"
    flat, _ = jax.tree.flatten(grads)
    assert all(jnp.isfinite(g).all() for g in flat), f"{arch}: NaN grads"
    assert any(jnp.abs(g).max() > 0 for g in flat), f"{arch}: all-zero grads"


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(arch, rng):
    cfg, api = _build(arch)
    params = api.init(rng)
    b, max_len = 2, 32
    cache = api.init_cache(b, max_len)
    tokens = jnp.zeros((b, 1), dtype=jnp.int32)
    step = jax.jit(api.decode_step)
    logits, cache = step(params, cache, tokens, jnp.int32(0))
    logits2, cache = step(params, cache, tokens + 1, jnp.int32(1))
    assert logits.shape == (b, 1, cfg.vocab_size)
    assert jnp.isfinite(jnp.asarray(logits2, jnp.float32)).all()


@pytest.mark.parametrize("arch", ["mamba2-370m", "zamba2-7b", "h2o-danube-1.8b", "gemma3-1b"])
def test_decode_matches_forward(arch, rng):
    """Prefill-vs-decode consistency: feeding tokens one-by-one through the
    cache must reproduce the teacher-forced logits."""
    cfg, api = _build(arch)
    params = api.init(rng)
    b, s = 1, 8
    tokens = jax.random.randint(rng, (b, s), 0, cfg.vocab_size, dtype=jnp.int32)
    full_logits, _ = api.forward(params, tokens)
    cache = api.init_cache(b, s)
    step = jax.jit(api.decode_step)
    outs = []
    for i in range(s):
        lg, cache = step(params, cache, tokens[:, i : i + 1], jnp.int32(i))
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    assert jnp.allclose(
        jnp.asarray(full_logits, jnp.float32),
        jnp.asarray(dec_logits, jnp.float32),
        atol=2e-2,
        rtol=2e-2,
    ), f"{arch}: max err {jnp.abs(full_logits - dec_logits).max()}"
