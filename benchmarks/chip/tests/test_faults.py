"""Each fault the serve cells can have, planted in the timed path under a
whole run at CPU sizes, comes out as not correct.  The cells run on one
chip, so there is no exchange between chips to leave out."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest
from conftest import small_cell

import harness


def unchanged_state(api):
    """The step returns the cache it was given."""

    def step(params, cache, tokens, pos):
        logits, _ = api.decode_step(params, cache, tokens, pos)
        return logits, cache

    return dataclasses.replace(api, decode_step=step)


def half_batch(api):
    """The second half of the batch is served the first half's outputs."""

    def step(params, cache, tokens, pos):
        logits, cache = api.decode_step(params, cache, tokens, pos)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]]), cache

    return dataclasses.replace(api, decode_step=step)


def altered_token(api):
    """Row 0's token is shifted to the next id at every 4th position."""

    def step(params, cache, tokens, pos):
        logits, cache = api.decode_step(params, cache, tokens, pos)
        shifted = logits.at[0].set(jnp.roll(logits[0], 1, axis=-1))
        return jnp.where(pos % 4 == 3, shifted, logits), cache

    return dataclasses.replace(api, decode_step=step)


@pytest.mark.parametrize("fault", [unchanged_state, half_batch, altered_token])
@pytest.mark.parametrize("name", ["h2o-danube-1.8b.chat", "mamba2-370m.fleet"])
def test_fault_is_not_correct(name, fault, counter):
    cell = small_cell(name)
    out = harness.run_cell(
        cell, seed=2**31 + 77, seconds=2.0, traced=False, t_proc0=time.monotonic(),
        counter=counter, break_step=fault,
    )
    assert not out["correct"], out["checks"]
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    jax.clear_caches()
