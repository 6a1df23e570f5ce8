"""Batched decode engine: prefill + greedy/temperature decode over a KV (or
SSM-state) cache.

``serve_step`` — one new token for every sequence in the batch against a
cache of ``max_len`` — is the decode program: ``jit_serve_step`` compiles
it with the KV cache donated, and every serving replica (``DecodeEngine``)
and the chip benchmark's cells run it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from .. import tracing
from ..checkpoint import decode_array, unflatten_tree
from ..models.registry import ModelApi
from ..models.transformer import STATE_LEAVES


def params_from_input(cu_ctx, weights_du: str) -> Any:
    """Model params from a checkpoint DU staged as a CU *input*.

    This is the serving cold-start path: every serve CU declares the
    weights DU in ``input_data``, so each replica's stage-in goes through
    the transfer service — recording a ``du:access`` — and after
    ``promote_after`` accesses the TierManager promotes the DU into the
    site's mem-tier cache.  The rest of the fleet then cold-starts from
    the promoted hot replica instead of re-pulling across the DCN (enable
    with ``tier_cache_bytes``/``tier_auto_promote`` on the Session).

    A ``du.load`` span over each file's ``du.read`` and ``ckpt.decode``.
    """
    with tracing.span("du.load", du=weights_du):
        items = {}
        for rel in cu_ctx.input_manifest(weights_du):
            if rel.startswith("params/") and rel.endswith(".npy"):
                items[rel[7:-4]] = decode_array(cu_ctx.read_input(weights_du, rel))
        return unflatten_tree(items)


def split_cache(cache: Dict) -> Tuple[Dict, Dict]:
    """(kv, state): a decode cache's KV leaves and its SSM and conv state
    leaves (``STATE_LEAVES``), each in the cache's own nesting."""
    kv, state = {}, {}
    for key, sub in cache.items():
        if isinstance(sub, dict):
            kv[key], state[key] = split_cache(sub)
        else:
            (state if key in STATE_LEAVES else kv)[key] = sub
    return kv, state


def join_cache(kv: Dict, state: Dict) -> Dict:
    """The decode cache ``split_cache`` took apart."""
    out = dict(kv)
    for key, sub in state.items():
        out[key] = join_cache(out.get(key, {}), sub) if isinstance(sub, dict) else sub
    return out


def tree_bytes(tree: Any) -> int:
    return sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(tree))


def cache_bytes(cache: Any) -> Dict[str, int]:
    """``kv_bytes`` and ``ssm_bytes`` (SSM and conv state) of a decode cache."""
    kv, state = split_cache(cache)
    return {"kv_bytes": tree_bytes(kv), "ssm_bytes": tree_bytes(state)}


def make_serve_step(api: ModelApi) -> Callable:
    """serve_step(params, kv, state, tokens [B,1], pos) → (next_tokens, kv,
    state), the decode cache split by ``split_cache`` so that its KV alone
    can be donated: the step writes one token per KV leaf in place, and
    rewrites the state whole."""

    def serve_step(params, kv, state, tokens, pos_index):
        cache = join_cache(kv, state)
        logits, cache = api.decode_step(params, cache, tokens, pos_index)
        next_tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (next_tokens[:, None], *split_cache(cache))

    return serve_step


def jit_serve_step(api: ModelApi) -> Callable:
    """``make_serve_step`` jitted with the KV argument donated."""
    return jax.jit(make_serve_step(api), donate_argnums=(1,))


@dataclasses.dataclass
class DecodeRequest:
    prompt: jnp.ndarray  # [S] int32
    max_new_tokens: int = 16


class DecodeEngine:
    """Minimal batched engine: static batch, greedy sampling; the
    pure-compute layer of a serve CU.

    Its step is ``jit_serve_step``: each writes its tokens into the
    cache's KV in place.

    Its host side is traced: ``engine.init`` (with the cache's
    ``kv_bytes``, ``ssm_bytes`` and the ``donated_bytes`` of its KV, also
    counted under those names),
    ``engine.prefill`` and ``engine.generate`` spans (the tracing and
    compiling of the step land under the first call as ``jit.*`` records),
    and an ``engine.steps`` counter of ``serve_step`` dispatches.
    """

    def __init__(self, api: ModelApi, params: Any, batch: int, max_len: int):
        with tracing.span("engine.init", batch=batch, max_len=max_len) as sp:
            self.api = api
            self.params = params
            self.batch = batch
            self.max_len = max_len
            self._kv, self._state = split_cache(api.init_cache(batch, max_len))
            self._step = jit_serve_step(api)
            self._pos = 0
            kv = tree_bytes(self._kv)  # all of it donated to each step
            sizes = {
                "kv_bytes": kv,
                "ssm_bytes": tree_bytes(self._state),
                "donated_bytes": kv,
            }
            sp.attrs.update(sizes)
            for name, n in sizes.items():
                tracing.count(name, n)

    @property
    def cache(self) -> Dict:
        """The whole decode cache, KV and state."""
        return join_cache(self._kv, self._state)

    @classmethod
    def from_cu_context(
        cls, api: ModelApi, cu_ctx, weights_du: str, batch: int, max_len: int
    ) -> "DecodeEngine":
        """Build a replica engine inside a serve CU, loading weights from
        the (tier-cache-eligible) checkpoint DU declared as its input."""
        return cls(api, params_from_input(cu_ctx, weights_du), batch, max_len)

    def prefill(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """Feed prompt tokens (teacher-forced, one step at a time — a
        production engine would batch this; CPU tests keep prompts short)."""
        with tracing.span("engine.prefill", tokens=tokens.shape[1]):
            return self._feed(tokens)

    def _feed(self, tokens: jnp.ndarray) -> jnp.ndarray:
        b, s = tokens.shape
        assert b == self.batch
        last = None
        for i in range(s):
            last, self._kv, self._state = self._step(
                self.params, self._kv, self._state, tokens[:, i : i + 1],
                jnp.int32(self._pos),
            )
            self._pos += 1
        tracing.count("engine.steps", s)
        return last

    def generate(self, tokens: jnp.ndarray, max_new_tokens: int) -> jnp.ndarray:
        """Greedy-decode continuation; returns [B, max_new_tokens]."""
        with tracing.span("engine.generate", tokens=max_new_tokens):
            cur = self._feed(tokens)
            out = [cur]
            for _ in range(max_new_tokens - 1):
                cur, self._kv, self._state = self._step(
                    self.params, self._kv, self._state, cur, jnp.int32(self._pos)
                )
                self._pos += 1
                out.append(cur)
            tracing.count("engine.steps", max(max_new_tokens - 1, 0))
            return jnp.concatenate(out, axis=1)
