"""Model registry: one uniform API over every architecture family.

``build_model(cfg)`` returns a :class:`ModelApi` whose members are plain
functions suitable for ``jax.jit`` / ``jax.eval_shape`` — init never
allocates under ``eval_shape``, so shape checks stay allocation-free.

``batch_spec`` describes the logical model inputs of a
:class:`ShapeConfig` (train / prefill / decode); ``make_fake_batch`` fills
them with random data.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from . import transformer, whisper


@dataclasses.dataclass(frozen=True)
class ModelApi:
    cfg: ModelConfig
    init: Callable[[Any], Dict]
    forward: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]]
    loss_fn: Callable[..., Tuple[jnp.ndarray, Dict]]
    init_cache: Callable[[int, int], Dict]
    decode_step: Callable[..., Tuple[jnp.ndarray, Dict]]
    batch_spec: Callable[[ShapeConfig], Dict[str, Tuple[Tuple[int, ...], Any]]]


def _lm_batch_spec(cfg: ModelConfig, shape: ShapeConfig):
    b, s = shape.global_batch, shape.seq_len
    cdt = jnp.dtype(cfg.compute_dtype)
    if shape.kind == "decode":
        return {"tokens": ((b, 1), jnp.int32)}
    if cfg.family == "vlm":
        p = cfg.vlm.n_patches
        spec = {
            "tokens": ((b, s - p), jnp.int32),
            "prefix_embeds": ((b, p, cfg.d_model), cdt),
        }
        if shape.kind == "train":
            spec["labels"] = ((b, s - p), jnp.int32)
        return spec
    if cfg.family == "encdec":
        spec = {
            "frames": ((b, cfg.encdec.n_frames, cfg.d_model), cdt),
            "tokens": ((b, s), jnp.int32),
        }
        if shape.kind == "train":
            spec["labels"] = ((b, s), jnp.int32)
        return spec
    spec = {"tokens": ((b, s), jnp.int32)}
    if shape.kind == "train":
        spec["labels"] = ((b, s), jnp.int32)
    return spec


def build_model(cfg: ModelConfig, ep: int = 1) -> ModelApi:
    """The model's functions with ``cfg`` bound; ``ep`` pads the MoE
    experts to a multiple of the expert-parallel axis size."""
    if cfg.family == "encdec":
        return ModelApi(
            cfg=cfg,
            init=functools.partial(whisper.init_encdec, cfg=cfg, ep=ep),
            forward=functools.partial(whisper.forward, cfg=cfg),
            loss_fn=functools.partial(whisper.loss_fn, cfg=cfg),
            init_cache=functools.partial(whisper.init_encdec_cache, cfg),
            decode_step=functools.partial(whisper.decode_step, cfg=cfg),
            batch_spec=functools.partial(_lm_batch_spec, cfg),
        )
    return ModelApi(
        cfg=cfg,
        init=functools.partial(transformer.init_lm, cfg=cfg, ep=ep),
        forward=functools.partial(transformer.forward, cfg=cfg),
        loss_fn=functools.partial(transformer.loss_fn, cfg=cfg),
        init_cache=functools.partial(transformer.init_lm_cache, cfg),
        decode_step=functools.partial(transformer.decode_step, cfg=cfg),
        batch_spec=functools.partial(_lm_batch_spec, cfg),
    )


def make_fake_batch(
    cfg: ModelConfig, shape: ShapeConfig, rng: Optional[Any] = None
) -> Dict[str, jnp.ndarray]:
    """Materialize a random batch matching ``batch_spec`` (smoke tests)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    spec = _lm_batch_spec(cfg, shape)
    out = {}
    for i, (name, (shp, dtype)) in enumerate(sorted(spec.items())):
        k = jax.random.fold_in(rng, i)
        if jnp.issubdtype(dtype, jnp.integer):
            out[name] = jax.random.randint(k, shp, 0, cfg.vocab_size, dtype=dtype)
        else:
            out[name] = jax.random.normal(k, shp, dtype=jnp.float32).astype(dtype) * 0.02
    return out
