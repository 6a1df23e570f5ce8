"""The four Pallas kernels at the widths of the models that run on the chip.

One list, read by ``tests/test_chip_compile.py`` (compiled for a described
v5e chip) and by ``chip_smoke.py`` (run on the chip and compared with the
pure-jnp references), so both exercise the same shapes:

  * ``flash_attention`` — gemma3-1b prefill (Hq=4, Hkv=1, D=256, window
    512) and an 80-wide head (h2o-danube-1.8b: Hq=32, Hkv=8, D=80);
  * ``decode_attention`` — gemma3-1b decode (B=8, Hq=4, Hkv=1, D=256,
    a 4096-token cache);
  * ``rmsnorm`` — gemma3-1b's d_model of 1152 over 8×2048 rows;
  * ``ssd`` — mamba2-370m (H=32, P=64, N=128, chunk 256) at batch 4 ×
    seq 2048, in fp32 as ``models.mamba2`` calls it.

Each kernel runs with ``interpret=False``: these cases exist for the chip.
The tolerances, per input dtype, are those ``tests/test_kernels.py`` holds
the interpret-mode kernels to.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .decode_attention import decode_attention
from .decode_attention.ref import decode_attention_ref
from .flash_attention import flash_attention
from .flash_attention.ref import attention_ref
from .rmsnorm import rmsnorm
from .rmsnorm.ref import rmsnorm_ref
from .ssd_scan import ssd
from .ssd_scan.ref import ssd_chunked

TOL_F32 = 2e-5
TOL_BF16 = 2e-2
#: the SSD scan accumulates over whole chunks and the carried state
TOL_SSD_F32 = 1e-4


@dataclasses.dataclass(frozen=True)
class KernelCase:
    name: str
    #: key -> tuple of input arrays (seeded; ``jax.eval_shape`` gives specs)
    make_inputs: Callable[[Any], Tuple[jnp.ndarray, ...]]
    #: the Pallas kernel, compiled for the device
    kernel: Callable[..., Any]
    #: the pure-jnp reference, computed in float32
    reference: Callable[..., Any]
    tol: float

    def arg_specs(self, sharding=None) -> Tuple[jax.ShapeDtypeStruct, ...]:
        shapes = jax.eval_shape(self.make_inputs, jax.random.PRNGKey(0))
        return tuple(
            jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
            for s in shapes
        )


def max_normalized_error(out: Any, ref: Any) -> float:
    """Max |out - ref| over every leaf, over max(1, max |ref|): bf16 outputs
    quantize in proportion to their scale, so an absolute error alone
    misleads."""
    err = 0.0
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = max(1.0, float(np.abs(b).max()))
        err = max(err, float(np.abs(a - b).max()) / scale)
    return err


def _f32(*xs):
    return [x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x for x in xs]


def _exact(fn):
    """Reference in true fp32: on TPU the default matmul precision of an
    fp32 dot is one bf16 pass, which would make the reference the noisier
    side of the comparison."""

    def run(*args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*_f32(*args))

    return run


def _attn_inputs(b, s, hq, hkv, d):
    def make(key):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (b, s, hq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
        return tuple(t.astype(jnp.bfloat16) for t in (q, k, v))

    return make


def _flash_ref(window):
    def ref(q, k, v):
        t = (0, 2, 1, 3)
        out = attention_ref(
            q.transpose(t), k.transpose(t), v.transpose(t),
            causal=True, window=window,
        )
        return out.transpose(t)

    return ref


def _flash_case(name, b, s, hq, hkv, d, window):
    return KernelCase(
        name=name,
        make_inputs=_attn_inputs(b, s, hq, hkv, d),
        kernel=jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=window, interpret=False
            )
        ),
        reference=_exact(_flash_ref(window)),
        tol=TOL_BF16,
    )


def _decode_inputs(key, b=8, s=4096, hq=4, hkv=1, d=256):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, 1, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    pos = jax.random.randint(ks[3], (b,), 0, s, dtype=jnp.int32)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    return q, k, v, pos


def _decode_ref(q, k, v, pos):
    t = (0, 2, 1, 3)
    return decode_attention_ref(q[:, 0], k.transpose(t), v.transpose(t), pos)[
        :, None
    ]


def _rmsnorm_inputs(key, rows=8 * 2048, d=1152):
    x = jax.random.normal(key, (rows, d), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (d,), jnp.float32) * 0.1
    return x, w


def _ssd_inputs(key, b=4, s=2048, h=32, p=64, n=128):
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    da = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    bm = jax.random.normal(ks[2], (b, s, h, n), jnp.float32) * 0.5
    cm = jax.random.normal(ks[3], (b, s, h, n), jnp.float32) * 0.5
    return x, da, bm, cm


KERNEL_CASES: Tuple[KernelCase, ...] = (
    _flash_case("flash_attention_gemma3_1b", 1, 2048, 4, 1, 256, window=512),
    _flash_case("flash_attention_d80", 1, 2048, 32, 8, 80, window=None),
    KernelCase(
        name="decode_attention_gemma3_1b",
        make_inputs=_decode_inputs,
        kernel=jax.jit(
            lambda q, k, v, pos: decode_attention(q, k, v, pos, interpret=False)
        ),
        reference=_exact(_decode_ref),
        tol=TOL_BF16,
    ),
    KernelCase(
        name="rmsnorm_d1152",
        make_inputs=_rmsnorm_inputs,
        kernel=jax.jit(lambda x, w: rmsnorm(x, w, interpret=False)),
        reference=_exact(rmsnorm_ref),
        tol=TOL_BF16,
    ),
    KernelCase(
        name="ssd_mamba2_370m",
        make_inputs=_ssd_inputs,
        kernel=jax.jit(
            lambda x, da, bm, cm: ssd(x, da, bm, cm, chunk=256, interpret=False)
        ),
        reference=_exact(lambda x, da, bm, cm: ssd_chunked(x, da, bm, cm, 256)),
        tol=TOL_SSD_F32,
    ),
)
