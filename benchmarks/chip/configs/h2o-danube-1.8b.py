"""Plain reference forward of h2o-danube-1.8b: the Mistral architecture
(arXiv:2401.16818; hf ``MistralForCausalLM``) in float32 ``jax.numpy``.

Pre-norm decoder: RMSNorm → grouped-query attention with rotary positions
(``rotate_half`` convention) under a causal sliding window → residual →
RMSNorm → SwiGLU MLP → residual; a final RMSNorm and an untied output head.
No kernels, cache or batching tricks; every matmul at HIGHEST precision.

It reads the weights by their place in the checkpoint tree:
``embed/table [V, d]``, ``embed/lm_head [d, V]``, ``final_norm/scale``, and
per layer, stacked on a leading axis, ``groups/pos0/{ln1,ln2}/scale``,
``attn/{q,k,v,o}/w`` and ``mlp/{gate,up,down}/w``, each ``[in, out]``.  A
norm stores ``weight - 1``.  One departure from the published model, taken
from the program: the input embedding is multiplied by sqrt(hidden_size).
With untied embeddings that is the same model with a re-scaled table.

``fp8=True`` is the control: every matmul's operands rounded to float8
e4m3 with one scale per tensor, the precision below the configuration's
bfloat16.
"""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: rows of ``tokens`` per call: one sequence of up to 4096 positions
ROWS = 1
#: query rows per attention block, and the multiple ``T`` is padded to
BLOCK = 512


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(spec, a, b, fp8):
    a, b = a.astype(F32), b.astype(F32)
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def _rope(x, theta):
    """x [B, T, H, D]; position t rotates pair (i, i + D/2) by t·θ^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def logits(weights, sizes, tokens, fp8=False):
    """Logits [B, T, vocab_size] for ``tokens`` [B, T] (T a multiple of
    ``BLOCK``), each position seeing itself and the positions before it
    within the sliding window."""
    d = sizes["hidden_size"]
    n_h, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = d // n_h
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    window, vocab = sizes["sliding_window"], sizes["vocab_size"]
    b, t = tokens.shape
    pos = jnp.arange(t)
    x = weights["embed"]["table"][tokens].astype(F32) * math.sqrt(d)

    def layer(x, lp):
        h = _norm(x, lp["ln1"]["scale"], eps)
        q = _mm("btd,de->bte", h, lp["attn"]["q"]["w"], fp8).reshape(b, t, n_h, hd)
        k = _mm("btd,de->bte", h, lp["attn"]["k"]["w"], fp8).reshape(b, t, n_kv, hd)
        v = _mm("btd,de->bte", h, lp["attn"]["v"]["w"], fp8).reshape(b, t, n_kv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, n_h // n_kv, axis=2)
        v = jnp.repeat(v, n_h // n_kv, axis=2)

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK, axis=1)
            dpos = (i * BLOCK + jnp.arange(BLOCK))[:, None] - pos[None, :]
            allowed = (dpos >= 0) & (dpos < window)
            s = _mm("bqhd,bkhd->bhqk", qb, k, fp8) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
            return _mm("bhqk,bkhd->bqhd", p, v, fp8)

        o = jax.lax.map(block, jnp.arange(t // BLOCK))  # [T/BLOCK, B, BLOCK, H, D]
        o = o.transpose(1, 0, 2, 3, 4).reshape(b, t, n_h * hd)
        x = x + _mm("bte,ed->btd", o, lp["attn"]["o"]["w"], fp8)
        h = _norm(x, lp["ln2"]["scale"], eps)
        g = _mm("btd,df->btf", h, lp["mlp"]["gate"]["w"], fp8)
        u = _mm("btd,df->btf", h, lp["mlp"]["up"]["w"], fp8)
        x = x + _mm("btf,fd->btd", jax.nn.silu(g) * u, lp["mlp"]["down"]["w"], fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, weights["groups"]["pos0"])
    x = _norm(x, weights["final_norm"]["scale"], eps)
    return _mm("btd,dv->btv", x, weights["embed"]["lm_head"][:, :vocab], fp8)
