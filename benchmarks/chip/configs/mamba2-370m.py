"""Plain reference forward of mamba2-370m: the Mamba2 language model
(arXiv:2405.21060; ``mamba_ssm`` ``MambaLMHeadModel`` with ``Mamba2``
mixers) in float32 ``jax.numpy``.

Pre-norm residual stack of Mamba2 mixers, each: projections to z, x, B, C
and dt → causal depthwise conv (width d_conv, with bias) and SiLU over x, B
and C → dt = softplus(dt + dt_bias), A = -exp(A_log) → the selective state
recurrence h_t = exp(dt_t·A)·h_{t-1} + dt_t·x_t·B_tᵀ, y_t = C_t·h_t + D·x_t,
stepped one position at a time (the recurrence itself, not the chunked SSD
algorithm) → gated RMSNorm of y·silu(z) → out projection.  A final RMSNorm
and the tied embedding as output head.  Every matmul at HIGHEST precision.

It reads the weights by their place in the checkpoint tree: ``embed/table
[V, d]``, ``final_norm/scale``, and per layer, stacked on a leading axis,
``groups/pos0/ln1/scale`` and ``groups/pos0/mamba/*``, where the fused
in_proj and conv1d of mamba_ssm are held as separate ``{z,x,bc,dt}_proj``
and ``conv_{x,bc}_{w,b}`` (the same maps, split by output channel).  A
norm stores ``weight - 1``.  One departure from the published model, taken
from the program: the input embedding is multiplied by sqrt(d_model).

``fp8=True`` is the control: every matmul's operands rounded to float8
e4m3 with one scale per tensor, the precision below the configuration's
bfloat16.
"""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: rows of ``tokens`` per call: one CU's batch
ROWS = 8
#: ``T`` is padded to a multiple of this
BLOCK = 256


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


def _conv(x, w, bias):
    """Causal depthwise conv: x [B, T, C], w [W, C]; out[t] = Σ_i w[i]·x[t-W+1+i]."""
    width, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    w = w.astype(F32)
    return sum(xp[:, i : i + t] * w[i] for i in range(width)) + bias.astype(F32)


def logits(weights, sizes, tokens, fp8=False):
    """Logits [B, T, padded_vocab_size] for ``tokens`` [B, T]."""
    d = sizes["d_model"]
    ssm = sizes["ssm_cfg"]
    n, p, g = ssm["d_state"], ssm["headdim"], ssm["ngroups"]
    d_in = ssm["expand"] * d
    h = d_in // p
    eps, vocab = sizes["norm_epsilon"], sizes["padded_vocab_size"]
    b, t = tokens.shape
    table = weights["embed"]["table"]
    x = table[tokens].astype(F32) * math.sqrt(d)

    def layer(x, lp):
        m = lp["mamba"]
        u = _norm(x, lp["ln1"]["scale"], eps)
        z = _mm("btd,de->bte", u, m["z_proj"]["w"], fp8)
        xs = _mm("btd,de->bte", u, m["x_proj"]["w"], fp8)
        bc = _mm("btd,de->bte", u, m["bc_proj"]["w"], fp8)
        dt = _mm("btd,dh->bth", u, m["dt_proj"]["w"], fp8)
        xs = jax.nn.silu(_conv(xs, m["conv_x_w"], m["conv_x_b"])).reshape(b, t, h, p)
        bc = jax.nn.silu(_conv(bc, m["conv_bc_w"], m["conv_bc_b"]))
        bb = jnp.repeat(bc[..., : g * n].reshape(b, t, g, n), h // g, axis=2)
        cc = jnp.repeat(bc[..., g * n :].reshape(b, t, g, n), h // g, axis=2)
        dt = jax.nn.softplus(dt + m["dt_bias"].astype(F32))
        a = -jnp.exp(m["A_log"].astype(F32))

        def step(state, inp):  # state [B, H, P, N]
            dt_t, x_t, b_t, c_t = inp
            decay = jnp.exp(dt_t * a)[:, :, None, None]
            state = state * decay + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
            return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HIGHEST)

        seq = (dt, xs, bb, cc)
        _, y = jax.lax.scan(
            step,
            jnp.zeros((b, h, p, n), F32),
            tuple(s.swapaxes(0, 1) for s in seq),
        )
        y = y.swapaxes(0, 1) + m["D"].astype(F32)[:, None] * xs
        y = _norm(y.reshape(b, t, d_in) * jax.nn.silu(z), m["gate_norm"]["scale"], eps)
        return x + _mm("bte,ed->btd", y, m["out_proj"]["w"], fp8), None

    x, _ = jax.lax.scan(layer, x, weights["groups"]["pos0"])
    x = _norm(x, weights["final_norm"]["scale"], eps)
    return _mm("btd,vd->btv", x, table[:vocab], fp8)
