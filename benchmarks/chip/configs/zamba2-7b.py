"""Plain reference forward of Zamba2 (arXiv:2411.15242; transformers'
``Zamba2ForCausalLM``, ``models/zamba2/modeling_zamba2.py``) in float32
``jax.numpy``.

A stack of Mamba2 decoder layers, each ``x ← x + Mamba(Norm(x + T))``,
where ``T`` is zero except at the hybrid layers.  There a shared
transformer block, taken in turn from ``num_mem_blocks`` (site k runs block
k mod ``num_mem_blocks``), computes ``T`` from ``x`` and the unscaled input
embedding ``e0``: RMSNorm(concat[x, e0]) → multi-head attention with rotary
positions over the whole head (``rotate_half``, θ = ``rope_theta``), scores
scaled by (head_dim/2)^-0.5, full causal mask → RMSNorm → GeGLU MLP (exact
GELU), whose gate and up projections add the site's rank-``adapter_rank``
adapter → the site's own ``linear``.  ``T`` feeds the mixer's input only;
the residual skips the block.

The Mamba2 mixer: projections to z, x, B, C and dt → causal depthwise conv
(width ``mamba_d_conv``, with bias) and SiLU over x, B and C → dt =
softplus(dt + dt_bias), floored at ``time_step_min`` as transformers' mixer
clamps it → A = -exp(A_log) → the recurrence h_t = exp(dt_t·A)·h_{t-1} +
dt_t·x_t·B_tᵀ, y_t = C_t·h_t + D·x_t, stepped one position at a time, with
B and C shared by the heads of each of ``mamba_ngroups`` groups →
RMSNorm of y·silu(z) over groups of d_inner / ``mamba_ngroups`` channels →
out projection.  A final RMSNorm and the tied embedding as output head.
Every matmul at HIGHEST precision.

It reads the weights by their place in the checkpoint tree: ``embed/table
[V, d]``, ``final_norm/scale``; ``shared/block<b>/{ln1,ln2}/scale``,
``attn/{q,k,v,o}/w`` and ``mlp/{gate,up,down}/w``, each ``[in, out]``; and
the layers under ``groups/pos<n>``, stacked over the repeats of their run
(a run of ``period`` keys ``pos<f>`` … ``pos<f + period - 1>``, layer
``f + r·period + j`` at ``pos<f + j>``, row ``r``; one key a run where the
rows are 1): ``ln1/scale``, ``mamba/*`` (mamba_ssm's fused in_proj and
conv1d held as separate ``{z,x,bc,dt}_proj`` and ``conv_{x,bc}_{w,b}``), and
at a hybrid layer ``adapter/{down,gate,up}/w`` and ``linear/w``.  A norm
stores ``weight - 1``.  No departure from the published model.

``fp8=True`` is the control: every matmul's operands rounded to float8
e4m3 with one scale per tensor, the precision below the configuration's
bfloat16.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: rows of ``tokens`` per call: one conversation's batch
ROWS = 8
#: query rows per attention block, and the multiple ``T`` is padded to
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


def _norm(x, scale, eps, groups=1):
    shape = x.shape
    x = x.reshape(*shape[:-1], groups, shape[-1] // groups)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).reshape(shape) * (1.0 + scale.astype(F32))


def _conv(x, w, bias):
    """Causal depthwise conv: x [B, T, C], w [W, C]; out[t] = Σ_i w[i]·x[t-W+1+i]."""
    width, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    w = w.astype(F32)
    return sum(xp[:, i : i + t] * w[i] for i in range(width)) + bias.astype(F32)


def _rope(x, theta):
    """x [B, T, H, D]; position t rotates pair (i, i + D/2) by t·θ^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layers(groups):
    """The layers' weights in depth order, from the ``groups/pos<n>`` runs."""
    out, first = [], 0
    while f"pos{first}" in groups:
        rows = jax.tree.leaves(groups[f"pos{first}"])[0].shape[0]
        period = 1
        while rows > 1 and f"pos{first + period}" in groups:
            period += 1
        for r in range(rows):
            for j in range(period):
                out.append(jax.tree.map(lambda a, r=r: a[r], groups[f"pos{first + j}"]))
        first += rows * period
    return out


def _shared_block(blk, site, x, e0, sizes, fp8):
    """T(x, e0) of one hybrid layer: ``blk`` the shared block, ``site`` the
    layer's adapter and linear."""
    b, t, _ = x.shape
    n_h, hd = sizes["num_attention_heads"], sizes["attention_head_dim"]
    eps = sizes["rms_norm_eps"]
    h = _norm(jnp.concatenate([x, e0], axis=-1), blk["ln1"]["scale"], eps)
    a = blk["attn"]
    q = _rope(_mm("btd,de->bte", h, a["q"]["w"], fp8).reshape(b, t, n_h, hd), sizes["rope_theta"])
    k = _rope(_mm("btd,de->bte", h, a["k"]["w"], fp8).reshape(b, t, n_h, hd), sizes["rope_theta"])
    v = _mm("btd,de->bte", h, a["v"]["w"], fp8).reshape(b, t, n_h, hd)
    pos = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK, axis=1)
        allowed = (i * BLOCK + jnp.arange(BLOCK))[:, None] >= pos[None, :]
        s = _mm("bqhd,bkhd->bhqk", qb, k, fp8) * (hd / 2) ** -0.5
        p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, fp8)

    o = jax.lax.map(block, jnp.arange(t // BLOCK))  # [T/BLOCK, B, BLOCK, H, D]
    o = o.transpose(1, 0, 2, 3, 4).reshape(b, t, n_h * hd)
    h = _norm(_mm("bte,ed->btd", o, a["o"]["w"], fp8), blk["ln2"]["scale"], eps)
    m, ad = blk["mlp"], site["adapter"]
    low = _mm("btd,dr->btr", h, ad["down"]["w"], fp8)
    g = _mm("btd,df->btf", h, m["gate"]["w"], fp8) + _mm("btr,rf->btf", low, ad["gate"]["w"], fp8)
    u = _mm("btd,df->btf", h, m["up"]["w"], fp8) + _mm("btr,rf->btf", low, ad["up"]["w"], fp8)
    y = _mm("btf,fd->btd", jax.nn.gelu(g, approximate=False) * u, m["down"]["w"], fp8)
    return _mm("btd,de->bte", y, site["linear"]["w"], fp8)


def _mixer(m, u, sizes, fp8):
    """Mamba2 mixer of the normed input ``u`` [B, T, d]."""
    b, t, d = u.shape
    n, p, g = sizes["mamba_d_state"], sizes["mamba_headdim"], sizes["mamba_ngroups"]
    d_in = sizes["mamba_expand"] * d
    h = d_in // p
    z = _mm("btd,de->bte", u, m["z_proj"]["w"], fp8)
    xs = _mm("btd,de->bte", u, m["x_proj"]["w"], fp8)
    bc = _mm("btd,de->bte", u, m["bc_proj"]["w"], fp8)
    dt = _mm("btd,dh->bth", u, m["dt_proj"]["w"], fp8)
    xs = jax.nn.silu(_conv(xs, m["conv_x_w"], m["conv_x_b"])).reshape(b, t, h, p)
    bc = jax.nn.silu(_conv(bc, m["conv_bc_w"], m["conv_bc_b"]))
    bb = jnp.repeat(bc[..., : g * n].reshape(b, t, g, n), h // g, axis=2)
    cc = jnp.repeat(bc[..., g * n :].reshape(b, t, g, n), h // g, axis=2)
    dt = jnp.maximum(jax.nn.softplus(dt + m["dt_bias"].astype(F32)), sizes["time_step_min"])
    a = -jnp.exp(m["A_log"].astype(F32))

    def step(state, inp):  # state [B, H, P, N]
        dt_t, x_t, b_t, c_t = inp
        decay = jnp.exp(dt_t * a)[:, :, None, None]
        state = state * decay + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HIGHEST)

    seq = (dt, xs, bb, cc)
    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, n), F32), tuple(s.swapaxes(0, 1) for s in seq))
    y = y.swapaxes(0, 1) + m["D"].astype(F32)[:, None] * xs
    y = _norm(y.reshape(b, t, d_in) * jax.nn.silu(z), m["gate_norm"]["scale"], sizes["rms_norm_eps"], g)
    return _mm("bte,ed->btd", y, m["out_proj"]["w"], fp8)


def logits(weights, sizes, tokens, fp8=False):
    """Logits [B, T, vocab_size] for ``tokens`` [B, T] (T a multiple of
    ``BLOCK``), each position seeing itself and the positions before it."""
    eps, vocab = sizes["rms_norm_eps"], sizes["vocab_size"]
    kinds = sizes["layers_block_type"]
    table = weights["embed"]["table"]
    x = table[tokens].astype(F32)
    e0 = x
    site = 0
    for kind, lp in zip(kinds, layers(weights["groups"]), strict=True):
        inp = x
        if kind == "hybrid":
            blk = weights["shared"][f"block{site % sizes['num_mem_blocks']}"]
            inp = x + _shared_block(blk, lp, x, e0, sizes, fp8)
            site += 1
        x = x + _mixer(lp["mamba"], _norm(inp, lp["ln1"]["scale"], eps), sizes, fp8)
    x = _norm(x, weights["final_norm"]["scale"], eps)
    return _mm("btd,vd->btv", x, table[:vocab], fp8)
