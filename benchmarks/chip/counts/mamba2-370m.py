"""Operations and bytes one ``serve_step`` of mamba2-370m needs.

One step feeds one token to each of ``batch`` sequences.  What the step
needs, not what the program does: every matmul weight read once in bf16
(the embedding only for the rows it looks up; the tied table once more as
the output head), and each sequence's float32 state (SSM state and conv
window) read and written once.  The state does not grow, so ``live`` is
unused.  Activations are a rounding error at this size and are left out.
"""

BF16, F32 = 2, 4


def serve_step(sizes, batch, live):
    """(flops, bytes) of one decode step."""
    d, layers = sizes["d_model"], sizes["n_layer"]
    vocab = sizes["padded_vocab_size"]
    ssm = sizes["ssm_cfg"]
    n, p, g, w = ssm["d_state"], ssm["headdim"], ssm["ngroups"], ssm["d_conv"]
    d_in = ssm["expand"] * d
    h = d_in // p
    proj = d * (2 * d_in + 2 * g * n + h) + d_in * d  # in and out projections
    conv_ch = d_in + 2 * g * n
    flops = 2 * batch * (layers * proj + d * vocab)
    flops += batch * layers * (2 * w * conv_ch + 5 * h * p * n)  # conv, state, C·h
    small = conv_ch * (w + 1) + 3 * h + d_in + d  # conv, A, D, dt_bias, norms
    weights = (layers * (proj + small) + d * vocab + batch * d + d) * BF16
    state = layers * (h * p * n + (w - 1) * conv_ch) * F32
    return flops, weights + 2 * batch * state
