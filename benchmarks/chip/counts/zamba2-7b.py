"""Operations and bytes one ``serve_step`` of Zamba2 needs.

One step feeds one token to each of ``batch`` sequences whose caches hold
``live`` positions once this token is written.  What the step needs, not
what the program does: every matmul weight read once in bf16 (each shared
block once, however many of its sites run; the embedding only for the rows
it looks up, and the tied table once more as the output head), the ``live``
keys and values of each sequence at each site read or written once (bf16),
and each sequence's float32 SSM state and conv window of every layer read
and written once.  Activations are a rounding error at this size and are
left out.
"""

BF16, F32 = 2, 4


def serve_step(sizes, batch, live):
    """(flops, bytes) of one decode step."""
    d, f, vocab = sizes["hidden_size"], sizes["intermediate_size"], sizes["vocab_size"]
    kinds = sizes["layers_block_type"]
    layers, sites = len(kinds), kinds.count("hybrid")
    n_h, n_kv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["attention_head_dim"]
    n, p, g, w = sizes["mamba_d_state"], sizes["mamba_headdim"], sizes["mamba_ngroups"], sizes["mamba_d_conv"]
    blocks, r = sizes["num_mem_blocks"], sizes["adapter_rank"]
    d_in = sizes["mamba_expand"] * d
    h = d_in // p
    conv_ch = d_in + 2 * g * n
    mixer = d * (2 * d_in + 2 * g * n + h) + d_in * d  # in and out projections
    attn = 2 * d * (n_h + 2 * n_kv) * hd + n_h * hd * d  # q, k, v from 2d; o
    site = d * r + 2 * r * f + d * d  # adapter and output projection
    # matmul weights one token passes through, and those stored
    passed = layers * mixer + sites * (attn + 3 * d * f + site) + d * vocab
    stored = layers * mixer + min(sites, blocks) * (attn + 3 * d * f) + sites * site + d * vocab
    flops = 2 * batch * passed
    flops += batch * layers * (2 * w * conv_ch + 5 * h * p * n)  # conv, state, C·h
    flops += 4 * batch * live * n_h * hd * sites  # q·k and p·v
    small = layers * (conv_ch * (w + 1) + 3 * h + d_in + d)  # conv, A, D, dt_bias, norms
    small += min(sites, blocks) * 3 * d + d  # the blocks' norms (2d and d), final norm
    weights = (stored + small + batch * d) * BF16
    kv = 2 * sites * n_kv * hd * BF16  # bytes of one position's K and V
    state = layers * (h * p * n + (w - 1) * conv_ch) * F32
    return flops, weights + batch * live * kv + 2 * batch * state
