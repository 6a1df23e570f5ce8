"""Operations and bytes one ``serve_step`` of h2o-danube-1.8b needs.

One step feeds one token to each of ``batch`` sequences whose caches hold
``live`` positions once this token is written.  What the step needs, not
what the program does: every matmul weight read once (the embedding only
for the rows it looks up), the ``live`` keys and values of each sequence
read or written once, bf16 throughout.  Activations are a rounding error
at this size and are left out.
"""

BF16 = 2


def serve_step(sizes, batch, live):
    """(flops, bytes) of one decode step."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    n_h, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    layers, vocab = sizes["num_hidden_layers"], sizes["vocab_size"]
    hd = d // n_h
    per_layer = 2 * d * n_h * hd + 2 * d * n_kv * hd + 3 * d * f
    matmul = layers * per_layer + d * vocab  # blocks and the output head
    flops = 2 * batch * matmul
    flops += 4 * batch * live * hd * n_h * layers  # q·k and p·v
    kv = 2 * layers * n_kv * hd * BF16  # bytes of one position's K and V
    weights = (matmul + batch * d + (2 * layers + 1) * d) * BF16
    return flops, weights + batch * live * kv
