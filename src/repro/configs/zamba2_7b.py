"""zamba2-7b [hybrid] — Mamba2 layers with two shared transformer blocks.

81L d_model=3584, 81 Mamba2 mixers (d_inner 7168, 112 heads of 64,
n_groups 2, d_state 64); at the 13 hybrid layers a shared block (taken in
turn from 2) runs MHA of 32 heads of 224 on concat[h, e0] and a GeGLU MLP
of 14336 with a per-site rank-128 adapter; vocab 32000, tied, unscaled
embedding.  [arXiv:2411.15242; hf Zyphra/Zamba2-7B-Instruct config.json]
"""

from .base import ModelConfig, SharedBlockConfig, SSMConfig

#: ``layers_block_type`` of the published config: hybrid at 6, 11, 17, …, 77
LAYERS = (
    ("mamba",) * 6 + ("hybrid",) + ("mamba",) * 4 + (("hybrid",) + ("mamba",) * 5) * 11
    + ("hybrid",) + ("mamba",) * 3
)

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    d_ff=14336,
    vocab_size=32000,
    head_dim=224,
    pattern=LAYERS,
    rope_theta=10000.0,
    mlp_type="geglu",
    scale_embedding=False,
    norm_eps=1e-5,
    tie_embeddings=True,
    ssm=SSMConfig(d_state=64, head_dim=64, expand=2, n_groups=2, chunk=256, dt_min=0.001),
    shared=SharedBlockConfig(n_blocks=2, adapter_rank=128),
    source="arXiv:2411.15242; hf Zyphra/Zamba2-7B-Instruct",
)
