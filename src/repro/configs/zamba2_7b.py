"""zamba2-7b [hybrid] — Zamba2-7B-Instruct
[hf:Zyphra/Zamba2-7B-Instruct config.json].  81 Mamba2 layers, d_model
3584, 112 SSM heads of 64 in 2 groups, d_state 64, conv 4 with bias,
vocab 32000, tied head, RMSNorm eps 1e-5.

At the 13 hybrid depths (6, 11, 17, ..., 77) a shared transformer block
runs before that depth's Mamba2 layer: 32 heads of 224 on concat(hidden,
embedding output) (7168 wide), softmax scale (224/2)^-0.5, a gated-GELU
MLP of 14336 with a rank-128 adapter per use, and a 3584 x 3584 linear
layer per use; its output joins only that Mamba2 layer's norm input.
Two such blocks (num_mem_blocks) run in turn: use u runs block u mod 2.

Stages put each use at the head of the group its Mamba2 layer starts:
[m x 6, S_A, m x 5], 5 x [S_B, m x 6, S_A, m x 6], [S_B, m x 6, S_A,
m x 4] — 81 Mamba2 layers, 13 uses.  Hybrid => runs long_500k.
"""
from ..models.config import Block, ModelConfig

M = Block("mamba2")
S_A, S_B = Block("shared_attn", mem=0), Block("shared_attn", mem=1)

CONFIG = ModelConfig(
    name="zamba2-7b",
    d_model=3584, n_heads=32, n_kv_heads=32, head_dim=224,
    d_ff=14336, vocab=32000,
    stages=(
        (1, (M,) * 6 + (S_A,) + (M,) * 5),
        (5, (S_B,) + (M,) * 6 + (S_A,) + (M,) * 6),
        (1, (S_B,) + (M,) * 6 + (S_A,) + (M,) * 4),
    ),
    ssm_state=64, ssm_heads=112, ssm_head_dim=64, ssm_ngroups=2,
    ssm_conv_bias=True,
    shared_attn_d_ff=14336, adapter_rank=128,
    norm_eps=1e-5, attn_scale=(224 / 2) ** -0.5,
    rope_theta=10_000.0,
    tie_embeddings=True,
    subquadratic=True,
)


def smoke() -> ModelConfig:
    """Both shared blocks in turn (one stage repeats them, so the uses'
    weights are stacked), adapters, 2 SSM groups and the conv bias."""
    return ModelConfig(
        name="zamba2-smoke",
        d_model=64, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=128, vocab=256,
        stages=(
            (1, (M, M, S_A, M)),
            (2, (S_B, M, S_A, M)),
            (1, (S_B, M)),
        ),
        ssm_state=16, ssm_heads=4, ssm_head_dim=32, ssm_chunk=32,
        ssm_ngroups=2, ssm_conv_bias=True,
        shared_attn_d_ff=128, adapter_rank=8,
        norm_eps=1e-5, attn_scale=(32 / 2) ** -0.5,
        rope_theta=10_000.0,
        tie_embeddings=True,
        dtype="float32",
        subquadratic=True,
    )
