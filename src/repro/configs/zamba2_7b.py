"""Zamba2-7B [arXiv:2411.15242; Zyphra/Zamba2-7B-Instruct config.json]: 81
layers of d=3584, each with a Mamba2 mixer (state 64, 112 heads of 64, 2
B/C groups, chunk 256); the 13 "hybrid" layers first run one of 2 shared
transformer blocks (32 heads of 224 over [h, embedding] of width 7168,
RoPE, gated-GELU MLP of 14,336 with a rank-128 adapter per invocation).
Vocabulary 32,000, tied head."""
from .base import ModelConfig

LAYERS_BLOCK_TYPE = tuple(
    ["mamba"] * 6 + ["hybrid"] + ["mamba"] * 4 + ["hybrid"]
    + (["mamba"] * 5 + ["hybrid"]) * 11 + ["mamba"] * 3)

CONFIG = ModelConfig(
    name="zamba2-7b", family="hybrid",
    n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32, head_dim=224,
    d_ff=14336, vocab_size=32000, tie_embeddings=True,
    ssm_state=64, ssm_conv=4, ssm_expand=2, ssm_head_dim=64, ssm_chunk=256,
    ssm_groups=2, ssm_dt_min=0.001,
    layers_block_type=LAYERS_BLOCK_TYPE, num_mem_blocks=2, adapter_rank=128,
    source="https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json",
)

SMOKE = CONFIG.replace(
    n_layers=4, d_model=64, n_heads=4, n_kv_heads=4, head_dim=32, d_ff=128,
    vocab_size=256, ssm_head_dim=16, ssm_chunk=16, adapter_rank=8,
    layers_block_type=("mamba", "hybrid", "mamba", "hybrid"), dtype="float32")
