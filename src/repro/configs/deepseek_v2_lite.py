"""deepseek-v2-lite [moe] — 27L d_model=2048 16H, MLA (kv_lora_rank 512,
qk_nope/qk_rope 128/64, v 128, no q_lora), layer 0 dense (SwiGLU 10944),
layers 1-26 MoE: 64 routed experts of width 1408, softmax top-6 without
renormalisation, 2 shared experts; YaRN rope (factor 40 over 4096
positions, mscale 0.707); vocab 102400, untied head.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]"""
from .base import ModelConfig

ARCH_ID = "deepseek-v2-lite"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID,
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=1408,
        vocab=102400,
        ffn="swiglu",
        n_experts=64,
        top_k=6,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        shared_experts=2,
        shared_d_ff=2 * 1408,
        first_dense_layers=1,
        dense_d_ff=10944,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=10000.0,
        yarn_factor=40.0,
        yarn_original_max_pos=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        source="[hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434]",
    )


def smoke_config() -> ModelConfig:
    """Every mechanism at CPU size: a dense first layer, MoE layers holding
    4 of 8 routed experts, MLA and YaRN (its ramp inside the rope width)."""
    return config().with_(
        name=ARCH_ID + "-smoke",
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32,
        vocab=512, n_experts=8, top_k=3, experts_held=4,
        shared_d_ff=64, dense_d_ff=128, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        yarn_original_max_pos=64, remat=False,
    )
