"""DeepSeek-V2-Lite [arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite].

27L, d_model 2048, vocab 102400, untied head. Multi-head latent attention:
16 heads, no q-LoRA, kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128;
YaRN RoPE (θ 1e4, factor 40 over 4096, β 32/1, mscale = mscale_all_dim =
0.707). Layer 0 is a dense SwiGLU MLP of width 10944; layers 1-26 route
over 64 experts of width 1408 (softmax, top-6, not renormalised, scale 1)
beside 2 shared experts (one SwiGLU of width 2816). RMSNorm eps 1e-6.

``CONFIG`` holds every expert; a replica that holds a share of them sets
``experts_held`` and ``expert_offset`` (the benchmark's configuration
holds 8 of the 64).
"""

from repro.models.config import MLA, ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=1,
    d_ff=10944,
    vocab_size=102400,
    pattern=(MLA,),
    head_dim=192,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_scaling_factor=40.0,
    rope_original_len=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
    norm_eps=1e-6,
    num_experts=64,
    experts_per_token=6,
    first_dense=1,
    moe_d_ff=1408,
    shared_d_ff=2816,
)


def reduced() -> ModelConfig:
    import dataclasses
    return dataclasses.replace(
        CONFIG, num_layers=3, d_model=64, num_heads=4, d_ff=96,
        vocab_size=128, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, head_dim=24, num_experts=8, experts_per_token=3,
        experts_held=0, moe_d_ff=32, shared_d_ff=48, rope_original_len=16,
        rope_scaling_factor=4.0)
