"""deepseek-v2-lite [moe]: 27L d_model=2048 16H, latent attention (MLA:
kv_lora_rank 512, qk 128 nope + 64 rope, v 128, no q-LoRA), layer 0 a dense
SwiGLU of 10944, layers 1-26 MoE with 64 routed experts of 1408 (top-6,
softmax, no renormalisation) and 2 shared experts, vocab 102400, untied,
YaRN rope (factor 40 over 4096).
[hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434]

``full()`` holds the experts 0-7 of every MoE layer: one chip's share of a
deployment that splits the experts 8 ways (EP 8) and replicates the rest.
"""
from repro.models.base import ModelConfig, Yarn

YARN = Yarn(factor=40.0, original_max=4096, beta_fast=32.0, beta_slow=1.0,
            mscale=0.707, mscale_all_dim=0.707)


def full():
    return ModelConfig(
        arch="deepseek-v2-lite", family="moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv=16, d_ff=10944,
        vocab=102400, moe_experts=64, moe_topk=6, moe_d_ff=1408,
        moe_norm_topk=False, moe_routed_scale=1.0, n_shared_experts=2,
        first_dense_layers=1, moe_expert_offset=0, moe_experts_held=8,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=10_000.0, yarn=YARN,
        norm="rmsnorm", act_fn="silu", gated_ffn=True,
        tied_embeddings=False)


def reduced():
    return ModelConfig(
        arch="deepseek-v2-lite", family="moe",
        n_layers=3, d_model=64, n_heads=4, n_kv=4, d_ff=96,
        vocab=256, moe_experts=16, moe_topk=3, moe_d_ff=32,
        moe_norm_topk=False, moe_routed_scale=1.0, n_shared_experts=2,
        first_dense_layers=1, moe_expert_offset=0, moe_experts_held=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=10_000.0,
        yarn=Yarn(factor=4.0, original_max=32, beta_fast=32.0,
                  beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        norm="rmsnorm", act_fn="silu", gated_ffn=True,
        tied_embeddings=False, loss_chunks=2)
