"""deepseek-v2-lite [moe] — MLA without q-LoRA, YaRN, 64 routed experts
top-6 plus 2 shared. [hf:deepseek-ai/DeepSeek-V2-Lite config.json]

27 layers of hidden 2048: layer 0 has a dense SwiGLU of 10944, layers
1-26 an expert layer (64 experts of 1408, softmax scores, top-6 without
renormalisation, routed scale 1, and two shared experts as one SwiGLU of
2816). Attention is MLA with 16 heads: a direct q projection, a latent of
512 and a shared rope key of 64, nope 128, v 128, YaRN rope (factor 40
over 4096 positions). Untied head over 102,400 ids.
"""
from repro.configs.base import ModelConfig, YarnScaling

_YARN = YarnScaling(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
    vocab_size=102_400, ffn_act="swiglu", rope_theta=10_000.0, yarn=_YARN,
    norm_eps=1e-6, block_pattern=("aM",), n_experts=64, n_experts_per_tok=6,
    n_shared_experts=2, first_dense_layers=1, moe_d_ff=1408,
    norm_topk_prob=False,
    mla=True, q_lora_rank=0, kv_lora_rank=512, qk_nope_dim=128,
    qk_rope_dim=64, v_head_dim=128,
)

# latent 64 and rope 32 are multiples of the 32-lane pack, so the smoke
# model's latent pool packs as the published one does
SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke", family="moe",
    n_layers=3, d_model=128, n_heads=4, n_kv_heads=4, d_ff=256,
    vocab_size=512, ffn_act="swiglu", rope_theta=10_000.0, yarn=_YARN,
    norm_eps=1e-6, block_pattern=("aM",), n_experts=8, n_experts_per_tok=3,
    n_shared_experts=2, first_dense_layers=1, moe_d_ff=64,
    norm_topk_prob=False,
    mla=True, q_lora_rank=0, kv_lora_rank=64, qk_nope_dim=32,
    qk_rope_dim=32, v_head_dim=32,
)
