"""deepseek-v2-lite-16b [moe] — MLA + fine-grained MoE [arXiv:2405.04434],
as published in hf:deepseek-ai/DeepSeek-V2-Lite config.json.

27 layers, d 2048, 16 heads of multi-head latent attention (no q LoRA,
kv_lora_rank 512, rope head 64, nope head 128, v head 128).  Layer 0 is a
dense SiLU MLP of width 10944 (`first_k_dense_replace` 1); layers 1-26
route each token to 6 of 64 experts of width 1408 (softmax scores, greedy
top-k, `norm_topk_prob` false, `routed_scaling_factor` 1) beside 2 shared
experts.  Rope is YaRN (factor 40 over 4096 original positions, beta_fast
32, beta_slow 1, mscale = mscale_all_dim = 0.707, theta 1e4); RMSNorm eps
1e-6; untied head over a 102400-token vocabulary.
"""
from repro.models.config import ModelConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    attn_kind="mla", kv_lora_rank=512,
    qk_rope_head_dim=64, qk_nope_head_dim=128, v_head_dim=128,
    n_experts=64, n_shared_experts=2, experts_per_token=6,
    moe_d_ff=1408, first_dense_layers=1, norm_topk_prob=False,
    rope_theta=1e4,
    rope_scaling=RopeScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6,
)
