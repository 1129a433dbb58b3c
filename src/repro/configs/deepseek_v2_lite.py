"""deepseek-v2-lite [moe] — 27L d_model=2048 16H, latent attention (MLA:
kv_lora_rank 512, no q_lora, qk 128 nope + 64 rope, v 128), layer 0
dense (d_ff 10944), layers 1-26 DeepSeekMoE (64 routed experts of 1408,
softmax top-6 without renorm, 2 shared), YaRN x40 on the rope half,
untied head, vocab 102400 [hf:deepseek-ai/DeepSeek-V2-Lite]."""
from repro.configs.base import ArchConfig, RopeScaling, register

CONFIG = register(ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    citation="hf:deepseek-ai/DeepSeek-V2-Lite (arXiv 2405.04434)",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    moe_d_ff=1408,
    vocab_size=102400,
    n_experts=64,
    top_k=6,
    shared_experts=2,
    norm_topk_prob=False,
    first_dense=1,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_scaling=RopeScaling(factor=40.0, original_max_len=4096,
                             beta_fast=32.0, beta_slow=1.0,
                             mscale=0.707, mscale_all_dim=0.707),
    norm_eps=1e-6,
    tie_embed=False,
))
