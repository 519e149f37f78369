"""deepseek-v2-lite — latent attention (MLA) and fine-grained experts.

[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite] 27L d_model=2048 16H,
MLA with kv_lora_rank=512 (q_lora_rank null), q/k head 128 + 64 rope,
v head 128; layer 0 dense (d_ff=10944), layers 1-26 MoE: 64 routed
experts of width 1408, top-6 greedy softmax routing without
renormalisation, 2 shared experts; YaRN rope (factor 40 over 4096
positions, theta 1e4); vocab 102400, untied head. The sequence-level
balance loss weight 0.001 is HF's default (the config does not give it).
"""
from repro.configs.base import ArchConfig, YarnScaling

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,
    d_ff=10_944,
    vocab_size=102_400,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    n_experts=64,
    top_k=6,
    expert_d_ff=1408,
    n_shared_experts=2,
    shared_expert_d_ff=2816,
    moe_dropless=True,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    moe_aux="seq",
    aux_loss_alpha=0.001,
    first_k_dense=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_scaling=YarnScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    source="MLA + 64 routed top-6 + 2 shared, 1 dense lead "
           "[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]",
)
