"""deepseek-v2-lite — MLA + shared/routed experts [hf:deepseek-ai/DeepSeek-V2-Lite].

27L d_model=2048 16H vocab=102400, untied head, RMSNorm eps 1e-6.  Multi-head
latent attention without query compression (q_lora_rank null),
kv_lora_rank=512, qk nope/rope 128/64, v 128; rope theta 1e4 with YaRN
(factor 40 over 4096 positions, mscale = mscale_all_dim = 0.707).  Layer 0
has a dense SwiGLU MLP of 10944 (first_k_dense_replace 1); layers 1-26 have
64 routed SwiGLU experts of 1408 (softmax over all 64, greedy top-6,
norm_topk_prob false, routed_scaling_factor 1) and 2 shared experts run as
one SwiGLU MLP of 2816.
"""
from repro.configs.base import MLASpec, ModelConfig, MoESpec, YaRNSpec, register

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_head=192,          # qk head dim (nope 128 + rope 64)
    d_ff=10944,          # the leading dense layer's MLP
    vocab_size=102400,
    rope_theta=10_000.0,
    rope_scaling=YaRNSpec(factor=40.0, original_max_position_embeddings=4096,
                          beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                          mscale_all_dim=0.707),
    norm_eps=1e-6,
    layer_pattern=("attn",),
    mlp_pattern=("moe",),
    n_dense_layers=1,
    mla=MLASpec(q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128),
    moe=MoESpec(n_experts=64, top_k=6, d_ff_expert=1408, norm_topk_prob=False,
                d_ff_shared=2816),
))
