"""Kanana-2-30B-A3B (kakaocorp/kanana-2-30b-a3b-instruct-2601), one chip's
share of one federated client (silo). ``model_type`` deepseek_v3, so the
layer equations are DeepSeek-V3's [arXiv:2412.19437 §2.1]; latent
attention without a query LoRA, as DeepSeek-V2-Lite [arXiv:2405.04434].

Published (config.json): 48 layers, hidden 2048, the first layer dense
(SwiGLU 6144), MLA with 32 heads, q_lora_rank null, kv_lora_rank 512,
q/k head 128 (no position) + 64 (interleaved rotary, θ 1e6), v head 128;
128 routed experts of width 768, 6 per token, sigmoid scores with a
selection-only correction bias (noaux_tc, one group), selected scores
renormalised and scaled by 2.448; 2 shared experts; vocabulary 128,256,
untied head; RMSNorm ε 1e-6.

The share (perfbench/configs/kanana2_30b_a3b.json states the deployment):
5 layers (the dense one and 4 MoE layers, the first pipeline stage), the
8 routed experts [0, 8) of 128 (experts split 16 ways) and a 16,032-row
slice of the vocabulary (split 8 ways). The router keeps its 128 outputs
and its 6 experts per token.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="kanana-2-30b-a3b",
    family="moe",
    num_layers=5,
    d_model=2048,
    num_heads=32,
    num_kv_heads=32,
    head_dim=64,
    d_ff=6144,
    vocab_size=16032,
    block_pattern=("attn",) + ("moe",) * 4,
    rope_theta=1e6,
    use_mla=True,
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    qk_nope_head_dim=128,
    v_head_dim=128,
    rope_interleave=True,
    num_experts=128,
    num_experts_per_tok=6,
    num_shared_experts=2,
    moe_d_ff=768,
    router_scoring="sigmoid",
    norm_topk_prob=True,
    routed_scaling_factor=2.448,
    experts_held=8,
    first_expert=0,
    remat=True,
    citation="huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601; "
             "arXiv:2412.19437",
)
