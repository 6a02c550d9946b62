"""deepseek-v2-lite [moe] — 27L d_model=2048 16H; multi-head latent
attention (kv_lora_rank 512, no q compression, qk 128 + 64 rotary, v 128,
YaRN x40 over 4096); DeepSeekMoE: 64 routed experts of width 1408, top-6
softmax weights not renormalized, 2 shared experts; the first layer dense
(d_ff 10944); vocab 102400.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]"""
from .base import ArchConfig, YarnScaling, attn_block

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv=16, d_ff=10944, vocab=102400,
    period=(attn_block(moe=True),),
    n_experts=64, top_k=6, capacity_factor=1.0, router_aux_weight=0.001,
    moe_d_ff=1408, n_shared_experts=2, moe_renormalize=False,
    moe_balance="expert", first_dense_layers=1,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=10000.0,
    rope_yarn=YarnScaling(factor=40.0, original_max_position=4096,
                          beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                          mscale_all_dim=0.707),
    source="hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434",
)
