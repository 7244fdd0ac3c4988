"""DeepSeek-V2-Lite 16B (2.4B active): MLA without query compression,
one dense layer then 26 MoE layers of 64 routed experts (top-6, softmax
gates left unnormalised) and 2 shared experts, YaRN rope, untied head.
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json]
"""
from repro.configs.base import (MLA, MOE_FFN, MLAConfig, ModelConfig,
                                MoEConfig, YarnConfig, shrink)

CONFIG = ModelConfig(
    name="deepseek_v2_lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,             # MLA: heads share the latent cache
    d_ff=10944,                  # the leading dense layer
    vocab_size=102400,
    pattern=((MLA, MOE_FFN),),
    leading_dense_layers=1,
    moe=MoEConfig(num_experts=64, top_k=6, expert_ffn=1408,
                  num_shared_experts=2, shared_ffn=1408, norm_topk=False),
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    rope_theta=10000.0,
    rope_style="rope",
    rope_scaling=YarnConfig(factor=40.0,
                            original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    tie_embeddings=False,
    norm_eps=1e-6,
    max_seq_len=163840,
    sub_quadratic=False,
)

SMOKE_CONFIG = shrink(CONFIG)
