"""deepseek-v2-lite — MLA + MoE, 15.7B parameters, 2.4B active.

[huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json] 27L d_model=2048
16H (MLA: no q_lora, kv_lora=512, head dims 128 nope + 64 rope, v 128),
first layer dense (d_ff 10,944), then 64 routed experts of 1,408 (6 a
token, softmax router, no top-k renormalisation) + 2 shared, YaRN rope
(factor 40 over 4,096 positions), RMSNorm eps 1e-6, vocab 102,400,
untied embeddings.  Its router (``routed_scaling_factor`` 1, no top-k
renormalisation) is what ``moe.held_moe_forward`` computes.

``FLEET`` is what one chip holds of an 8-way expert-parallel deployment
of it, the trunk fleet clients fine-tune adapters over
(``FLConfig(model="deepseek-v2-lite")``): the dense layer and MoE layers
1-5 (the rest would be further pipeline stages), experts 0-7 of 64 in
each MoE layer (the router still scores all 64), and the vocabulary's
first eighth (12,800 of 102,400).  Attention is data-parallel, so every
head and width is as published.  ``LORA``: rank-16 adapters (alpha 32)
on q_proj, kv_a_proj_with_mqa, kv_b_proj and o_proj of every layer.
"""
import dataclasses

from repro.configs.base import (LoRAConfig, MLAConfig, ModelConfig,
                                MoEConfig, YarnConfig)

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    arch_type="moe",
    source="huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,           # MLA decompresses to per-head K/V
    d_ff=10944,                # the dense first layer's MLP
    vocab_size=102400,
    attention="mla",
    rope_theta=10000.0,
    rope_scaling=YarnConfig(factor=40.0,
                            original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0,
                            mscale=0.707, mscale_all_dim=0.707),
    norm_eps=1e-6,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, num_shared_experts=2,
                  expert_d_ff=1408, shared_d_ff=1408,
                  first_k_dense_replace=1),
    mlp_act="silu_glu",
)

# one chip's share of an 8-way expert-parallel deployment
EP_WAYS = 8
FLEET = dataclasses.replace(
    CONFIG, name="deepseek-v2-lite-fleet", num_layers=6,
    vocab_size=CONFIG.vocab_size // EP_WAYS,
    moe=dataclasses.replace(CONFIG.moe,
                            experts_held=CONFIG.moe.num_experts // EP_WAYS))

LORA = LoRAConfig(rank=16, alpha=32.0)
