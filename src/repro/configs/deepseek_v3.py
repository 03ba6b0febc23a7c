"""deepseek-v3-671b [moe] — 61L d_model=7168 128H d_ff=2048 (expert)
vocab=129280, MoE 256 routed top-8 + 1 shared, MLA (q_lora 1536,
kv_lora 512, rope 64, nope 128, v 128), first 3 layers dense
(d_ff 18432); sigmoid router with a selection-only bias, top-8 within
the best 4 of 8 groups, weights scaled by 2.5; YaRN RoPE (factor 40 over
4,096 positions).  The multi-token-prediction module is not modelled
(one token a step).  [arXiv:2412.19437; huggingface.co/deepseek-ai/
DeepSeek-V3 config.json]"""

import dataclasses

from repro.models.common import ModelConfig, YaRN

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128, d_head=192,
    d_ff=18432, vocab_size=129280,
    attention="mla",
    q_lora_rank=1536, kv_lora_rank=512,
    qk_rope_head_dim=64, qk_nope_head_dim=128, v_head_dim=128,
    moe=True, n_experts=256, top_k=8, d_expert=2048,
    n_shared_experts=1, first_dense_layers=3,
    router="sigmoid", n_groups=8, topk_groups=4, routed_scale=2.5,
    rope_theta=1e4,
    rope_scaling=YaRN(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                      mscale_all_dim=1.0),
    mlp="silu_glu",
    param_dtype="bfloat16", compute_dtype="bfloat16",
)

#: the same family at a CPU size: 16 experts routed in 4 groups (best 2
#: kept), a share of 4 of them held here (experts 4-7), YaRN over a
#: 64-position original window
SMOKE_CONFIG = dataclasses.replace(
    CONFIG, name="deepseek-v3-smoke",
    n_layers=3, first_dense_layers=1, d_model=128, n_heads=4,
    n_kv_heads=4, d_head=48,
    q_lora_rank=64, kv_lora_rank=48, qk_rope_head_dim=16,
    qk_nope_head_dim=32, v_head_dim=32,
    d_ff=256, n_experts=16, top_k=4, d_expert=96, vocab_size=256,
    n_groups=4, topk_groups=2, expert_offset=4, experts_held=4,
    rope_scaling=YaRN(factor=4.0, original_max_position=64,
                      beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                      mscale_all_dim=1.0),
    param_dtype="float32",
    compute_dtype="float32", remat="none", attn_impl="xla")
