"""DeepSeek-V3 (671B-A37B) — multi-head latent attention in all 61 layers,
3 leading dense layers, then 58 layers of 256 routed experts (top-8 of 4
node groups out of 8, sigmoid scores) plus 1 shared expert.
[hf:deepseek-ai/DeepSeek-V3]

The multi-token-prediction module (``num_nextn_predict_layers`` 1) serves
speculative decoding and is not modelled; RoPE runs at ``rope_theta``
without the published YaRN scaling (no FLOP or byte of the graph moves)."""
from repro.configs.base import ArchConfig, FULL_ATTENTION_SKIP

CONFIG = ArchConfig(
    name="deepseek-v3",
    family="mla_moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    head_dim=192,               # qk_nope_head_dim + qk_rope_head_dim
    d_ff=18432,                 # the leading dense layers' FFN
    vocab=129280,
    gated_mlp=True,
    rope_theta=10000.0,
    norm_eps=1e-6,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    first_k_dense=3,
    n_experts=256,
    top_k=8,
    n_shared_experts=1,
    expert_ff=2048,
    n_group=8,
    topk_group=4,
    routed_scaling=2.5,
    skip_shapes=FULL_ATTENTION_SKIP,
)
