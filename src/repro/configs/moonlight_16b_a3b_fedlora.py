"""Moonlight-16B-A3B (DeepSeek-V3's block at d=2048) fine-tuned federatedly
with rank-8 LoRA adapters over a frozen base, as one chip of a deployment in
which 8 chips share every layer (8-way expert parallelism).
[hf:moonshotai/Moonlight-16B-A3B config.json; MLA arXiv:2405.04434; gate
arXiv:2412.19437; LoRA arXiv:2106.09685]

Every width is as published.  Cut: 5 of 27 layers (the leading dense layer
and 4 MoE layers), the 8 of 64 routed experts this chip holds (the router
keeps its 64 outputs and top-6), and a 20,480-row slice (one eighth) of the
163,840-row vocabulary.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b-fedlora",
    family="moe",
    num_layers=5,                 # published 27
    first_dense_layers=1,
    layer_pattern=("mla",),
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,                 # qk_nope_head_dim + qk_rope_head_dim
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    d_ff=11264,                   # the dense layer
    moe_d_ff=1408,
    moe_shared_d_ff=2816,         # 2 shared experts of 1,408
    num_experts=64,
    experts_per_token=6,
    experts_held=8,               # published: all 64 on one chip
    moe_gate="sigmoid",
    moe_routed_scale=2.446,
    vocab_size=20480,             # published 163,840
    rope_theta=50_000.0,
    norm_type="rmsnorm",
    norm_eps=1e-5,
    act="silu",
    tie_embeddings=False,
    lora_rank=8,
    lora_alpha=16.0,
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
    source="hf:moonshotai/Moonlight-16B-A3B",
)
