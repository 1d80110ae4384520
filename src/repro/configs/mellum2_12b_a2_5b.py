"""Mellum2-12B-A2.5B [hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json].

MoE in every layer (64 SwiGLU experts of 896, top-8, softmax then top-k
renormalised, no shared expert), GQA 32/4 x 128, no biases.  Three
sliding-window layers (1024 positions, plain RoPE) then one full layer
(YaRN RoPE), seven times over.  ``d_ff`` is the expert width; the
published dense ``intermediate_size`` (7168) belongs to no layer.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=896,
    vocab_size=98304,
    qkv_bias=False,
    rope_theta=5e5,
    layer_types=("sliding", "sliding", "sliding", "full"),
    sliding_window=1024,
    yarn_factor=16.0,
    yarn_original_max_len=8192,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_attention_factor=1.2772588722239782,
    act="swiglu",
    norm="rmsnorm",
    num_experts=64,
    top_k=8,
)
