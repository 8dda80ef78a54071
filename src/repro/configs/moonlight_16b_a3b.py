"""Moonlight-16B-A3B — the DeepSeek-V3 block at 2048 wide
(huggingface.co/moonshotai/Moonlight-16B-A3B, config.json, model_type
deepseek_v3): multi-head latent attention with 16 heads and no query LoRA
(queries 128 + 64 rotary dims; a 512-wide latent and one shared 64-dim
rotary key cached per token; values 128), one leading dense SwiGLU layer of
width 11264, then 26 layers of 64 routed experts (top-6, sigmoid scores with
a selection-only bias, renormalised and scaled by 2.446) plus 2 shared
experts of width 1408; untied output head, vocabulary 163840.
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    d_ff=11264,
    vocab_size=163840,
    kv_lora_rank=512,
    qk_rope_dim=64,
    v_head_dim=128,
    first_dense_layers=1,
    rope_theta=50000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        d_expert=1408,
        num_shared_experts=2,
        scoring="sigmoid",
        routed_scale=2.446,
    ),
)

SMOKE = ModelConfig(
    name="moonlight-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    head_dim=24,
    d_ff=96,
    vocab_size=256,
    kv_lora_rank=32,
    qk_rope_dim=8,
    v_head_dim=16,
    first_dense_layers=1,
    rope_theta=50000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    dtype="float32",
    moe=MoEConfig(
        num_experts=8,
        top_k=2,
        d_expert=32,
        num_shared_experts=1,
        scoring="sigmoid",
        routed_scale=2.446,
    ),
)
