"""stablelm-1.6b [dense] — MHA (kv == heads), LayerNorm with bias, QKV
bias, RoPE on the first quarter of each head.
[hf:stabilityai/stablelm-2-1_6b config.json]
24L d_model=2048 32H (kv=32, head 64) d_ff=5632 SiLU-gated vocab=100352,
partial_rotary_factor 0.25, rope_theta 10000, layer_norm_eps 1e-5,
use_qkv_bias, no qk layernorm, sequential residual, untied head.
"""
from repro.configs.base import ModelConfig

ARCH_ID = "stablelm-1.6b"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, family="dense",
        num_layers=24, d_model=2048, num_heads=32, num_kv_heads=32,
        head_dim=64, d_ff=5632, vocab_size=100_352,
        attn_kind="full", qkv_bias=True, act="swiglu", norm="layernorm",
        rope_theta=10_000.0, rotary_fraction=0.25, norm_eps=1e-5,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID + "-smoke", family="dense",
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        head_dim=16, d_ff=128, vocab_size=256,
        attn_kind="full", qkv_bias=True, act="swiglu", norm="layernorm",
        rope_theta=10_000.0, rotary_fraction=0.25, norm_eps=1e-5,
        remat="none",
    )
