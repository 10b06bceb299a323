"""Mellum2-12B-A2.5B [hf:JetBrains/Mellum2-12B-A2.5B-Instruct] — a code
model of 28 sparse layers: 64 routed experts, top-8, renormalised, no
shared expert; GQA 32/4 x 128; three sliding-window layers (1024, default
RoPE) then one full layer (YaRN RoPE), repeated."""

from repro.models.config import ModelConfig

PERIOD = ("sliding", "sliding", "sliding", "full")

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b", family="moe",
    num_layers=28, d_model=2304, num_heads=32, num_kv_heads=4, head_dim=128,
    d_ff=7168, vocab_size=98304,
    moe_experts=64, moe_top_k=8, moe_interleave=1, moe_d_ff=896,
    mlp="silu_glu", tie_embeddings=False, rope_theta=5e5,
    layer_types=PERIOD * 7, sliding_window=1024,
    yarn_factor=16.0, yarn_original_max=8192, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_attention_factor=1.2772588722239782,
)


def smoke_config() -> ModelConfig:
    """One period of the layer pattern at toy widths; the window (8)
    binds at the tests' prompt lengths and YaRN's ramp covers some of the
    16-wide heads' frequencies."""
    return ModelConfig(
        name="mellum2-smoke", family="moe",
        num_layers=4, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256,
        moe_experts=8, moe_top_k=2, moe_interleave=1, moe_d_ff=32,
        mlp="silu_glu", tie_embeddings=False, rope_theta=1e4,
        layer_types=PERIOD, sliding_window=8,
        yarn_factor=4.0, yarn_original_max=32, yarn_beta_fast=32.0,
        yarn_beta_slow=1.0, yarn_attention_factor=1.1386,
    )
