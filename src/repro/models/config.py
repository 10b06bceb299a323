"""Unified model configuration covering all assigned architecture families."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 => d_model // num_heads

    # attention options
    qk_norm: bool = False
    rope_theta: float = 10000.0
    mrope: bool = False           # Qwen2-VL multimodal RoPE
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)

    # attention kinds per layer (published ``layer_types``): "full" or
    # "sliding"; empty = every layer full.  A sliding layer's query at
    # position i sees keys j with i - sliding_window < j <= i.
    layer_types: Tuple[str, ...] = ()
    sliding_window: int = 0
    # YaRN RoPE on the full layers (``rope_type: "yarn"``; 0 = default
    # RoPE everywhere); sliding layers keep default RoPE at rope_theta
    yarn_factor: float = 0.0
    yarn_original_max: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0

    # MLP
    mlp: str = "silu_glu"         # silu_glu | relu2 | gelu
    tie_embeddings: bool = False

    # MoE
    moe_experts: int = 0
    moe_top_k: int = 1
    moe_interleave: int = 1       # every Nth layer is MoE
    moe_d_ff: int = 0
    moe_shared_expert: bool = False
    capacity_factor: float = 1.25

    # SSM (Mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    ssm_conv: int = 4

    # hybrid (Zamba2): one shared transformer block every N SSM layers
    hybrid_attn_period: int = 0

    # enc-dec (Whisper): num_layers = decoder layers
    encoder_layers: int = 0

    # vlm: fraction of sequence positions fed by the (stub) vision frontend
    vision_frac: int = 8          # 1/8 of the sequence

    dtype: str = "bfloat16"
    # training plumbing
    train_microbatches: int = 1
    optimizer_state_dtype: str = "float32"

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def is_moe_layer(self, idx: int) -> bool:
        if self.moe_experts == 0:
            return False
        return (idx % self.moe_interleave) == (self.moe_interleave - 1)

    def param_count(self) -> int:
        """Analytic parameter count (used for roofline MODEL_FLOPS)."""
        d, hd = self.d_model, self.hd
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        attn = d * self.num_heads * hd * 2 + d * self.num_kv_heads * hd * 2
        if self.mlp == "silu_glu":
            mlp = 3 * d * self.d_ff
        else:
            mlp = 2 * d * self.d_ff
        total = emb
        if self.family in ("dense", "vlm", "moe"):
            for i in range(self.num_layers):
                total += attn
                if self.is_moe_layer(i):
                    e_mlp = 3 * d * self.moe_d_ff
                    total += self.moe_experts * e_mlp
                    if self.moe_shared_expert:
                        total += e_mlp
                else:
                    total += mlp
        elif self.family == "ssm":
            total += self.num_layers * self._ssm_layer_params()
        elif self.family == "hybrid":
            total += self.num_layers * self._ssm_layer_params()
            total += attn + mlp  # one shared transformer block
        elif self.family == "encdec":
            total += self.encoder_layers * (attn + mlp)
            total += self.num_layers * (2 * attn + mlp)  # self + cross
        return total

    def _ssm_layer_params(self) -> int:
        d, din, n = self.d_model, self.d_inner, self.ssm_state
        h = self.ssm_heads
        in_proj = d * (2 * din + 2 * n + h)
        conv = self.ssm_conv * (din + 2 * n)
        out = din * d
        return in_proj + conv + out + 2 * h + din

    def active_param_count(self) -> int:
        """Activated params per token (MoE: top_k experts only)."""
        if self.moe_experts == 0:
            return self.param_count()
        full = self.param_count()
        d = self.d_model
        e_mlp = 3 * d * self.moe_d_ff
        n_moe = sum(1 for i in range(self.num_layers) if self.is_moe_layer(i))
        inactive = n_moe * (self.moe_experts - self.moe_top_k) * e_mlp
        return full - inactive


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

# long_500k needs sub-quadratic attention: only SSM/hybrid run it
LONG_CONTEXT_FAMILIES = ("ssm", "hybrid")


def shapes_for(cfg: ModelConfig):
    out = []
    for s in SHAPES.values():
        if s.name == "long_500k" and cfg.family not in LONG_CONTEXT_FAMILIES:
            continue  # skipped per DESIGN.md §4 (quadratic full attention)
        out.append(s)
    return out
