"""Architecture registry + per-(arch x shape) input specs.

``--arch <id>`` resolves through :data:`ARCHS`; ``input_specs`` builds the
ShapeDtypeStruct stand-ins for every model input of a given shape cell (the
dry-run lowers against these; nothing is allocated).

Shape semantics per family are documented in DESIGN.md §8:
  * LM families: train/prefill take tokens (B, S); decode takes one token
    against a cache of S.
  * whisper-tiny: encoder frames are stub embeddings; decoder length is
    S/8 for training, 64-token prompt for prefill, cache of S for decode
    (cross-KV of S/8).
  * qwen2-vl: stub vision embeddings fill the first S/8 positions; M-RoPE
    position grid is (3, B, S).
"""

from __future__ import annotations

import importlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.models import ModelConfig, ShapeConfig, SHAPES, shapes_for
from repro.models.api import build_model

_MODULES = {
    "smollm-135m": "smollm_135m",
    "qwen3-14b": "qwen3_14b",
    "starcoder2-7b": "starcoder2_7b",
    "nemotron-4-340b": "nemotron_4_340b",
    "whisper-tiny": "whisper_tiny",
    "zamba2-2.7b": "zamba2_2p7b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "grok-1-314b": "grok_1_314b",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "mamba2-130m": "mamba2_130m",
    "mellum2-12b-a2.5b": "mellum2_12b",
}

ARCH_IDS: List[str] = list(_MODULES)
# the architectures of the production dry-run's (arch x shape) cells: all
# but those that run on one chip's path only
DRYRUN_ARCH_IDS: List[str] = [a for a in ARCH_IDS
                              if a != "mellum2-12b-a2.5b"]


def _module(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _module(arch).smoke_config()


ARCHS: Dict[str, ModelConfig] = {a: get_config(a) for a in ARCH_IDS}


# ---------------------------------------------------------------------- #
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input of one cell."""
    B, S = shape.global_batch, shape.seq_len
    i32, bf16 = jnp.int32, jnp.bfloat16

    if cfg.family == "encdec":
        if shape.kind == "train":
            sd = max(1, S // 8)
            return {"enc_frames": _sds((B, S, cfg.d_model), bf16),
                    "tokens": _sds((B, sd), i32),
                    "labels": _sds((B, sd), i32)}
        if shape.kind == "prefill":
            return {"enc_frames": _sds((B, S, cfg.d_model), bf16),
                    "tokens": _sds((B, 64), i32),
                    "labels": _sds((B, 64), i32)}
        # decode: one decoder token vs caches of S (cross-KV of S/8)
        model = build_model(cfg)
        cache = jax.eval_shape(
            lambda: model.init_cache(B, S, enc_len=max(1, S // 8)))
        return {"tokens": _sds((B, 1), i32), "pos": _sds((B,), i32),
                "cache": cache}

    batch = {"tokens": _sds((B, S), i32)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = _sds((B, S // cfg.vision_frac,
                                       cfg.d_model), bf16)
        batch["positions"] = _sds((3, B, S), i32)
    if shape.kind == "train":
        batch["labels"] = _sds((B, S), i32)
        return batch
    if shape.kind == "prefill":
        return batch
    # decode
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(B, S))
    out = {"tokens": _sds((B, 1), i32), "pos": _sds((B,), i32),
           "cache": cache}
    if cfg.family == "vlm":
        out["positions"] = _sds((3, B, 1), i32)
    return out


def all_cells() -> List[Tuple[str, str]]:
    """The 40 assigned (arch x shape) dry-run cells (skips noted in
    DESIGN.md produce fewer than 10 x 4)."""
    cells = []
    for a in DRYRUN_ARCH_IDS:
        for s in shapes_for(ARCHS[a]):
            cells.append((a, s.name))
    return cells
