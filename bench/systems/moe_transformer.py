"""The program's transformer with routed experts and mixed attention
(``repro.models.transformer``, family "moe")."""

from __future__ import annotations

from typing import Dict

from .dense_transformer import PROGRAM_RMS_EPS

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def program_config(cfg: Dict):
    """The program's ``ModelConfig`` for ``cfg``, served in bfloat16."""
    from repro.models import ModelConfig
    if float(cfg["rms_norm_eps"]) != PROGRAM_RMS_EPS:
        raise ValueError(f"the program's RMS norm uses eps "
                         f"{PROGRAM_RMS_EPS}, the configuration states "
                         f"{cfg['rms_norm_eps']}")
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program renormalises the top-k weights")
    rope = cfg["rope_parameters"]
    thetas = {float(r["rope_theta"]) for r in rope.values()}
    if len(thetas) != 1 or rope["sliding_attention"]["rope_type"] != "default":
        raise ValueError("the program has one rope_theta, and default "
                         "RoPE on sliding layers")
    full = rope["full_attention"]
    yarn = {}
    if full["rope_type"] == "yarn":
        yarn = dict(yarn_factor=float(full["factor"]),
                    yarn_original_max=int(
                        full["original_max_position_embeddings"]),
                    yarn_beta_fast=float(full["beta_fast"]),
                    yarn_beta_slow=float(full["beta_slow"]),
                    yarn_attention_factor=float(full["attention_factor"]))
    L = cfg["num_hidden_layers"]
    return ModelConfig(
        name=cfg["name"], family="moe", num_layers=L,
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        moe_experts=cfg["num_experts"],
        moe_top_k=cfg["num_experts_per_tok"], moe_interleave=1,
        moe_d_ff=cfg["moe_intermediate_size"], mlp="silu_glu",
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=thetas.pop(),
        layer_types=tuple(KINDS[t] for t in cfg["layer_types"][:L]),
        sliding_window=cfg["sliding_window"], dtype=cfg["torch_dtype"],
        **yarn)


def program_model(cfg: Dict):
    from repro.models.api import build_model
    return build_model(program_config(cfg))


def program_params(cfg: Dict, w: Dict) -> Dict:
    """The benchmark's weights in the program's parameter tree (the same
    device buffers, no copies)."""
    layer = {"ln1": w["ln1"], "ln2": w["ln2"],
             "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
             "moe": {n: w[n] for n in ("router", "w_gate", "w_up",
                                       "w_down")}}
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "lm_head": w["head"], "layers": {"l0": layer}}


def moe_params(w: Dict) -> Dict:
    """One layer's expert weights in the program's MoE block layout."""
    return {n: w[n] for n in ("router", "w_gate", "w_up", "w_down")}
