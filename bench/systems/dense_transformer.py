"""The program's dense transformer (``repro.models.transformer``)."""

from __future__ import annotations

from typing import Dict

MLP = {"silu": "silu_glu", "gelu_pytorch_tanh": "gelu"}
# fixed in the program's ``models/layers.rmsnorm``
PROGRAM_RMS_EPS = 1e-6


def program_model(cfg: Dict):
    """The program's model for ``cfg``, served in bfloat16."""
    from repro.models import ModelConfig
    from repro.models.api import build_model
    if float(cfg["rms_norm_eps"]) != PROGRAM_RMS_EPS:
        raise ValueError(f"the program's RMS norm uses eps "
                         f"{PROGRAM_RMS_EPS}, the configuration states "
                         f"{cfg['rms_norm_eps']}")
    mcfg = ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", 0), d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], mlp=MLP[cfg["hidden_act"]],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), dtype=cfg["torch_dtype"])
    return build_model(mcfg)


def program_params(cfg: Dict, w: Dict) -> Dict:
    """The benchmark's weights in the program's parameter tree (the same
    device buffers, no copies)."""
    mlp = {"w_up": w["w_up"], "w_down": w["w_down"]}
    if "w_gate" in w:
        mlp["w_gate"] = w["w_gate"]
    layer = {"ln1": w["ln1"], "ln2": w["ln2"],
             "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                      "wo": w["wo"]},
             "mlp": mlp}
    params = {"embed": w["embed"], "final_norm": w["final_norm"],
              "layers": {"l0": layer}}
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = w["head"]
    return params
