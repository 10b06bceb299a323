"""Operations and bytes, computed from shapes alone.

These are the benchmark's own arithmetic: a later change to the program
cannot move them.  Configurations are the JSON files of ``configs/``, in
the key names of their published ``config.json``.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

Shape = Tuple[int, int, int]        # (M, N, K) of an (M, K) x (K, N) GEMM


def head_dim(cfg: Dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def gated_mlp(cfg: Dict) -> bool:
    return cfg["hidden_act"] == "silu"


def layer_weight_shapes(cfg: Dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of each weight one dense transformer layer multiplies
    by, in the order a layer runs them."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["intermediate_size"]
    out = [("wq", d, hq * hd), ("wk", d, hkv * hd), ("wv", d, hkv * hd),
           ("wo", hq * hd, d)]
    if gated_mlp(cfg):
        out.append(("w_gate", d, f))
    out += [("w_up", d, f), ("w_down", f, d)]
    return out


def layer_params(cfg: Dict) -> int:
    """Parameters of the matrices of one layer (norm vectors left out)."""
    return sum(k * n for _, k, n in layer_weight_shapes(cfg))


def gemm_classes(cfg: Dict, rows: int) -> List[Tuple[Shape, int]]:
    """Every distinct GEMM of one model step over ``rows`` token rows,
    with how often it occurs: each layer's projections, then the head."""
    L = cfg["num_hidden_layers"]
    counts: Dict[Shape, int] = {}
    for _, k, n in layer_weight_shapes(cfg):
        counts[(rows, n, k)] = counts.get((rows, n, k), 0) + L
    head = (rows, cfg["vocab_size"], cfg["hidden_size"])
    counts[head] = counts.get(head, 0) + 1
    return list(counts.items())


def gemm_flops(shape: Shape) -> int:
    M, N, K = shape
    return 2 * M * N * K


def gemm_min_bytes(shape: Shape, itemsize: int = 2) -> int:
    """Each operand read once and the result written once."""
    M, N, K = shape
    return itemsize * (M * K + K * N + M * N)


def gemm_min_seconds(shape: Shape, peak: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(gemm_flops(shape) / peak["bf16_flops"],
               gemm_min_bytes(shape) / peak["hbm_bytes_per_s"])


def request_flops(cfg: Dict, prompt_len: int, new_tokens: int) -> int:
    """Useful model FLOPs of one served request.

    Every prompt token and every fed-back output token passes the stack
    once: the projections, and attention's two products over its causal
    context (itself included).  The head counts only where a token is
    produced: the last prompt row and each decode step.  Padding, parked
    slots and discarded chunk logits are not counted."""
    L, hd = cfg["num_hidden_layers"], head_dim(cfg)
    fed = prompt_len + max(0, new_tokens - 1)
    contexts = fed * (fed + 1) // 2
    attn = 2 * 2 * cfg["num_attention_heads"] * hd
    head = 2 * cfg["vocab_size"] * cfg["hidden_size"]
    return (L * (2 * layer_params(cfg) * fed + attn * contexts)
            + head * new_tokens)
