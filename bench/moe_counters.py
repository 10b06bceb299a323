"""Operations and bytes of the expert layer, from group sizes alone.

The benchmark's own arithmetic for the MoE cells, beside ``counters.py``:
a later change to the program cannot move it.  A grouped call multiplies
the rows of each group (expert) g, ``m_g`` of them, by that group's
(K, N) weight.  Its useful work is ``2 m_g N K`` per group; its least
traffic reads the group's rows, its weight (only if ``m_g > 0``: an
expert with no rows is not read) and writes its output rows.  Padding of
partial tiles and the dispatch around the calls are not counted.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple

Call = Tuple[Sequence[int], int, int]   # (group sizes, N, K)


def group_flops(m: int, N: int, K: int) -> int:
    return 2 * m * N * K


def group_bytes(m: int, N: int, K: int, itemsize: int = 2) -> int:
    return itemsize * (m * K + (K * N if m > 0 else 0) + m * N)


def layer_calls(cfg: Dict, sizes: Sequence[int]) -> List[Call]:
    """The grouped calls of one expert layer whose experts took ``sizes``
    rows: gate and up (N = expert width, K = hidden), then down."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return [(sizes, F, D), (sizes, F, D), (sizes, D, F)]


def min_seconds(calls: Iterable[Call], peak: Dict) -> float:
    """The least time the chip could take for ``calls``: per group the
    larger of its FLOPs over peak FLOP/s and its bytes over HBM
    bandwidth, summed."""
    return sum(max(group_flops(m, N, K) / peak["bf16_flops"],
                   group_bytes(m, N, K) / peak["hbm_bytes_per_s"])
               for sizes, N, K in calls for m in sizes)


def router_flops(cfg: Dict, tokens: int) -> int:
    return 2 * tokens * cfg["hidden_size"] * cfg["num_experts"]


def expert_flops(cfg: Dict, tokens: int) -> int:
    """The routed experts' useful work: k rows a token through gate, up
    and down (the same as the grouped calls' at any routing)."""
    rows = tokens * cfg["num_experts_per_tok"]
    return 3 * 2 * rows * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_flops(cfg: Dict, tokens: int) -> int:
    """Useful work of one expert layer: the router and the experts."""
    return router_flops(cfg, tokens) + expert_flops(cfg, tokens)
