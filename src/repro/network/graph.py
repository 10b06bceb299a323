"""LayerGraph IR: whole-network workload graphs for network-level DSE.

A :class:`LayerGraph` is an ordered sequence of :class:`LayerNode`s, each
wrapping one ``repro.core`` workload (a CONV layer or a GEMM) with an
occurrence count.  Two extractor families build graphs:

  * **CONV tables** — ``vgg16_graph()`` / ``resnet50_graph()`` wrap the
    per-layer tuples in ``core.workloads`` (including the stride-2
    ResNet50 downsampling cores) one node per layer, network order
    preserved, so contiguous-segment array assignment (``assign.py``)
    is meaningful.
  * **Model configs** — ``model_config_graph()`` walks a
    ``repro.models.ModelConfig`` and emits every GEMM a forward pass
    issues (attention projections, MLP, MoE experts + router, SSM
    in/out projections, LM head) for the prefill and decode stages.
    Identical shapes are deduped into one node with an occurrence
    count, so a 32-layer transformer collapses to a handful of unique
    workloads; ``tests/test_network.py`` pins these shapes against the
    actual parameter shapes of ``models/`` (``jax.eval_shape`` of
    ``init``).

``classes()`` is the shape-class dedup consumed by
:class:`~repro.network.session.NetworkSession` (one design sweep per
class, not per layer); ``gemm_shapes()`` is the (M, N, K) list the
TPU-side kernel pre-tune (``kernels.autotune.pretune_gemms``) resolves.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.core.workloads import (RESNET50_LAYERS, VGG16_LAYERS, Workload,
                                  conv2d, matmul)

ClassKey = Tuple[str, str]          # (workload name, dtype)


@dataclasses.dataclass(frozen=True)
class LayerNode:
    """One layer (or a deduped group of identical layers) of a network."""

    name: str
    wl: Workload
    count: int = 1                  # executions per network forward pass
    stage: str = ""                 # "conv" | "prefill" | "decode"

    @property
    def key(self) -> ClassKey:
        return (self.wl.name, self.wl.dtype)

    def macs(self) -> int:
        return self.count * self.wl.total_macs()


@dataclasses.dataclass(frozen=True)
class LayerClass:
    """All occurrences of one workload shape across the graph."""

    key: ClassKey
    wl: Workload
    count: int                      # total executions across all nodes
    nodes: Tuple[int, ...]          # indices into graph.nodes


@dataclasses.dataclass(frozen=True)
class LayerGraph:
    name: str
    nodes: Tuple[LayerNode, ...]

    def __len__(self) -> int:
        return len(self.nodes)

    def classes(self) -> Dict[ClassKey, LayerClass]:
        """Shape-class dedup, insertion-ordered by first occurrence."""
        out: Dict[ClassKey, LayerClass] = {}
        for i, n in enumerate(self.nodes):
            c = out.get(n.key)
            if c is None:
                out[n.key] = LayerClass(key=n.key, wl=n.wl, count=n.count,
                                        nodes=(i,))
            else:
                out[n.key] = LayerClass(key=c.key, wl=c.wl,
                                        count=c.count + n.count,
                                        nodes=c.nodes + (i,))
        return out

    def subset(self, stage: str) -> "LayerGraph":
        return LayerGraph(name=f"{self.name}:{stage}",
                          nodes=tuple(n for n in self.nodes
                                      if n.stage == stage))

    def total_macs(self) -> int:
        return sum(n.macs() for n in self.nodes)

    def total_flops(self) -> int:
        return 2 * self.total_macs()

    def gemm_shapes(self) -> List[Tuple[int, int, int]]:
        """Unique (M, N, K) of every matmul node, first-occurrence order.

        Raises on non-GEMM nodes — CONV graphs go through the systolic
        DSE, not the Pallas block tuner.
        """
        seen, out = set(), []
        for n in self.nodes:
            bounds = n.wl.bounds
            if set(bounds) != {"i", "j", "k"}:
                raise ValueError(
                    f"node {n.name!r} is not a GEMM (loops {n.wl.loop_names})")
            s = (bounds["i"], bounds["j"], bounds["k"])
            if s not in seen:
                seen.add(s)
                out.append(s)
        return out

    def summary(self) -> Dict:
        return {
            "name": self.name,
            "layers": sum(n.count for n in self.nodes),
            "nodes": len(self.nodes),
            "classes": len(self.classes()),
            "total_flops": self.total_flops(),
        }


# ---------------------------------------------------------------------- #
# CONV-table extractors
# ---------------------------------------------------------------------- #
def conv_graph(name: str, layers: Sequence[Tuple], dtype: str = "fp32"
               ) -> LayerGraph:
    """One node per table row ((I, O, H, W, P, Q[, stride]) tuples),
    network order preserved."""
    nodes = []
    for li, spec in enumerate(layers):
        wl = conv2d(*spec, dtype=dtype)
        nodes.append(LayerNode(name=f"conv{li}", wl=wl, stage="conv"))
    return LayerGraph(name=name, nodes=tuple(nodes))


def vgg16_graph() -> LayerGraph:
    return conv_graph("vgg16", VGG16_LAYERS)


def resnet50_graph() -> LayerGraph:
    """All 16 bottleneck 3x3 cores, including the stride-2 downsamplers."""
    return conv_graph("resnet50", RESNET50_LAYERS)


# ---------------------------------------------------------------------- #
# ModelConfig extractors
# ---------------------------------------------------------------------- #
def layer_gemm_slots(cfg) -> List[Tuple[str, int, int, int]]:
    """Per-network GEMM slots as (slot name, N, K, occurrences).

    N/K are the weight dims of ``x @ W`` (W stored (K, N) by
    ``models/layers.dense_init``); occurrences count how many times the
    slot's GEMM runs in one forward pass.  This is the single source of
    truth the parity test checks against the actual ``models/`` params.
    """
    d, hd = cfg.d_model, cfg.hd
    L = cfg.num_layers
    slots: List[Tuple[str, int, int, int]] = []

    def mlp_slots(prefix: str, f: int, times: int) -> None:
        if f <= 0 or times <= 0:
            return
        if cfg.mlp == "silu_glu":
            slots.append((f"{prefix}.w_gate", f, d, times))
        slots.append((f"{prefix}.w_up", f, d, times))
        slots.append((f"{prefix}.w_down", d, f, times))

    def attn_slots(prefix: str, times: int) -> None:
        slots.append((f"{prefix}.wq", cfg.num_heads * hd, d, times))
        slots.append((f"{prefix}.wk", cfg.num_kv_heads * hd, d, times))
        slots.append((f"{prefix}.wv", cfg.num_kv_heads * hd, d, times))
        slots.append((f"{prefix}.wo", d, cfg.num_heads * hd, times))

    if cfg.family in ("dense", "moe", "vlm"):
        n_moe = sum(1 for i in range(L) if cfg.is_moe_layer(i))
        attn_slots("attn", L)
        mlp_slots("mlp", cfg.d_ff, L - n_moe)
        if n_moe:
            slots.append(("moe.router", cfg.moe_experts, d, n_moe))
            # per-expert GEMMs run once per expert per MoE layer
            e_times = n_moe * cfg.moe_experts
            if cfg.mlp == "silu_glu":
                slots.append(("moe.w_gate", cfg.moe_d_ff, d, e_times))
            slots.append(("moe.w_up", cfg.moe_d_ff, d, e_times))
            slots.append(("moe.w_down", d, cfg.moe_d_ff, e_times))
            if cfg.moe_shared_expert:
                mlp_slots("moe.shared", cfg.moe_d_ff, n_moe)
    elif cfg.family in ("ssm", "hybrid"):
        din, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        slots.append(("mixer.in_proj", 2 * din + 2 * n + h, d, L))
        slots.append(("mixer.out_proj", d, din, L))
        if cfg.family == "hybrid" and cfg.hybrid_attn_period:
            # one *shared* transformer block invoked every period layers
            times = L // cfg.hybrid_attn_period
            attn_slots("shared_attn", times)
            mlp_slots("shared_mlp", cfg.d_ff, times)
    elif cfg.family == "encdec":
        # encoder blocks + decoder blocks (self- and cross-attention);
        # num_layers counts decoder layers (models/encdec.py)
        attn_slots("enc.attn", cfg.encoder_layers)
        mlp_slots("enc.mlp", cfg.d_ff, cfg.encoder_layers)
        attn_slots("dec.self_attn", L)
        attn_slots("dec.cross_attn", L)
        mlp_slots("dec.mlp", cfg.d_ff, L)
    else:
        raise ValueError(f"no GEMM extractor for family {cfg.family!r}")

    slots.append(("lm_head", cfg.vocab_size, d, 1))
    return slots


def _moe_expert_m(cfg, tokens: int) -> int:
    """Rows one expert's GEMM sees in a MoE layer over ``tokens`` tokens:
    the mean routed rows, tokens * top_k / experts (rounded up), as the
    dropless grouped kernel runs them."""
    return max(1, -(-tokens * cfg.moe_top_k // cfg.moe_experts))


def model_config_graph(cfg, batch: int = 1, prefill_len: int = 512,
                       decode_batch: Optional[int] = None,
                       stages: Iterable[str] = ("prefill", "decode"),
                       dtype: str = "bf16") -> LayerGraph:
    """Every GEMM shape a model config issues, deduped with counts.

    ``prefill`` GEMMs see ``batch * prefill_len`` token rows, ``decode``
    GEMMs ``decode_batch`` (default ``batch``) rows.  MoE expert GEMMs
    see the mean routed rows of one expert instead of the token count.
    """
    decode_batch = decode_batch if decode_batch is not None else batch
    slots = layer_gemm_slots(cfg)
    nodes: List[LayerNode] = []
    grouped: Dict[Tuple, int] = {}
    order: List[Tuple] = []
    for stage in stages:
        m_tokens = batch * prefill_len if stage == "prefill" else decode_batch
        for name, n_dim, k_dim, times in slots:
            m = m_tokens
            if name.startswith("moe.w"):
                m = _moe_expert_m(cfg, m_tokens)
            key = (stage, m, n_dim, k_dim)
            if key not in grouped:
                grouped[key] = 0
                order.append(key)
            grouped[key] += times
    for stage, m, n_dim, k_dim in order:
        wl = matmul(m, n_dim, k_dim, dtype=dtype)
        nodes.append(LayerNode(
            name=f"{stage}:mm_{m}x{n_dim}x{k_dim}", wl=wl,
            count=grouped[(stage, m, n_dim, k_dim)], stage=stage))
    return LayerGraph(name=f"{cfg.name}:{batch}x{prefill_len}",
                      nodes=tuple(nodes))
