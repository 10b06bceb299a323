"""Odyssey-on-TPU: the paper's DSE machinery applied to Pallas block shapes.

This is the faithful hardware adaptation (DESIGN.md §2): the genome is the
Pallas block shape ``(bm, bk, bn)`` plus the grid permutation (k-innermost vs
k-outermost), the resource constraint is VMEM instead of BRAM/DSP, and the
latency model keeps the paper's prologue + steady-state max(compute, DMA) +
epilogue structure with double buffering, and adds what each grid step
costs beyond it, with constants measured on the chip
(``benchmarks.step_cost``).  Non-divisor block shapes are
first-class — edge blocks are padded, and the model charges the padding
(``ceil`` grid terms), exactly like the paper's zero-padded non-divisor
tiling.  The evolutionary engine is literally ``repro.core.evolutionary``.

Every genome the search draws compiles for the chip: block dims obey
Mosaic's (8, 128) rule (``matmul.legal_blocks``) and the block's VMEM
limit, the one ``matmul`` passes to Mosaic, fits ``VMEM_LIMIT_MAX``.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import os
import random
import threading
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.evolutionary import EvoConfig, Problem, evolve
from repro.core.hardware import TPU_V5E, HardwareProfile
from repro.core.perf_model import _quartic
from repro.obs import get_metrics, get_tracer

from .gmm import GmmConfig, max_tile_visits
from .matmul import (LANE, SUBLANE, VMEM_LIMIT_MAX, MatmulConfig,
                     legal_block, legal_blocks, vmem_bytes,
                     vmem_limit_bytes)

BlockGenome = Tuple[int, int, int, bool]  # (bm, bk, bn, k_innermost)

# Fixed cost of one grid step beyond max(compute, DMA): pipeline
# bookkeeping, DMA issue and wait (``benchmarks.step_cost``, TPU v5e).
GRID_STEP_S = 2.08e-7
# Cost per byte of the f32 accumulator read and written back on each
# k-inner step after the first (``benchmarks.step_cost``, TPU v5e).
ACC_RMW_S_PER_BYTE = 4.28e-14


def _up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass
class TpuMatmulModel:
    """Analytic latency/VMEM model of the Pallas matmul on one TPU core."""

    M: int
    N: int
    K: int
    dtype_bytes: int = 2
    hw: HardwareProfile = TPU_V5E

    def grid(self, g: BlockGenome) -> Tuple[int, int, int]:
        bm, bk, bn, _ = g
        return (math.ceil(self.M / bm), math.ceil(self.N / bn),
                math.ceil(self.K / bk))

    def vmem_bytes(self, g: BlockGenome) -> int:
        bm, bk, bn, k_inner = g
        return vmem_bytes(bm, bk, bn, self.dtype_bytes, k_inner,
                          masked=self.K % bk != 0)

    def vmem_limit_bytes(self, g: BlockGenome) -> int:
        """The scoped-VMEM limit ``matmul`` passes to Mosaic for ``g``."""
        return vmem_limit_bytes(self.vmem_bytes(g))

    def feasible(self, g: BlockGenome) -> bool:
        return self.vmem_limit_bytes(g) <= VMEM_LIMIT_MAX

    def block_compute_s(self, g: BlockGenome) -> float:
        bm, bk, bn, _ = g
        # MXU granularity: sublane 8 on M, lane 128 on K/N
        flops = 2 * _up(bm, 8) * _up(bk, 128) * _up(bn, 128)
        return flops / self.hw.flops_peak

    def block_dma_s(self, g: BlockGenome) -> float:
        bm, bk, bn, k_inner = g
        gm, gn, gk = self.grid(g)
        bytes_in = (bm * bk + bk * bn) * self.dtype_bytes
        # C written once per (m, n) block, amortized over the k sweep; the
        # k-outer partial sums' round trip is waited (``step_cost_s``)
        bytes_out = bm * bn * self.dtype_bytes / gk if k_inner else 0
        t = (bytes_in + bytes_out) / self.hw.hbm_bw
        return t + self.hw.dma_overhead_cycles / self.hw.freq_hz

    def pipeline_s(self, g: BlockGenome) -> float:
        """The paper's double-buffered pipeline: prologue + steady-state
        max(compute, DMA) per block + epilogue."""
        gm, gn, gk = self.grid(g)
        n_blocks = gm * gn * gk
        tc, td = self.block_compute_s(g), self.block_dma_s(g)
        prologue = td
        epilogue = (g[0] * g[2] * self.dtype_bytes) / self.hw.hbm_bw
        return prologue + tc + (n_blocks - 1) * max(tc, td) + epilogue

    def step_cost_s(self, g: BlockGenome) -> float:
        """What the grid steps cost beyond the pipeline's max(compute,
        DMA): a fixed cost per step, and the f32 accumulator's update,
        in VMEM on each k-inner step after the first, or as the k-outer
        partial sum's waited round trip through HBM on every step."""
        bm, _, bn, k_inner = g
        gm, gn, gk = self.grid(g)
        acc = bm * bn * 4
        if k_inner:
            extra = gm * gn * (gk - 1) * acc * ACC_RMW_S_PER_BYTE
        else:
            extra = gm * gn * gk * (2 * acc / self.hw.hbm_bw)
        return gm * gn * gk * GRID_STEP_S + extra

    def latency_s(self, g: BlockGenome) -> float:
        return self.pipeline_s(g) + self.step_cost_s(g)

    def fitness(self, g: BlockGenome) -> float:
        lat = self.latency_s(g)
        v = self.vmem_limit_bytes(g)
        if v > VMEM_LIMIT_MAX:
            lat *= _quartic(v / VMEM_LIMIT_MAX)
        return -lat

    def mfu(self, g: BlockGenome) -> float:
        useful = 2 * self.M * self.N * self.K
        return useful / self.hw.flops_peak / self.latency_s(g)

    # -- batched evaluation (same interface as BatchPerformanceModel) ------
    def fitness_batch(self, genomes: Sequence[BlockGenome]) -> np.ndarray:
        """Vectorized ``fitness`` over a whole population.

        Mirrors the scalar arithmetic operation-for-operation (same float
        divisions and accumulation order), so it matches scalar ``fitness``
        bit-for-bit — the same contract the FPGA-side batch model honors.
        """
        bm = np.array([g[0] for g in genomes], dtype=np.int64)
        bk = np.array([g[1] for g in genomes], dtype=np.int64)
        bn = np.array([g[2] for g in genomes], dtype=np.int64)
        k_inner = np.array([g[3] for g in genomes], dtype=bool)
        db = self.dtype_bytes

        gm = np.ceil(self.M / bm)
        gn = np.ceil(self.N / bn)
        gk = np.ceil(self.K / bk)

        def up(x, m):
            return ((x + m - 1) // m) * m

        tc = (2 * up(bm, 8) * up(bk, 128) * up(bn, 128)) / self.hw.flops_peak
        bytes_in = (bm * bk + bk * bn) * db
        bytes_out = np.where(k_inner, bm * bn * db / gk, 0.0)
        td = (bytes_in + bytes_out) / self.hw.hbm_bw \
            + self.hw.dma_overhead_cycles / self.hw.freq_hz

        n_blocks = gm * gn * gk
        epilogue = (bm * bn * db) / self.hw.hbm_bw
        acc = bm * bn * 4
        extra = np.where(k_inner,
                         gm * gn * (gk - 1) * acc * ACC_RMW_S_PER_BYTE,
                         n_blocks * (2 * acc / self.hw.hbm_bw))
        lat = (td + tc + (n_blocks - 1) * np.maximum(tc, td) + epilogue) \
            + (n_blocks * GRID_STEP_S + extra)

        limit = vmem_limit_bytes(vmem_bytes(bm, bk, bn, db, k_inner,
                                            masked=self.K % bk != 0))
        lat = np.where(limit > VMEM_LIMIT_MAX,
                       lat * _quartic(limit / VMEM_LIMIT_MAX), lat)
        return -lat


class TpuMatmulProblem(Problem):
    """core.evolutionary.Problem over Pallas block genomes.

    ``sample``, ``mutate`` and ``crossover`` draw freely and then
    :meth:`legalize`, so every genome they return compiles."""

    def __init__(self, model: TpuMatmulModel):
        self.model = model
        self.dims = (model.M, model.K, model.N)

    def legalize(self, g: BlockGenome) -> BlockGenome:
        """Snap ``g`` to Mosaic's block rule, then halve its largest
        block dim until the VMEM limit fits."""
        M, K, N = self.dims
        blocks = list(legal_blocks(g[0], g[1], g[2], M, K, N))
        granules = ((M, SUBLANE), (K, LANE), (N, LANE))
        k_inner = g[3]
        while not self.model.feasible((*blocks, k_inner)):
            for i in sorted(range(3), key=lambda i: (blocks[i], i),
                            reverse=True):
                half = legal_block(blocks[i] // 2, *granules[i])
                if half < blocks[i]:
                    blocks[i] = half
                    break
            else:
                break           # every dim at its smallest legal block
        return (blocks[0], blocks[1], blocks[2], k_inner)

    def sample(self, rng: random.Random) -> BlockGenome:
        vals = []
        for d in self.dims:
            vals.append(rng.randint(1, min(d, 2048)))
        return self.legalize((vals[0], vals[1], vals[2], rng.random() < 0.9))

    def mutate(self, g: BlockGenome, rng: random.Random,
               alpha: float) -> BlockGenome:
        bm, bk, bn, k_inner = g
        vals = [bm, bk, bn]
        i = rng.randrange(3)
        if rng.random() < alpha:
            # factorization-style: halve/double
            vals[i] = max(1, vals[i] // 2) if rng.random() < 0.5 \
                else min(self.dims[i], vals[i] * 2)
        else:
            # random (non-divisor) mutation
            vals[i] = rng.randint(1, min(self.dims[i], 2048))
        if rng.random() < 0.05:
            k_inner = not k_inner
        return self.legalize((vals[0], vals[1], vals[2], k_inner))

    def crossover(self, a: BlockGenome, b: BlockGenome,
                  rng: random.Random) -> BlockGenome:
        pick = lambda i: (a if rng.random() < 0.5 else b)[i]
        return self.legalize((pick(0), pick(1), pick(2), pick(3)))

    def fitness(self, g: BlockGenome) -> float:
        return self.model.fitness(g)

    def fitness_batch(self, genomes: Sequence[BlockGenome]) -> np.ndarray:
        return self.model.fitness_batch(genomes)

    def key(self, g: BlockGenome):
        return g


@dataclasses.dataclass
class TpuGmmModel:
    """Latency model of the grouped kernel (``kernels/gmm.py``) for ``R``
    routed rows in ``E`` groups of sizes known only at run time.

    Each (group, m-tile) visit runs the k-inner matmul of one ``bm``-row
    tile against its group's ``(K, N)`` weight, so the kernel is priced as
    :class:`TpuMatmulModel` over ``visits(bm) * bm`` rows: its pipeline
    and step costs per tile, and each visit's read of its expert's
    weight blocks.  ``visits`` charges the partial tiles at group
    boundaries at their full cost: one extra tile for every group but
    the first, at most one per row."""

    R: int
    N: int
    K: int
    E: int
    dtype_bytes: int = 2
    hw: HardwareProfile = TPU_V5E

    def visits(self, bm: int) -> int:
        return max_tile_visits(self.R, min(self.E, self.R), bm)

    def tiles_model(self, g: BlockGenome) -> TpuMatmulModel:
        """The dense matmul model of the kernel's tile visits."""
        return TpuMatmulModel(M=self.visits(g[0]) * g[0], N=self.N,
                              K=self.K, dtype_bytes=self.dtype_bytes,
                              hw=self.hw)

    def grid(self, g: BlockGenome) -> Tuple[int, int, int]:
        return self.tiles_model(g).grid(g)

    def feasible(self, g: BlockGenome) -> bool:
        return self.tiles_model(g).feasible(g)

    def latency_s(self, g: BlockGenome) -> float:
        mm = self.tiles_model(g)
        return mm.pipeline_s(g) + mm.step_cost_s(g)

    def fitness(self, g: BlockGenome) -> float:
        return self.tiles_model(g).fitness(g)

    def mfu(self, g: BlockGenome) -> float:
        useful = 2 * self.R * self.N * self.K
        return useful / self.hw.flops_peak / self.latency_s(g)

    def fitness_batch(self, genomes: Sequence[BlockGenome]) -> np.ndarray:
        return np.array([self.fitness(g) for g in genomes])


class TpuGmmProblem(TpuMatmulProblem):
    """Block genomes of the grouped kernel: k innermost, always."""

    def __init__(self, model: TpuGmmModel):
        self.model = model
        self.dims = (model.R, model.K, model.N)

    def legalize(self, g: BlockGenome) -> BlockGenome:
        return super().legalize((g[0], g[1], g[2], True))


@functools.lru_cache(maxsize=4096)
def _tune_matmul_cached(M: int, N: int, K: int, dtype_bytes: int,
                        evals: int, seed: int,
                        extra_seeds: Tuple[BlockGenome, ...]
                        ) -> Tuple[MatmulConfig, int]:
    """(config, evals_spent); ``extra_seeds`` warm-start the search."""
    model = TpuMatmulModel(M=M, N=N, K=K, dtype_bytes=dtype_bytes)
    problem = TpuMatmulProblem(model)
    cfg = EvoConfig(population=48, parents=12, epochs=60, seed=seed,
                    max_evals=evals)
    seeds = [problem.legalize(g) for g in list(extra_seeds) +
             [(256, 512, 256, True), (128, 128, 128, True)]]
    res = evolve(problem, cfg, seeds=seeds)
    bm, bk, bn, k_inner = res.best
    return (MatmulConfig(bm=bm, bk=bk, bn=bn, k_innermost=k_inner),
            res.evals)


def tune_matmul(M: int, N: int, K: int, dtype_bytes: int = 2,
                evals: int = 2000, seed: int = 0) -> MatmulConfig:
    """Search the block-shape space for (M, N, K); returns a MatmulConfig."""
    return _tune_matmul_cached(M, N, K, dtype_bytes, evals, seed, ())[0]


@functools.lru_cache(maxsize=4096)
def _tune_gmm_cached(R: int, N: int, K: int, E: int, dtype_bytes: int,
                     evals: int, seed: int,
                     extra_seeds: Tuple[BlockGenome, ...]
                     ) -> Tuple[GmmConfig, int]:
    """(config, evals_spent) of the grouped kernel's block search."""
    model = TpuGmmModel(R=R, N=N, K=K, E=E, dtype_bytes=dtype_bytes)
    problem = TpuGmmProblem(model)
    cfg = EvoConfig(population=48, parents=12, epochs=60, seed=seed,
                    max_evals=evals)
    seeds = [problem.legalize(g) for g in list(extra_seeds) +
             [(256, 512, 256, True), (128, 128, 128, True)]]
    res = evolve(problem, cfg, seeds=seeds)
    bm, bk, bn, _ = res.best
    return GmmConfig(bm=bm, bk=bk, bn=bn), res.evals


# ---------------------------------------------------------------------- #
# Registry-backed resolution: in-memory LRU in front of the on-disk store
# ---------------------------------------------------------------------- #
_lru_lock = threading.Lock()
_config_lru: "collections.OrderedDict[Tuple, object]" = \
    collections.OrderedDict()
_CONFIG_LRU_MAX = 4096


def default_registry():
    """The process-default block registry: $REPRO_REGISTRY_DIR, else None.

    Returning None (no env var) keeps library behavior hermetic — nothing
    is read from or written to the user's home directory unless a
    registry is opted into explicitly or via the environment.
    """
    from repro.registry import RegistryStore, DEFAULT_ROOT_ENV
    root = os.environ.get(DEFAULT_ROOT_ENV)
    return RegistryStore(root) if root else None


def _genome(cfg) -> BlockGenome:
    """The search genome of a matmul or grouped config (the grouped
    kernel is k-inner)."""
    return (cfg.bm, cfg.bk, cfg.bn, getattr(cfg, "k_innermost", True))


def _block_entry(g: BlockGenome, model) -> Dict:
    return {"bm": g[0], "bk": g[1], "bn": g[2], "k_innermost": g[3],
            "latency_s": model.latency_s(g), "mfu": model.mfu(g),
            "feasible": model.feasible(g)}


def resolve_matmul_config(M: int, N: int, K: int, dtype_bytes: int = 2,
                          registry=None, evals: int = 2000,
                          seed: int = 0) -> MatmulConfig:
    """Block shape for (M, N, K): LRU -> disk registry -> warm-started tune.

    The call-time path the kernels use.  Exact registry hits return the
    cached shape with zero search evals; misses warm-start from the
    nearest cached matmul (dims clamped), tune, and record — so every
    replica sharing a registry root tunes each shape once, fleet-wide.

    Each call is a ``tuner.resolve`` span (``M, N, K, source, evals``,
    and ``steps``, the picked block's grid step count) and counts its
    source in ``obs.Metrics``: ``tuner.lru_hits``, ``tuner.disk_hits``
    or ``tuner.tuned``, with ``tuner.evals`` the search evaluations spent
    and ``tuner.resolve_s`` its seconds.

    The LRU is keyed by (shape, dtype, registry root), so resolving
    against different registries never cross-talks and a registry-backed
    call always reaches its store at least once.  ``evals``/``seed`` are
    deliberately not in the key: the first config resolved for a shape
    is reused for the process lifetime — call :func:`tune_matmul` for a
    budget-controlled search.
    """
    def fingerprint():
        from repro.registry import matmul_block_fingerprint
        return matmul_block_fingerprint(M, N, K, dtype_bytes, TPU_V5E)

    def tune(extra):
        return _tune_matmul_cached(M, N, K, dtype_bytes, evals, seed,
                                   extra)

    return _resolve((M, N, K, dtype_bytes), registry,
                    TpuMatmulModel(M=M, N=N, K=K, dtype_bytes=dtype_bytes),
                    lambda g: MatmulConfig(bm=g[0], bk=g[1], bn=g[2],
                                           k_innermost=g[3]),
                    fingerprint, tune, "tpu_block", dict(M=M, N=N, K=K))


def resolve_gmm_config(R: int, N: int, K: int, E: int,
                       dtype_bytes: int = 2, registry=None,
                       evals: int = 2000, seed: int = 0) -> GmmConfig:
    """Block shape of the grouped kernel for ``R`` routed rows times
    ``E`` weights of (K, N): the same LRU -> registry -> tune path as
    :func:`resolve_matmul_config`, under keys of their own (the LRU key
    starts with ``"gmm"``; the registry fingerprint is of kind
    ``tpu_gmm_block``), so a grouped record never serves or seeds a
    matmul and the other way round.  The ``tuner.resolve`` span carries
    ``kind="gmm"`` and ``E``; the counters are the matmul's."""
    def fingerprint():
        from repro.registry import gmm_block_fingerprint
        return gmm_block_fingerprint(R, N, K, E, dtype_bytes, TPU_V5E)

    def tune(extra):
        return _tune_gmm_cached(R, N, K, E, dtype_bytes, evals, seed, extra)

    return _resolve(("gmm", R, N, K, E, dtype_bytes), registry,
                    TpuGmmModel(R=R, N=N, K=K, E=E, dtype_bytes=dtype_bytes),
                    lambda g: GmmConfig(bm=g[0], bk=g[1], bn=g[2]),
                    fingerprint, tune, "tpu_gmm_block",
                    dict(kind="gmm", M=R, N=N, K=K, E=E))


def _resolve(key: Tuple, registry, model, make_config, fingerprint, tune,
             kind: str, span_args: Dict):
    """The config for ``key``: LRU -> disk registry -> warm-started tune,
    under a ``tuner.resolve`` span, counted in ``obs.Metrics``.
    ``make_config`` builds a config from a genome; ``fingerprint()`` is
    the registry's key; ``tune(seeds)`` searches, warm-started from
    neighbours' genomes (legalized by the search); ``kind`` names the
    records."""
    t0 = time.perf_counter()
    with get_tracer().span("tuner.resolve", "tuner", **span_args) as span:
        cfg, source, spent = _lookup(key, registry, model, make_config,
                                     fingerprint, tune, kind)
        gm, gn, gk = model.grid(_genome(cfg))
        span.set(source=source, evals=spent, steps=gm * gn * gk)
    metrics = get_metrics()
    metrics.counter("tuner." + source)
    metrics.counter("tuner.evals", spent)
    metrics.observe("tuner.resolve_s", time.perf_counter() - t0)
    return cfg


def _lookup(key: Tuple, registry, model, make_config, fingerprint, tune,
            kind: str) -> Tuple[object, str, int]:
    """(config, source, evals spent) for :func:`_resolve`."""
    registry = registry if registry is not None else default_registry()
    key = key + (registry.root if registry is not None else None,)
    with _lru_lock:
        hit = _config_lru.get(key)
        if hit is not None:
            _config_lru.move_to_end(key)
    if hit is not None:
        return hit, "lru_hits", 0

    fp = rec = None
    spent = 0
    if registry is not None:
        fp = fingerprint()
        rec = registry.get(fp)
    if rec is not None:
        b = rec.best
        cfg = make_config((b["bm"], b["bk"], b["bn"],
                           b.get("k_innermost", True)))
        registry.touch(fp)
        source = "disk_hits"
    else:
        extra: Tuple[BlockGenome, ...] = ()
        if registry is not None:
            extra = tuple((r.best["bm"], r.best["bk"], r.best["bn"],
                           r.best.get("k_innermost", True))
                          for _, r in registry.neighbors(fp, k=2))
        cfg, spent = tune(extra)
        source = "tuned"
        if registry is not None:
            from repro.registry import Record
            registry.put(Record(
                fingerprint=fp.digest, family=fp.family,
                features=list(fp.features), workload=fp.workload,
                kind=kind, hardware=TPU_V5E.name,
                best=_block_entry(_genome(cfg), model), pareto=[],
                evals=spent))
    with _lru_lock:
        _config_lru[key] = cfg
        _config_lru.move_to_end(key)
        while len(_config_lru) > _CONFIG_LRU_MAX:
            _config_lru.popitem(last=False)
    return cfg, source, spent


TUNER_COUNTERS = ("tuned", "disk_hits", "lru_hits", "evals")


def tuner_counts() -> Dict[str, int]:
    """The process's ``tuner.*`` counters so far (resolutions by source,
    and search evals spent): the difference of two readings counts what
    happened between them."""
    counters = get_metrics().counters
    return {c: int(counters.get("tuner." + c, 0)) for c in TUNER_COUNTERS}


def predicted_mfu(M: int, N: int, K: int, cfg: MatmulConfig,
                  dtype_bytes: int = 2) -> float:
    model = TpuMatmulModel(M=M, N=N, K=K, dtype_bytes=dtype_bytes)
    return model.mfu((cfg.bm, cfg.bk, cfg.bn, cfg.k_innermost))


def reset_config_lru() -> None:
    """Drop the in-process block-config LRU (not the disk registry).

    Lets tests and the pre-tune benchmark prove that a second resolution
    pass is served by the *persistent* registry rather than process
    memory."""
    with _lru_lock:
        _config_lru.clear()
    _tune_matmul_cached.cache_clear()
    _tune_gmm_cached.cache_clear()


# ---------------------------------------------------------------------- #
# Network-level pre-tune: resolve every GEMM a model will issue, upfront
# ---------------------------------------------------------------------- #
def pretune_gemms(shapes: Sequence[Tuple[int, int, int]],
                  registry=None, evals: int = 2000, seed: int = 0,
                  dtype_bytes: int = 2) -> Dict[str, int]:
    """Resolve a block config for every (M, N, K), warming LRU + registry.

    Returns resolution-source counts (``shapes``/``tuned``/
    ``disk_hits``/``lru_hits``) and the search ``evals`` spent, read
    from the ``tuner.*`` counters: a warm second pass over the same
    shapes against the same registry reports ``tuned == evals == 0`` —
    every config comes from the persistent store.
    """
    registry = registry if registry is not None else default_registry()
    before = tuner_counts()
    for (M, N, K) in shapes:
        resolve_matmul_config(M, N, K, dtype_bytes=dtype_bytes,
                              registry=registry, evals=evals, seed=seed)
    after = tuner_counts()
    return {"shapes": len(shapes),
            **{c: after[c] - before[c] for c in TUNER_COUNTERS}}


def pretune_model_config(mcfg, batch: int, prefill_len: int,
                         registry=None, evals: int = 2000,
                         decode_batch: Optional[int] = None
                         ) -> Dict[str, int]:
    """One network pass over a model config's whole GEMM graph.

    Builds the per-layer prefill+decode :class:`repro.network.LayerGraph`
    for ``mcfg`` and resolves every unique (M, N, K) block config, so a
    serving replica (``launch/serve.py --pretune``) starts with all of
    its matmul schedules decided before traffic arrives.
    """
    from repro.network.graph import model_config_graph
    graph = model_config_graph(mcfg, batch=batch, prefill_len=prefill_len,
                               decode_batch=decode_batch)
    return pretune_gemms(graph.gemm_shapes(), registry=registry,
                         evals=evals)
