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


def _cdiv(x: int, m: int) -> int:
    return -(-x // m)


def _where(cond, a, b):
    """``np.where`` that keeps Python scalars scalar: the search prices
    one genome at a time at least as often as a population."""
    if isinstance(cond, np.ndarray):
        return np.where(cond, a, b)
    return a if cond else b


@dataclasses.dataclass
class TpuMatmulModel:
    """Analytic latency/VMEM model of the Pallas matmul on one TPU core."""

    M: int
    N: int
    K: int
    dtype_bytes: int = 2
    hw: HardwareProfile = TPU_V5E

    # Each cost term is written once, over Python ints or NumPy integer
    # arrays of the block dims and bools (or bool arrays) of k_inner:
    # the scalar methods and ``fitness_batch`` run the same arithmetic,
    # so a genome is priced bit for bit alike either way.
    def _grid(self, bm, bk, bn):
        return _cdiv(self.M, bm), _cdiv(self.N, bn), _cdiv(self.K, bk)

    def _compute_s(self, bm, bk, bn):
        # MXU granularity: sublane 8 on M, lane 128 on K/N
        flops = 2 * _up(bm, 8) * _up(bk, 128) * _up(bn, 128)
        return flops / self.hw.flops_peak

    def _dma_s(self, bm, bk, bn, k_inner):
        gk = self._grid(bm, bk, bn)[2]
        bytes_in = (bm * bk + bk * bn) * self.dtype_bytes
        # C written once per (m, n) block, amortized over the k sweep; the
        # k-outer partial sums' round trip is waited (``step_cost_s``)
        bytes_out = _where(k_inner, bm * bn * self.dtype_bytes / gk, 0.0)
        t = (bytes_in + bytes_out) / self.hw.hbm_bw
        return t + self.hw.dma_overhead_cycles / self.hw.freq_hz

    def _pipeline_s(self, bm, bk, bn, k_inner):
        gm, gn, gk = self._grid(bm, bk, bn)
        tc = self._compute_s(bm, bk, bn)
        td = self._dma_s(bm, bk, bn, k_inner)
        prologue, steady = td, _where(tc > td, tc, td)
        epilogue = (bm * bn * self.dtype_bytes) / self.hw.hbm_bw
        return prologue + tc + (gm * gn * gk - 1) * steady + epilogue

    def _step_cost_s(self, bm, bk, bn, k_inner):
        gm, gn, gk = self._grid(bm, bk, bn)
        acc = bm * bn * 4
        extra = _where(k_inner,
                       gm * gn * (gk - 1) * acc * ACC_RMW_S_PER_BYTE,
                       gm * gn * gk * (2 * acc / self.hw.hbm_bw))
        return gm * gn * gk * GRID_STEP_S + extra

    def _latency_s(self, bm, bk, bn, k_inner):
        return self._pipeline_s(bm, bk, bn, k_inner) \
            + self._step_cost_s(bm, bk, bn, k_inner)

    def _vmem_bytes(self, bm, bk, bn, k_inner):
        return vmem_bytes(bm, bk, bn, self.dtype_bytes, k_inner,
                          masked=self.K % bk != 0)

    def _fitness(self, bm, bk, bn, k_inner):
        lat = self._latency_s(bm, bk, bn, k_inner)
        v = vmem_limit_bytes(self._vmem_bytes(bm, bk, bn, k_inner))
        return -_where(v > VMEM_LIMIT_MAX,
                       lat * _quartic(v / VMEM_LIMIT_MAX), lat)

    def grid(self, g: BlockGenome) -> Tuple[int, int, int]:
        gm, gn, gk = self._grid(*g[:3])
        return int(gm), int(gn), int(gk)

    def vmem_bytes(self, g: BlockGenome) -> int:
        return self._vmem_bytes(*g)

    def vmem_limit_bytes(self, g: BlockGenome) -> int:
        """The scoped-VMEM limit ``matmul`` passes to Mosaic for ``g``."""
        return vmem_limit_bytes(self.vmem_bytes(g))

    def feasible(self, g: BlockGenome) -> bool:
        return self.vmem_limit_bytes(g) <= VMEM_LIMIT_MAX

    def block_compute_s(self, g: BlockGenome) -> float:
        return float(self._compute_s(*g[:3]))

    def block_dma_s(self, g: BlockGenome) -> float:
        return float(self._dma_s(*g))

    def pipeline_s(self, g: BlockGenome) -> float:
        """The paper's double-buffered pipeline: prologue + steady-state
        max(compute, DMA) per block + epilogue."""
        return float(self._pipeline_s(*g))

    def step_cost_s(self, g: BlockGenome) -> float:
        """What the grid steps cost beyond the pipeline's max(compute,
        DMA): a fixed cost per step, and the f32 accumulator's update,
        in VMEM on each k-inner step after the first, or as the k-outer
        partial sum's waited round trip through HBM on every step."""
        return float(self._step_cost_s(*g))

    def latency_s(self, g: BlockGenome) -> float:
        return float(self._latency_s(*g))

    def fitness(self, g: BlockGenome) -> float:
        """Minus the latency, times the quartic penalty of a VMEM limit
        over ``VMEM_LIMIT_MAX``."""
        return float(self._fitness(*g))

    def mfu(self, g: BlockGenome) -> float:
        useful = 2 * self.M * self.N * self.K
        return useful / self.hw.flops_peak / self.latency_s(g)

    def fitness_batch(self, genomes: Sequence[BlockGenome]) -> np.ndarray:
        """``fitness`` over a whole population at once (the interface of
        ``BatchPerformanceModel``)."""
        return self._fitness(*(np.array(c) for c in zip(*genomes)))


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
    and step costs per tile.  ``visits`` charges the partial tiles at
    group boundaries at their full cost: one extra tile for every group
    but the first, at most one per row.

    The expert's weight is charged as the kernel fetches it.  Pallas
    copies a block only when its index differs from the step before's,
    and the weight's index is ``(group, k, n)``: with a block that holds
    the whole K (one k-step, :meth:`weight_per_group`) the visits of one
    group keep it, so each n-block's sweep fetches a group's weight once,
    at the switch to that group, one step ahead of its first visit
    (:meth:`per_group_s`).  With several k-steps the index changes every
    step and each visit reads the weight (:meth:`per_visit_s`)."""

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

    def weight_per_group(self, g: BlockGenome) -> bool:
        """Whether a group's weight block is fetched once per group
        switch, not once per visit: the block holds the whole K."""
        return g[1] >= self.K

    def per_visit_s(self, g: BlockGenome) -> float:
        """Every visit reads its weight blocks: the tiles' dense model."""
        mm = self.tiles_model(g)
        return mm.pipeline_s(g) + mm.step_cost_s(g)

    def per_group_s(self, g: BlockGenome) -> float:
        """One k-step a visit: each visit pays max(compute, rows in and
        out), and each of the ``gn * min(E, R)`` group switches the part
        of its rows' and weight's fetch, issued one step ahead, that the
        step before's compute cannot hide."""
        bm, bk, bn, _ = g
        mm = self.tiles_model(g)
        _, gn, _ = mm.grid(g)
        steps = gn * self.visits(bm)
        switches = gn * min(self.E, self.R)
        tc = mm.block_compute_s(g)
        td_w = bk * bn * self.dtype_bytes / self.hw.hbm_bw
        td_rows = mm.block_dma_s(g) - td_w
        exposed = max(0.0, td_rows + td_w - tc)
        prologue = td_rows + td_w
        epilogue = bm * bn * self.dtype_bytes / self.hw.hbm_bw
        return (prologue + steps * max(tc, td_rows)
                + (switches - 1) * exposed + epilogue + mm.step_cost_s(g))

    def latency_s(self, g: BlockGenome) -> float:
        if self.weight_per_group(g):
            return self.per_group_s(g)
        return self.per_visit_s(g)

    def fitness(self, g: BlockGenome) -> float:
        lat = self.latency_s(g)
        v = self.tiles_model(g).vmem_limit_bytes(g)
        if v > VMEM_LIMIT_MAX:
            lat *= _quartic(v / VMEM_LIMIT_MAX)
        return -lat

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
    ``kind="gmm"`` and ``E``, and of the pick its tile ``visits`` and
    ``weight_per_group`` (whether the weight was charged once a group
    switch); the counters are the matmul's."""
    def fingerprint():
        from repro.registry import gmm_block_fingerprint
        return gmm_block_fingerprint(R, N, K, E, dtype_bytes, TPU_V5E)

    def tune(extra):
        return _tune_gmm_cached(R, N, K, E, dtype_bytes, evals, seed, extra)

    model = TpuGmmModel(R=R, N=N, K=K, E=E, dtype_bytes=dtype_bytes)
    return _resolve(("gmm", R, N, K, E, dtype_bytes), registry, model,
                    lambda g: GmmConfig(bm=g[0], bk=g[1], bn=g[2]),
                    fingerprint, tune, "tpu_gmm_block",
                    dict(kind="gmm", M=R, N=N, K=K, E=E),
                    lambda g: dict(visits=model.visits(g[0]),
                                   weight_per_group=model.weight_per_group(g)))


def _resolve(key: Tuple, registry, model, make_config, fingerprint, tune,
             kind: str, span_args: Dict, pick_args=None):
    """The config for ``key``: LRU -> disk registry -> warm-started tune,
    under a ``tuner.resolve`` span, counted in ``obs.Metrics``.
    ``make_config`` builds a config from a genome; ``fingerprint()`` is
    the registry's key; ``tune(seeds)`` searches, warm-started from
    neighbours' genomes (legalized by the search); ``kind`` names the
    records; ``pick_args(genome)``, if given, adds the pick's own
    attributes to the span."""
    t0 = time.perf_counter()
    with get_tracer().span("tuner.resolve", "tuner", **span_args) as span:
        cfg, source, spent = _lookup(key, registry, model, make_config,
                                     fingerprint, tune, kind)
        g = _genome(cfg)
        gm, gn, gk = model.grid(g)
        span.set(source=source, evals=spent, steps=gm * gn * gk,
                 **(pick_args(g) if pick_args else {}))
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
    for ``mcfg`` and resolves every unique (M, N, K) block config, as
    ``python -m repro.network --pretune`` does, so a later process
    against the same registry finds every matmul schedule decided.
    """
    from repro.network.graph import model_config_graph
    graph = model_config_graph(mcfg, batch=batch, prefill_len=prefill_len,
                               decode_batch=decode_batch)
    return pretune_gemms(graph.gemm_shapes(), registry=registry,
                         evals=evals)
