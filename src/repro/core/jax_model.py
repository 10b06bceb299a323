"""JAX port of the batched performance model (DESIGN.md §3, "JAX engine").

:class:`JaxBatchModel` compiles the whole fitness pipeline of
:class:`~repro.core.perf_model.BatchPerformanceModel` — tile bytes, DMA
transfer cycles, carry-depth steady state, resources and the smooth
overuse penalty — into **one fused jitted function** over the ``[B, L]``
level matrices.  The NumPy model remains the numeric oracle: the port
replicates its operation order exactly (same integer products, same
float64 divisions and ceils, same accumulation order), so on CPU the
returned fitness is bit-identical in practice and is asserted to
``rtol=1e-12`` (the documented tolerance — XLA is permitted to fuse
elementwise chains, which may perturb the last ulp on some backends).

Dtype policy (the 4096³ overflow guard, mirrored from the NumPy path):

* every call runs under ``jax.enable_x64`` — without it JAX
  lowers the int64 genome matrices to int32, and the band prefix
  products alone reach ~7e10 at 4096³ scale (int32 wraps at 2.1e9);
* integer arithmetic stays int64 exactly where the NumPy path is int64
  (tile elements, prefix products, resource counts);
* cycle/traffic *products* that can outgrow int64 are promoted to
  float64 **before** the multiply, exactly like the NumPy path:
  ``compute_cycles * num_tiles`` (max-model latency) and the off-chip
  ``events * tile_bytes`` traffic.

The x64 mode is scoped to the context manager, so importing this module
never flips process-global JAX config — Pallas kernels and the serving
stack keep their float32 defaults.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64

from .perf_model import BatchPerformanceModel, _quartic

__all__ = ["JaxBatchModel", "build_fitness_fn"]

_I8 = np.int64
_F8 = np.float64


def _colprod(mat, cols: Sequence[int]):
    """Chained column product (identical op order to the NumPy model)."""
    if not cols:
        return jnp.ones(mat.shape[0], dtype=mat.dtype)
    out = mat[:, cols[0]]
    for c in cols[1:]:
        out = out * mat[:, c]
    return out


def build_fitness_fn(bm: BatchPerformanceModel):
    """A trace-compatible ``fitness(n0, n1, n2, use_max) -> [B] f64`` for
    the design behind ``bm``.

    The returned function is pure jnp arithmetic over the static design
    structure precomputed by :class:`BatchPerformanceModel` (band order,
    per-array subscript indices, carry-depth masks, loop roles) — it can
    be jitted standalone (:class:`JaxBatchModel`) or inlined into a
    larger compiled program (the ``jax_evolve`` generation step).
    ``use_max`` must be static at trace time.
    """
    hw = bm.hw
    desc = bm.desc
    arrays = bm._arrays
    band = bm._band
    space = bm._space
    par = bm._par
    red = bm._red
    simd_col = bm._simd
    # per-array window coefficients as static int64 constants
    coeff_consts = [[np.asarray(cs, dtype=_I8) for cs in a["coeffs"]]
                    for a in arrays]

    def tile_bytes(ai: int, t1):
        a = arrays[ai]
        elems = None
        for dim, cs in zip(a["dims"], coeff_consts[ai]):
            if len(dim) == 1 and cs[0] == 1:
                size = t1[:, dim[0]]
            else:
                size = ((t1[:, dim] - 1) * cs).sum(axis=1) + 1
            elems = size if elems is None else elems * size
        if elems is None:
            elems = jnp.ones(t1.shape[0], dtype=t1.dtype)
        return elems * desc.dtype_bytes

    def transfer(nbytes):
        return hw.dma_overhead_cycles + jnp.ceil(
            nbytes / hw.dram_bus_bytes)

    def events(ai: int, n0, prefix):
        a = arrays[ai]
        episodes = prefix[a["maxpos"]]
        if not a["is_output"]:
            return episodes, jnp.zeros_like(episodes)
        if not a["flow"]:
            return jnp.zeros_like(episodes), episodes
        fresh = episodes // _colprod(n0, a["flow"])
        return episodes - fresh, episodes

    def resources(n1, n2, t1, tb):
        pes = _colprod(n1, space)
        simd = n2[:, simd_col]
        lanes = pes * simd
        dsp = lanes * hw.dsp_per_lane
        port_brams = jnp.ceil(simd * desc.dtype_bytes * 8
                              / hw.bram_port_bits).astype(_I8)
        total_bram = jnp.zeros(n1.shape[0], dtype=_I8)
        for ai, a in enumerate(arrays):
            banks = jnp.maximum(1, _colprod(n1, a["bank_loops"]))
            bank_bytes = jnp.ceil(tb[ai] / banks)
            per_bank = jnp.maximum(
                port_brams,
                jnp.ceil(2 * bank_bytes / hw.bram_bytes).astype(_I8))
            n = 2 * banks * per_bank
            if a["needs_inbound_partials"]:
                n = n * 2
            total_bram += n
        acc_elems = _colprod(t1, par)
        acc_elems = jnp.ceil(acc_elems / jnp.maximum(1, pes)).astype(_I8)
        acc_bytes = acc_elems * desc.dtype_bytes
        pe_bram = jnp.where(
            acc_bytes <= 1024, 0,
            pes * jnp.ceil(2 * acc_bytes / hw.bram_bytes).astype(_I8))
        total_bram = total_bram + pe_bram
        lut = pes * hw.lut_per_pe + lanes * hw.lut_per_lane
        return dsp, total_bram, lut

    def compute_cycles(n1, n2, t1):
        pes = _colprod(n1, space)
        simd = n2[:, simd_col]
        p = _colprod(t1, par)
        par_per_pe = jnp.maximum(1, p // jnp.maximum(1, pes))
        r = jnp.ones(n1.shape[0], dtype=_I8)
        for j in red:
            t = t1[:, j]
            if j == simd_col:
                t = jnp.maximum(1, t // simd)
            r = r * t
        ii = jnp.where(r > 1,
                       jnp.maximum(par_per_pe, hw.mac_pipeline_depth),
                       par_per_pe)
        fill_drain = n1[:, space].sum(axis=1) + hw.mac_pipeline_depth
        return r * ii + fill_drain

    def fitness(n0, n1, n2, use_max: bool):
        t1 = n1 * n2
        B = n0.shape[0]
        tb = [tile_bytes(ai, t1) for ai in range(len(arrays))]
        xfer = [transfer(b) for b in tb]
        # band prefix products P_0..P_len(band) (int64 — the x64 policy)
        prefix = [jnp.ones(B, dtype=_I8)]
        for j in band:
            prefix.append(prefix[-1] * n0[:, j])

        c_tile = compute_cycles(n1, n2, t1)
        c_tile_f = c_tile.astype(_F8)

        prologue = jnp.zeros(B, dtype=_F8)
        epilogue = jnp.zeros(B, dtype=_F8)
        for a, x in zip(arrays, xfer):
            if a["is_output"]:
                epilogue += x
            else:
                prologue += x

        ev = [events(ai, n0, prefix)
              if use_max or (arrays[ai]["is_output"] and arrays[ai]["flow"])
              else None
              for ai in range(len(arrays))]

        steady = jnp.zeros(B, dtype=_F8)
        for p in range(1, len(band) + 1):
            n_p = prefix[p] - prefix[p - 1]
            dma = jnp.zeros(B, dtype=_F8)
            for ai, a in enumerate(arrays):
                if a["maxpos"] < p:
                    continue
                dma += xfer[ai]
                if a["is_output"] and a["flow"]:
                    load, store = ev[ai]
                    dma += (load / jnp.maximum(1, store)) * xfer[ai]
            step = jnp.maximum(c_tile_f, dma)
            steady += jnp.where(n_p > 0, n_p * step, 0.0)
        steady = steady + c_tile_f
        latency = (prologue + steady) + epilogue

        dsp, total_bram, lut = resources(n1, n2, t1, tb)

        num_tiles = prefix[-1]
        if use_max:
            dma_total = jnp.zeros(B, dtype=_F8)
            for ai in range(len(arrays)):
                load, store = ev[ai]
                dma_total += (load + store) * xfer[ai]
            # float64 promotion *before* the product — c_tile * num_tiles
            # outgrows int64 at large scale (the overflow guard)
            lat = jnp.maximum(c_tile_f * num_tiles.astype(_F8), dma_total)
        else:
            lat = latency
        penalty = jnp.where(dsp > hw.dsp_available,
                            _quartic(dsp / hw.dsp_available), 1.0)
        penalty = penalty * jnp.where(
            total_bram > hw.bram_available,
            _quartic(total_bram / hw.bram_available), 1.0)
        if hw.lut_available:
            penalty = penalty * jnp.where(
                lut > hw.lut_available,
                _quartic(lut / hw.lut_available), 1.0)
        return -lat * penalty

    fitness.resources = resources          # reused by jax_evolve / tests
    return fitness


class JaxBatchModel:
    """Jitted standalone entry points over a design's fitness pipeline.

    >>> jm = JaxBatchModel(batch_model)          # shares the statics
    >>> fit = jm.fitness_matrix(mat)             # np.float64 [B]

    One XLA computation per (batch size, use_max) pair; re-calls at the
    same shape hit the jit cache.  Inputs/outputs are plain NumPy arrays
    so callers never touch jax types.
    """

    def __init__(self, bm: BatchPerformanceModel):
        self.bm = bm
        self.hw = bm.hw
        self.desc = bm.desc
        self._fn = build_fitness_fn(bm)
        # the level split happens inside the trace: one [B, L, 3] device
        # transfer per call instead of three strided host copies
        self._jit = jax.jit(
            lambda mat, use_max: self._fn(
                mat[:, :, 0], mat[:, :, 1], mat[:, :, 2], use_max),
            static_argnames=("use_max",))

    def fitness_matrix(self, mat: np.ndarray,
                       use_max_model: bool = False) -> np.ndarray:
        """Fitness of a ``[B, L, 3]`` int64 population matrix."""
        with enable_x64(True):
            out = self._jit(mat, use_max=bool(use_max_model))
            return np.asarray(out)
