"""Tunable Pallas TPU matmul — the "systolic array instance".

This kernel is the TPU realization of one Odyssey design point (DESIGN.md §2):

  * the BlockSpec block shape ``(bm, bk, bn)`` is the array-partitioning tile
    ``(T_I1, T_K1, T_J1)`` — **non-divisor** shapes are first-class: edge
    blocks are masked on the contraction dim (out-of-bounds regions of a
    Pallas block are undefined, so both operands are zeroed past ``K``) and
    out-of-bounds output rows/cols are dropped on store, which is exactly the
    paper's zero-padding semantics;
  * the grid iteration order is the array-partitioning **loop permutation**:
    ``k`` innermost (``<[i,j],k>``) accumulates in a VMEM scratch and writes
    each output block once, while ``k`` outermost (``<[k],[i,j]>``-style)
    revisits output blocks and round-trips the f32 partial sums through HBM
    on every step — the Theorem 3.1 "dominated ordering", implemented so the
    benchmark can measure its cost on TPU as the paper did on FPGA;
  * the MXU plays the role of the fixed 128x128 PE array.  Mosaic accepts a
    block whose last two dims are multiples of (8, 128) or equal to the
    array's dims; :func:`legal_blocks` is that rule, and the autotuner only
    draws genomes that satisfy it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANE, LANE = 8, 128      # Mosaic's (second-minor, minor) block granule
MiB = 1 << 20
# Mosaic's default scoped-VMEM limit (16 MiB on v5e) refuses large tuned
# blocks, so every call passes an explicit limit sized to its block.  The
# cap stays below the chip's 128 MiB of VMEM for Mosaic's own scratch; it
# is also the autotuner's feasibility bound.
VMEM_LIMIT_MAX = 100 * MiB


@dataclasses.dataclass(frozen=True)
class MatmulConfig:
    bm: int = 128
    bk: int = 128
    bn: int = 128
    k_innermost: bool = True    # loop-permutation choice (Theorem 3.1)
    interpret: bool = False     # CPU validation mode


def legal_block(b: int, dim: int, align: int) -> int:
    """Nearest block size to ``b`` that Mosaic accepts on a dim of ``dim``:
    a positive multiple of ``align`` below ``dim``, or ``dim`` itself."""
    if b >= dim:
        return dim
    b = max(align, ((b + align // 2) // align) * align)
    return dim if b >= dim else b


def legal_blocks(bm: int, bk: int, bn: int, M: int, K: int, N: int
                 ) -> Tuple[int, int, int]:
    """``(bm, bk, bn)`` snapped to Mosaic's block rule for (M,K)x(K,N).

    ``bm`` is the second-minor dim of the A and output blocks (multiple
    of 8), ``bk`` the minor dim of A and ``bn`` that of B and the output
    (multiples of 128); any of them may instead span the whole dim.
    Tiles that do not divide the problem dims stay legal."""
    return (legal_block(bm, M, SUBLANE), legal_block(bk, K, LANE),
            legal_block(bn, N, LANE))


def vmem_bytes(bm: int, bk: int, bn: int, dtype_bytes: int,
               k_innermost: bool = True, masked: bool = True) -> int:
    """VMEM one grid step holds: double-buffered A/B blocks, the output
    block (double-buffered for k-inner; k-outer streams it through HBM),
    the f32 accumulator, the f32 ``dot`` temporary, and the masked copies
    of the operands on a ragged contraction edge.  Plain arithmetic, so
    the autotuner applies it to whole populations of NumPy arrays."""
    ab = (bm * bk + bk * bn) * dtype_bytes
    acc = bm * bn * 4
    out = 2 * bm * bn * dtype_bytes * k_innermost
    return 2 * ab + out + 2 * acc + ab * masked


def vmem_limit_bytes(need: int) -> int:
    """Scoped-VMEM limit passed to Mosaic for a kernel needing ``need``
    bytes: the need plus a quarter and 4 MiB for Mosaic's own scratch.
    The autotuner treats a block as feasible iff this is at most
    :data:`VMEM_LIMIT_MAX`."""
    return need + need // 4 + 4 * MiB


def _mask_k(a, b, k_idx, bk, K):
    """Zero both operands past the true contraction bound (edge blocks)."""
    kk = k_idx * bk
    ka = kk + jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    kb = kk + jax.lax.broadcasted_iota(jnp.int32, b.shape, 0)
    return (jnp.where(ka < K, a, jnp.zeros_like(a)),
            jnp.where(kb < K, b, jnp.zeros_like(b)))


def _kernel_k_inner(a_ref, b_ref, o_ref, acc_ref, *, bk: int, K: int,
                    mask: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a, b = a_ref[...], b_ref[...]
    if mask:
        a, b = _mask_k(a, b, k, bk, K)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_k_outer(a_ref, b_ref, c_hbm, acc_ref, sem, *, bm: int, bn: int,
                    bk: int, K: int, mask: bool):
    """Dominated ordering: k is the outermost grid dim, so each output
    block is revisited across k steps with every other block in between.
    The f32 partial sum of block (i, j) lives in HBM (``c_hbm``) and is
    read back and written out around each step — exactly the extra C(in)
    traffic of the paper's Fig. 3 second design.  The copies are explicit
    and waited on: the Pallas pipeline never loads an output block back
    from HBM, so a revisited pipelined output block would hold stale data.
    ``acc_ref`` is the block padded to whole (8, 128) tiles, the unit a
    copy moves, and ``c_hbm`` holds a grid of such tiles; only its top-left
    ``(bm, bn)`` corner is the result.
    """
    k, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    a, b = a_ref[...], b_ref[...]
    if mask:
        a, b = _mask_k(a, b, k, bk, K)
    part = jnp.dot(a, b, preferred_element_type=jnp.float32)
    bmp, bnp = acc_ref.shape
    blk = c_hbm.at[pl.ds(pl.multiple_of(i * bmp, SUBLANE), bmp),
                   pl.ds(pl.multiple_of(j * bnp, LANE), bnp)]

    @pl.when(k == 0)
    def _first():
        acc_ref[:bm, :bn] = part

    @pl.when(k > 0)
    def _acc():
        load = pltpu.make_async_copy(blk, acc_ref, sem)
        load.start()
        load.wait()
        acc_ref[:bm, :bn] += part

    store = pltpu.make_async_copy(acc_ref, blk, sem)
    store.start()
    store.wait()


def resolve_config(M: int, N: int, K: int,
                   dtype_bytes: int = 2, registry=None) -> MatmulConfig:
    """Tuned block shape for (M, N, K) from the design registry.

    In-memory LRU in front of the on-disk store; a miss tunes (warm-
    started from the nearest cached matmul) and records the winner so
    other processes sharing the registry root skip the search entirely.
    """
    from .autotune import resolve_matmul_config
    return resolve_matmul_config(M, N, K, dtype_bytes, registry=registry)


def matmul(a: jax.Array, b: jax.Array,
           config: Optional[MatmulConfig] = None,
           out_dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """``a @ b`` via the tunable Pallas kernel.  Any (M, K) x (K, N).

    ``config="auto"`` resolves the block shape at call time through the
    design registry (see :func:`resolve_config`); ``None`` keeps the
    static default.
    """
    M, K = a.shape
    K2, N = b.shape
    if isinstance(config, str):
        if config != "auto":
            raise ValueError(f"unknown config {config!r}; "
                             "expected a MatmulConfig, None or 'auto'")
        config = resolve_config(M, N, K, dtype_bytes=a.dtype.itemsize)
    config = config or MatmulConfig()
    assert K == K2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    bm, bk, bn = (min(config.bm, M), min(config.bk, K), min(config.bn, N))
    gm, gn, gk = pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk)
    mask = (K % bk) != 0
    need = vmem_bytes(bm, bk, bn, a.dtype.itemsize, config.k_innermost,
                      mask)

    if config.k_innermost:
        kern = functools.partial(_kernel_k_inner, bk=bk, K=K, mask=mask)
        grid = (gm, gn, gk)
        in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                    pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))]
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((M, N), out_dtype)
        scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
        dims = ("parallel", "parallel", "arbitrary")
    else:
        kern = functools.partial(_kernel_k_outer, bm=bm, bn=bn, bk=bk, K=K,
                                 mask=mask)
        grid = (gk, gm, gn)
        in_specs = [pl.BlockSpec((bm, bk), lambda k, i, j: (i, k)),
                    pl.BlockSpec((bk, bn), lambda k, i, j: (k, j))]
        # f32 partial sums in HBM, one tile-padded block per grid cell so
        # every copy is aligned and in bounds; cropped and cast below
        bmp, bnp = -(-bm // SUBLANE) * SUBLANE, -(-bn // LANE) * LANE
        out_spec = pl.BlockSpec(memory_space=pl.ANY)
        out_shape = jax.ShapeDtypeStruct((gm * bmp, gn * bnp), jnp.float32)
        scratch = [pltpu.VMEM((bmp, bnp), jnp.float32),
                   pltpu.SemaphoreType.DMA]
        # every step reads back what an earlier step wrote: keep the
        # grid sequential
        dims = ("arbitrary", "arbitrary", "arbitrary")

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=config.interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=dims,
            vmem_limit_bytes=vmem_limit_bytes(need)),
    )(a, b)
    if not config.k_innermost:
        out = out.reshape(gm, bmp, gn, bnp)[:, :bm, :, :bn]
        out = out.reshape(gm * bm, gn * bn)[:M, :N].astype(out_dtype)
    return out
