"""Grouped (ragged) Pallas TPU matmul: the routed experts of a MoE layer.

``gmm(lhs, rhs, group_sizes)`` multiplies rows sorted by group (expert)
with each group's own weight::

    out[r] = lhs[r] @ rhs[g(r)]     for the rows r of group g

``lhs`` is ``(R, K)``, ``rhs`` ``(E, K, N)`` and ``group_sizes`` ``(E,)``
int32 on the device: the groups are consecutive runs of rows, in order,
and only their sizes are known, at run time.

The grid runs over (n-block, tile visit, k-block), k innermost as in
``matmul.py``'s k-inner kernel, with its f32 accumulator, ``legal_blocks``
and ``vmem_limit_bytes``.  A *tile visit* is one (group, m-tile) pair: a
scalar-prefetched table gives each grid index its group and its m-tile
(:func:`group_metadata`).  A tile that straddles a group boundary is
visited once per group it holds; each visit multiplies the whole tile by
its group's weight and stores only that group's rows, so the padding a
boundary costs is one extra tile.  An empty group has no visit and its
weight is never read.  The number of visits is a run-time grid bound.
The n-blocks are outermost so that the visits of one output tile are
consecutive grid steps: Pallas keeps an output block in VMEM while its
block index stays the same, and the visits of a straddled tile fill it
row range by row range before it is written back.

Rows past ``sum(group_sizes)`` are left undefined.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .matmul import _mask_k, vmem_bytes, vmem_limit_bytes


@dataclasses.dataclass(frozen=True)
class GmmConfig:
    bm: int = 128
    bk: int = 128
    bn: int = 128
    interpret: bool = False     # CPU validation mode


def kernel_name(R: int, N: int, K: int, E: int, bm: int, bk: int,
                bn: int) -> str:
    """``gmm_{R}x{N}x{K}_g{E}_{bm}x{bk}x{bn}``: the name the kernel's
    custom call carries in the HLO and in a device trace."""
    return f"gmm_{R}x{N}x{K}_g{E}_{bm}x{bk}x{bn}"


def max_tile_visits(R: int, E: int, bm: int) -> int:
    """Most (group, m-tile) visits ``R`` rows in ``E`` groups can need:
    every tile once, plus one for each group that starts inside a tile."""
    return pl.cdiv(R, bm) + E - 1


def group_metadata(group_sizes: jax.Array, R: int, bm: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``(offsets, group_ids, tile_ids, visits)`` for ``bm``-row tiles.

    ``offsets`` (E + 1,): group g holds rows [offsets[g], offsets[g+1]).
    Grid index t < ``visits`` works on group ``group_ids[t]`` in m-tile
    ``tile_ids[t]``; groups and tiles both ascend, so the visits of one
    tile are consecutive.  Entries past ``visits`` are padding."""
    E = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // bm
    tiles = jnp.where(sizes > 0, (ends - 1) // bm - first + 1, 0)
    total = max_tile_visits(R, E, bm)
    group_ids = jnp.repeat(jnp.arange(E, dtype=jnp.int32), tiles,
                           total_repeat_length=total)
    base = jnp.cumsum(tiles) - tiles       # first grid index of each group
    t = jnp.arange(total, dtype=jnp.int32)
    tile_ids = jnp.clip(first[group_ids] + t - base[group_ids], 0,
                        pl.cdiv(R, bm) - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return offsets, group_ids, tile_ids, jnp.sum(tiles)


def _kernel(offsets, group_ids, tile_ids, a_ref, b_ref, o_ref, acc_ref, *,
            bm: int, bk: int, K: int, mask: bool):
    t, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a, b = a_ref[...], b_ref[...]
    if mask:
        a, b = _mask_k(a, b, k, bk, K)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        g = group_ids[t]
        rows = tile_ids[t] * bm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
        o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype),
                               o_ref[...])


def _gmm_pallas(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                config: GmmConfig) -> jax.Array:
    R, K = lhs.shape
    E, K2, N = rhs.shape
    assert K == K2, (lhs.shape, rhs.shape)
    bm, bk, bn = min(config.bm, R), min(config.bk, K), min(config.bn, N)
    gn, gk = pl.cdiv(N, bn), pl.cdiv(K, bk)
    mask = (K % bk) != 0
    offsets, group_ids, tile_ids, visits = group_metadata(group_sizes, R,
                                                          bm)
    need = vmem_bytes(bm, bk, bn, lhs.dtype.itemsize, True, mask)
    kern = functools.partial(_kernel, bm=bm, bk=bk, K=K, mask=mask)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(gn, visits, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda n, t, k, o, g, m: (m[t], k)),
            pl.BlockSpec((None, bk, bn),
                         lambda n, t, k, o, g, m: (g[t], k, n))],
        out_specs=pl.BlockSpec((bm, bn), lambda n, t, k, o, g, m: (m[t], n)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)])
    # the scope names the custom call's HLO instruction, which is the
    # op's name in a device trace
    with jax.named_scope(kernel_name(R, N, K, E, bm, bk, bn)):
        return pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((R, N), lhs.dtype),
            interpret=config.interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=vmem_limit_bytes(need)),
        )(offsets, group_ids, tile_ids, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
        config: GmmConfig = GmmConfig()) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[g(r)]`` through the Pallas kernel, in the
    dtype of ``lhs``.  Differentiable: the backward pass is that of
    ``jax.lax.ragged_dot`` on the same operands."""
    return _gmm_pallas(lhs, rhs, group_sizes, config)


def _gmm_fwd(lhs, rhs, group_sizes, config):
    return _gmm_pallas(lhs, rhs, group_sizes, config), (lhs, rhs,
                                                        group_sizes)


def _gmm_bwd(config, res, g):
    lhs, rhs, group_sizes = res
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(
        a, b, group_sizes, preferred_element_type=jnp.float32
    ).astype(lhs.dtype), lhs, rhs)
    d_lhs, d_rhs = vjp(g)
    return d_lhs, d_rhs, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)
