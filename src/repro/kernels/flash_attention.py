"""Flash attention Pallas TPU kernel (online softmax, GQA-aware).

Grid: ``(batch, q_heads, q_blocks, kv_blocks)`` with the KV dim innermost —
the same ``<[i,j],k>`` accumulate-in-VMEM ordering the Odyssey analysis
selects for matmul (Theorem 3.1): running ``(m, l, acc)`` state lives in VMEM
scratch and each output block is written exactly once.  GQA is expressed in
the BlockSpec index maps (``h -> h // group``), not by materializing repeated
KV heads.  Block sizes ``(bq, bkv)`` are tuning parameters surfaced to the
Odyssey autotuner.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class FlashConfig:
    bq: int = 256
    bkv: int = 256
    interpret: bool = False


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bkv: int,
            q_len: int, kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (bkv, d)
    v = v_ref[0, 0].astype(jnp.float32)          # (bkv, d)

    if kv_len % bkv:
        # zero the padded KV rows: out-of-bounds block contents are
        # undefined and 0 * undefined would poison the PV accumulation
        vpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(vpos < kv_len, v, 0.0)
        k = jnp.where(vpos < kv_len, k, 0.0)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # kv-edge mask (non-divisor kv_len) and causal mask
    kv_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kv_pos < kv_len
    if causal:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        mask = mask & (kv_pos <= q_pos + (kv_len - q_len))
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (bq, 1)
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ki == pl.num_programs(3) - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: Optional[float] = None,
                    config: Optional[FlashConfig] = None) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, Hkv, T, D) with Hkv | H."""
    config = config or FlashConfig()
    B, H, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    group = H // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    bq, bkv = min(config.bq, S), min(config.bkv, T)
    grid = (B, H, pl.cdiv(S, bq), pl.cdiv(T, bkv))

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             bq=bq, bkv=bkv, q_len=S, kv_len=T)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((bq, D), jnp.float32),   # output accumulator
        ],
        interpret=config.interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
