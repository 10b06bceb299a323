"""Operand rounding for the references and their low-precision control."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any seed >= 0, wider than 32 bits included."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def rounded(x: jax.Array, quant: Optional[str]) -> jax.Array:
    """``x`` in float32, rounded first to ``quant`` when it is given.

    ``"fp8"``: scaled per tensor so its largest magnitude maps to e4m3's
    largest, rounded to float8_e4m3fn, and scaled back: what an fp8
    product with float32 accumulation sees."""
    x = x.astype(jnp.float32)
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, E4M3_MAX / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale

