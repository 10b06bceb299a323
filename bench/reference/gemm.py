"""Reference for the GEMM cells: (M, K) x (K, N) in float32."""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .precision import HIGHEST, key_for, rounded

Shape = Tuple[int, int, int]


def make_inputs(classes: Sequence[Tuple[Shape, int]], seed: int
                ) -> List[Tuple[jax.Array, List[jax.Array]]]:
    """Per class, one activation A (M, K) and one weight per occurrence
    (K, N), bfloat16, made on the device in one jitted call: each weight
    is its own buffer, as each layer's is in a model."""
    shapes = tuple((s, c) for s, c in classes)
    flat = _make(shapes, key_for(seed))
    out, i = [], 0
    for (M, N, K), count in shapes:
        out.append((flat[i], list(flat[i + 1:i + 1 + count])))
        i += 1 + count
    return out


@functools.partial(jax.jit, static_argnums=0)
def _make(shapes, key):
    arrays = []
    for ci, ((M, N, K), count) in enumerate(shapes):
        kc = jax.random.fold_in(key, ci)
        arrays.append(jax.random.normal(jax.random.fold_in(kc, 0), (M, K),
                                        jnp.bfloat16))
        for j in range(count):
            w = jax.random.normal(jax.random.fold_in(kc, j + 1), (K, N),
                                  jnp.float32) / jnp.sqrt(K)
            arrays.append(w.astype(jnp.bfloat16))
    return tuple(arrays)


@functools.partial(jax.jit, static_argnames="quant")
def matmul(a: jax.Array, b: jax.Array, quant: Optional[str] = None
           ) -> jax.Array:
    return jnp.dot(rounded(a, quant), rounded(b, quant), precision=HIGHEST)


@jax.jit
def row_rel_err(out: jax.Array, ref: jax.Array) -> jax.Array:
    """Largest relative error of a row: ||out_i - ref_i|| / ||ref_i||."""
    diff = jnp.linalg.norm(out.astype(jnp.float32) - ref, axis=1)
    norm = jnp.maximum(jnp.linalg.norm(ref, axis=1), 1e-30)
    return jnp.max(diff / norm)


def worst_row_error(pairs: Sequence[Tuple[jax.Array, jax.Array, jax.Array]],
                    quant: Optional[str] = None) -> Dict[str, float]:
    """Worst row error of each (a, b, out) against the float32 product.
    With ``quant``, ``out`` is ignored and the rounded reference is
    compared instead (the control)."""
    worst = 0.0
    for a, b, out in pairs:
        ref = matmul(a, b)
        got = matmul(a, b, quant) if quant else out
        worst = max(worst, float(row_rel_err(got, ref)))
        del ref, got
    return {"row_err": worst}
