"""Jit'd public wrappers for the Pallas kernels.

A config left as ``None`` takes the mode of the platform, decided at the
first call (never at import, so importing the kernels starts no JAX
backend): a TPU lowers the kernels through Mosaic, the CPU runs them in
the Pallas interpreter, and any other platform is an error.
``set_interpret_default`` overrides that choice for tests.

``conv2d`` lowers convolution to im2col + the tunable matmul kernel — on TPU
the MXU *is* the systolic array, so conv shares the tuned MM design exactly
as AutoSA maps both workloads onto the same array generator.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.obs import install_compile_listener

from . import ref
from .flash_attention import FlashConfig, flash_attention
from .gmm import GmmConfig, gmm
from .matmul import MatmulConfig, matmul
from .ssd import SSDConfig, ssd_chunk

install_compile_listener()      # before the kernels' first jit

_interpret_override: Optional[bool] = None


def set_interpret_default(value: Optional[bool]) -> None:
    """Force interpret mode on or off; ``None`` returns the choice to the
    platform."""
    global _interpret_override
    _interpret_override = value


def interpret_default() -> bool:
    """Whether a config left as ``None`` runs in the Pallas interpreter."""
    if _interpret_override is not None:
        return _interpret_override
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"no kernel path for platform {platform!r}: the Pallas kernels "
        "lower through Mosaic on a TPU or run interpreted on the CPU")


def _mm_cfg(config: Optional[MatmulConfig]) -> MatmulConfig:
    return config or MatmulConfig(interpret=interpret_default())


def _gmm_cfg(config: Optional[GmmConfig]) -> GmmConfig:
    return config or GmmConfig(interpret=interpret_default())


def _fa_cfg(config: Optional[FlashConfig]) -> FlashConfig:
    return config or FlashConfig(interpret=interpret_default())


def _ssd_cfg(config: Optional[SSDConfig]) -> SSDConfig:
    return config or SSDConfig(interpret=interpret_default())


# The public wrappers resolve the interpret default *outside* jit: the
# resolved (frozen, hashable) config is the static jit key, so a
# ``set_interpret_default()`` flip after the first call retraces instead
# of silently serving the stale mode from the jit cache (a ``config=None``
# static key would pin whatever mode held at first trace).

@functools.partial(jax.jit, static_argnames=("config", "out_dtype"))
def _matmul_jit(a, b, config: MatmulConfig, out_dtype):
    return matmul(a, b, config, out_dtype=out_dtype)


def matmul_op(a: jax.Array, b: jax.Array,
              config: Optional[MatmulConfig] = None,
              out_dtype=None) -> jax.Array:
    return _matmul_jit(a, b, _mm_cfg(config), out_dtype)


@functools.partial(jax.jit, static_argnames=("config",))
def _gmm_jit(lhs, rhs, group_sizes, config: GmmConfig):
    return gmm(lhs, rhs, group_sizes, config)


def gmm_op(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
           config: Optional[GmmConfig] = None) -> jax.Array:
    """Grouped matmul: rows of ``lhs`` (R, K) sorted by group, each
    group's rows times its own ``rhs`` (E, K, N); ``group_sizes`` (E,)
    int32 on the device."""
    return _gmm_jit(lhs, rhs, group_sizes, _gmm_cfg(config))


@functools.partial(jax.jit, static_argnames=("causal", "scale", "config"))
def _attention_jit(q, k, v, causal: bool, scale: Optional[float],
                   config: FlashConfig):
    return flash_attention(q, k, v, causal=causal, scale=scale, config=config)


def attention_op(q: jax.Array, k: jax.Array, v: jax.Array,
                 causal: bool = False, scale: Optional[float] = None,
                 config: Optional[FlashConfig] = None) -> jax.Array:
    return _attention_jit(q, k, v, causal, scale, _fa_cfg(config))


@functools.partial(jax.jit, static_argnames=("config",))
def _conv2d_jit(x: jax.Array, w: jax.Array,
                config: MatmulConfig) -> jax.Array:
    N, H, W, Ci = x.shape
    P, Q, _, Co = w.shape
    Ho, Wo = H - P + 1, W - Q + 1
    # im2col: gather P*Q shifted views -> (N*Ho*Wo, P*Q*Ci)
    cols = []
    for p in range(P):
        for q in range(Q):
            cols.append(jax.lax.dynamic_slice(
                x, (0, p, q, 0), (N, Ho, Wo, Ci)))
    patches = jnp.stack(cols, axis=3).reshape(N * Ho * Wo, P * Q * Ci)
    wmat = w.reshape(P * Q * Ci, Co)
    out = matmul(patches, wmat, config)
    return out.reshape(N, Ho, Wo, Co)


def conv2d_op(x: jax.Array, w: jax.Array,
              config: Optional[MatmulConfig] = None) -> jax.Array:
    """VALID conv via im2col + the tunable Pallas matmul.

    x: (N, H, W, Ci); w: (P, Q, Ci, Co) -> (N, H-P+1, W-Q+1, Co).
    """
    return _conv2d_jit(x, w, _mm_cfg(config))


@functools.partial(jax.jit, static_argnames=("config",))
def _ssd_chunk_jit(x, a, b, c, h0, config: SSDConfig):
    return ssd_chunk(x, a, b, c, h0=h0, config=config)


def ssd_chunk_op(x, a, b, c, h0=None, config: Optional[SSDConfig] = None):
    return _ssd_chunk_jit(x, a, b, c, h0, _ssd_cfg(config))


__all__ = ["matmul_op", "gmm_op", "attention_op", "conv2d_op",
           "ssd_chunk_op", "MatmulConfig", "GmmConfig", "FlashConfig",
           "SSDConfig", "ref",
           "set_interpret_default", "interpret_default"]
