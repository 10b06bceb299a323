"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships with a jit'd wrapper (`ops`) and a pure-jnp oracle (`ref`);
tests sweep shapes/dtypes against the oracle in interpret mode.  Block shapes
are tuning parameters owned by the Odyssey autotuner (`autotune`).
"""

from .matmul import MatmulConfig, matmul
from .gmm import GmmConfig, gmm
from .flash_attention import FlashConfig, flash_attention
from .ssd import SSDConfig, ssd_chunk
from . import ops, ref, autotune

__all__ = ["MatmulConfig", "matmul", "GmmConfig", "gmm", "FlashConfig", "flash_attention",
           "SSDConfig", "ssd_chunk", "ops", "ref", "autotune"]
