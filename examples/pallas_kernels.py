"""The TPU kernel layer: tuned Pallas matmul + flash attention + SSD chunk,
validated against their jnp oracles, with the Odyssey autotuner choosing
the block shapes (the paper's technique on TPU).  The kernels lower
through Mosaic on a TPU and run in the Pallas interpreter on the CPU.

    PYTHONPATH=src python examples/pallas_kernels.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (FlashConfig, MatmulConfig, SSDConfig,
                           flash_attention, matmul, ops, ref, ssd_chunk)
from repro.kernels.autotune import TpuMatmulModel, tune_matmul

# 1. Odyssey picks the Pallas block shapes for an awkward (non-power-of-2)
#    matmul — non-divisor blocks are first-class, exactly like the paper's
#    non-divisor tiling factors.
M, N, K = 1000, 1000, 1000
cfg = tune_matmul(M, N, K)
model = TpuMatmulModel(M, N, K)
print(f"tuned blocks for {M}x{N}x{K}: bm={cfg.bm} bk={cfg.bk} bn={cfg.bn} "
      f"k_innermost={cfg.k_innermost}")
print(f"  modeled MFU: {model.mfu((cfg.bm, cfg.bk, cfg.bn, cfg.k_innermost)):.3f} "
      f"(naive 128^3 blocks: {model.mfu((128, 128, 128, True)):.3f})")

# 2. run it (interpret mode on CPU; Mosaic on TPU) on a small instance;
#    blocks obey Mosaic's (8, 128) rule or span the whole dim
interpret = ops.interpret_default()
a = jax.random.normal(jax.random.key(0), (130, 70), jnp.float32)
b = jax.random.normal(jax.random.key(1), (70, 90), jnp.float32)
got = matmul(a, b, MatmulConfig(bm=32, bk=128, bn=128,
                                interpret=interpret))
err = float(jnp.abs(got - ref.matmul(a, b)).max())
print(f"pallas matmul vs oracle: max err {err:.2e}")

# 3. flash attention with GQA + non-divisor lengths
q = jax.random.normal(jax.random.key(2), (2, 6, 33, 32)) * 0.5
k = jax.random.normal(jax.random.key(3), (2, 3, 77, 32)) * 0.5
v = jax.random.normal(jax.random.key(4), (2, 3, 77, 32))
o = flash_attention(q, k, v, causal=True,
                    config=FlashConfig(bq=32, bkv=32, interpret=interpret))
err = float(jnp.abs(o - ref.attention(q, k, v, causal=True)).max())
print(f"flash attention vs oracle: max err {err:.2e}")

# 4. Mamba2 SSD chunk kernel (the time-tiled state-space dual form)
L, H, P, Nst = 32, 4, 16, 8
x = jax.random.normal(jax.random.key(5), (L, H, P))
al = -jax.nn.softplus(jax.random.normal(jax.random.key(6), (L, H)))
bm = jax.random.normal(jax.random.key(7), (L, H, Nst)) * 0.3
cm = jax.random.normal(jax.random.key(8), (L, H, Nst)) * 0.3
y, hT = ssd_chunk(x, al, bm, cm, config=SSDConfig(interpret=interpret))
yw, hw = ref.ssd_chunk(x, al, bm, cm)
print(f"ssd chunk vs oracle: max err {float(jnp.abs(y - yw).max()):.2e}")
print("all kernels validated against their oracles.")
