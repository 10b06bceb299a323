"""The Pallas kernels at real widths, compiled for a described TPU v5e.

Nothing runs and no chip is needed: the TPU compiler installed with JAX
compiles for a v5e:2x2 topology described in a fixture, and refuses what
the chip would refuse -- a block off Mosaic's (8, 128) tiling, scoped VMEM
over its limit.  Interpret mode checks neither.  Each compiled program
must hold a ``tpu_custom_call``: the kernel went through Mosaic.

The topology is described only inside the module-scoped fixture, never
at import: one process at a time may load the TPU library, and every
test worker imports this file.
"""

import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.kernels import FlashConfig, MatmulConfig, SSDConfig, ops  # noqa: E402
from repro.kernels.autotune import (TpuMatmulModel, TpuMatmulProblem,  # noqa: E402
                                    tune_matmul)

# (M, N, K): non-divisor, square-large, a wide prefill GEMM, the
# smollm-135m MLP up-projection (prefill) and LM head (decode), and the
# starcoder2-7b prefill up, down and head projections (large blocks)
TUNED_SHAPES = [(1000, 1000, 1000), (4096, 4096, 4096), (8192, 1536, 576),
                (1024, 1536, 576), (4, 49152, 576), (4096, 18432, 4608),
                (4096, 4608, 18432), (4096, 49152, 4608)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("k_innermost", [True, False],
                         ids=["k_inner", "k_outer"])
@pytest.mark.parametrize("shape", TUNED_SHAPES,
                         ids=["x".join(map(str, s)) for s in TUNED_SHAPES])
def test_tuned_matmul_compiles(one_chip, shape, k_innermost):
    """The tuner's own winner, and the same blocks in the other k order
    (shrunk only where that order needs more VMEM than the limit)."""
    M, N, K = shape
    cfg = tune_matmul(M, N, K)
    g = TpuMatmulProblem(TpuMatmulModel(M=M, N=N, K=K)).legalize(
        (cfg.bm, cfg.bk, cfg.bn, k_innermost))
    mm = MatmulConfig(bm=g[0], bk=g[1], bn=g[2], k_innermost=g[3])
    _compile(lambda a, b: ops.matmul_op(a, b, mm), one_chip,
             ((M, K), jnp.bfloat16), ((K, N), jnp.bfloat16))


@pytest.mark.parametrize("seq", [512, 1000])
def test_flash_attention_compiles_at_smollm_widths(one_chip, seq):
    B, H, Hkv, D = 2, 9, 3, 64
    _compile(lambda q, k, v: ops.attention_op(q, k, v, causal=True,
                                              config=FlashConfig()),
             one_chip, ((B, H, seq, D), jnp.bfloat16),
             ((B, Hkv, seq, D), jnp.bfloat16),
             ((B, Hkv, seq, D), jnp.bfloat16))


def test_ssd_chunk_compiles_at_mamba2_widths(one_chip):
    L, H, P, N = 256, 24, 64, 128
    f32 = jnp.float32
    _compile(lambda x, a, b, c, h0: ops.ssd_chunk_op(x, a, b, c, h0,
                                                     config=SSDConfig()),
             one_chip, ((L, H, P), f32), ((L, H), f32), ((L, H, N), f32),
             ((L, H, N), f32), ((H, N, P), f32))


def test_conv2d_im2col_compiles_on_a_vgg16_layer(one_chip):
    ci, co, h, w, p, q = 256, 256, 56, 56, 3, 3
    cfg = tune_matmul(h * w, co, p * q * ci)
    _compile(lambda x, wt: ops.conv2d_op(x, wt, cfg), one_chip,
             ((1, h + p - 1, w + q - 1, ci), jnp.bfloat16),
             ((p, q, ci, co), jnp.bfloat16))


def test_gemm_classes_carry_their_names(one_chip):
    """Each tuned GEMM's custom call is the HLO instruction named for its
    class: the op name a device trace shows (``.<n>`` aside)."""
    one = MatmulConfig(bm=256, bk=512, bn=256)
    two = MatmulConfig(bm=128, bk=256, bn=512, k_innermost=False)

    def step(a, b, b2, c):
        return [ops.matmul_op(a, w, one) for w in (b, b2)] + \
            [ops.matmul_op(a, c, two)]
    text = _compile(step, one_chip, ((512, 1024), jnp.bfloat16),
                    ((1024, 512), jnp.bfloat16), ((1024, 512), jnp.bfloat16),
                    ((1024, 1024), jnp.bfloat16))
    calls = [line.split(" = ")[0].split()[-1].lstrip("%")
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(re.sub(r"\.\d+$", "", c) for c in calls) == [
        "matmul_512x1024x1024_128x256x512_ko",
        "matmul_512x512x1024_256x512x256_ki",
        "matmul_512x512x1024_256x512x256_ki"]
