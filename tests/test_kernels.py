"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode executes the Pallas kernel bodies on CPU)."""

import numpy as np
import pytest

pytest.importorskip("jax")
pytest.importorskip("hypothesis")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import (FlashConfig, MatmulConfig, SSDConfig,
                           flash_attention, matmul, ref, ssd_chunk)
from repro.kernels import ops
from repro.kernels.autotune import TpuMatmulModel, tune_matmul
from repro.kernels.matmul import VMEM_LIMIT_MAX


# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("mnk", [(128, 128, 128), (130, 70, 50),
                                 (257, 129, 65), (64, 192, 300), (8, 8, 8)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k_inner", [True, False])
def test_matmul_vs_ref(mnk, dt, k_inner):
    M, N, K = mnk
    a = jax.random.normal(jax.random.key(0), (M, K), dt)
    b = jax.random.normal(jax.random.key(1), (K, N), dt)
    cfg = MatmulConfig(bm=32, bk=32, bn=32, k_innermost=k_inner,
                       interpret=True)
    got = np.asarray(matmul(a, b, cfg, out_dtype=jnp.float32))
    want = np.asarray(ref.matmul(a, b, out_dtype=jnp.float32))
    tol = 2e-5 if dt == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * K)


@given(st.integers(1, 150), st.integers(1, 150), st.integers(1, 150),
       st.sampled_from([8, 16, 32, 48]))
@settings(max_examples=12, deadline=None)
def test_matmul_property_shapes(M, N, K, blk):
    """Non-divisor block shapes are first-class: any (M, N, K)."""
    a = jax.random.normal(jax.random.key(2), (M, K), jnp.float32)
    b = jax.random.normal(jax.random.key(3), (K, N), jnp.float32)
    cfg = MatmulConfig(bm=blk, bk=blk, bn=blk, interpret=True)
    got = np.asarray(matmul(a, b, cfg))
    np.testing.assert_allclose(got, np.asarray(a @ b), rtol=3e-5,
                               atol=3e-5 * max(K, 1))


# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("dims", [(2, 4, 4, 64, 64, 32),
                                  (1, 8, 2, 100, 100, 64),
                                  (2, 6, 3, 33, 77, 32),
                                  (1, 2, 1, 1, 96, 32)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_vs_ref(dims, causal):
    B, H, Hkv, S, T, D = dims
    q = jax.random.normal(jax.random.key(0), (B, H, S, D)) * 0.5
    k = jax.random.normal(jax.random.key(1), (B, Hkv, T, D)) * 0.5
    v = jax.random.normal(jax.random.key(2), (B, Hkv, T, D))
    got = flash_attention(q, k, v, causal=causal,
                          config=FlashConfig(bq=32, bkv=32, interpret=True))
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_block_invariance():
    B, H, S, D = 1, 2, 96, 32
    q = jax.random.normal(jax.random.key(0), (B, H, S, D))
    k = jax.random.normal(jax.random.key(1), (B, H, S, D))
    v = jax.random.normal(jax.random.key(2), (B, H, S, D))
    outs = [flash_attention(q, k, v, causal=True,
                            config=FlashConfig(bq=bq, bkv=bkv,
                                               interpret=True))
            for bq, bkv in [(32, 32), (96, 48), (16, 96)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------- #
def test_conv2d_vs_ref():
    x = jax.random.normal(jax.random.key(0), (2, 12, 12, 8))
    w = jax.random.normal(jax.random.key(1), (3, 3, 8, 16))
    got = ops.conv2d_op(x, w, config=MatmulConfig(bm=32, bk=32, bn=16,
                                                  interpret=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.conv2d(x, w)),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("dims", [(32, 4, 16, 8), (17, 2, 8, 4),
                                  (64, 1, 32, 16)])
def test_ssd_chunk_vs_ref(dims):
    L, H, P, N = dims
    x = jax.random.normal(jax.random.key(0), (L, H, P))
    a = -jax.nn.softplus(jax.random.normal(jax.random.key(1), (L, H)))
    b = jax.random.normal(jax.random.key(2), (L, H, N)) * 0.3
    c = jax.random.normal(jax.random.key(3), (L, H, N)) * 0.3
    h0 = jax.random.normal(jax.random.key(4), (H, N, P)) * 0.2
    y, ht = ssd_chunk(x, a, b, c, h0, config=SSDConfig(interpret=True))
    yw, htw = ref.ssd_chunk(x, a, b, c, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yw),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(ht), np.asarray(htw),
                               rtol=2e-4, atol=2e-4)


def test_ssd_chunk_chaining():
    """Two chained chunks == one double chunk (state handoff correct)."""
    L, H, P, N = 32, 2, 8, 4
    x = jax.random.normal(jax.random.key(0), (2 * L, H, P))
    a = -jax.nn.softplus(jax.random.normal(jax.random.key(1), (2 * L, H)))
    b = jax.random.normal(jax.random.key(2), (2 * L, H, N)) * 0.3
    c = jax.random.normal(jax.random.key(3), (2 * L, H, N)) * 0.3
    cfg = SSDConfig(interpret=True)
    y_full, ht_full = ssd_chunk(x, a, b, c, config=cfg)
    y1, h1 = ssd_chunk(x[:L], a[:L], b[:L], c[:L], config=cfg)
    y2, h2 = ssd_chunk(x[L:], a[L:], b[L:], c[L:], h0=h1, config=cfg)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2])),
                               np.asarray(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(ht_full),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------- #
def test_autotuner_prefers_k_inner_and_fits_vmem():
    cfg = tune_matmul(2048, 2048, 2048)
    assert cfg.k_innermost        # Theorem 3.1 on TPU
    model = TpuMatmulModel(2048, 2048, 2048)
    g = (cfg.bm, cfg.bk, cfg.bn, cfg.k_innermost)
    assert model.vmem_limit_bytes(g) <= VMEM_LIMIT_MAX
    assert model.mfu(g) > 0.5


def test_autotuner_model_k_outer_penalty():
    """The dominated grid order pays for HBM partial-spills."""
    m = TpuMatmulModel(1024, 1024, 1024)
    g_in = (256, 256, 256, True)
    g_out = (256, 256, 256, False)
    assert m.latency_s(g_out) > m.latency_s(g_in)


def _steps(model, g):
    gm, gn, gk = model.grid(g)
    return gm * gn * gk


def test_autotuner_charges_grid_steps_at_prefill():
    """Each grid step's fixed cost moves a compute-bound GEMM (the
    starcoder2-7b up projection at M 4096) off thousands of short
    steps, within the VMEM cap."""
    model = TpuMatmulModel(4096, 18432, 4608)
    cfg = tune_matmul(4096, 18432, 4608)
    g = (cfg.bm, cfg.bk, cfg.bn, cfg.k_innermost)
    short = (512, 384, 1152, True)        # the pick before steps were priced
    assert 8 * _steps(model, g) <= _steps(model, short)
    assert model.vmem_limit_bytes(g) <= VMEM_LIMIT_MAX
    assert model.latency_s(g) < model.latency_s(short)


def test_autotuner_keeps_decode_steps_few():
    """A byte-bound skinny GEMM (M 16) already runs few steps."""
    model = TpuMatmulModel(16, 18432, 4608)
    cfg = tune_matmul(16, 18432, 4608)
    assert _steps(model, (cfg.bm, cfg.bk, cfg.bn, cfg.k_innermost)) <= 16
