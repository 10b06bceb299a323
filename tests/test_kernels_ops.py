"""Public kernel wrappers (`repro.kernels.ops`) — jit-cache semantics.

Separate from tests/test_kernels.py so this regression coverage does not
disappear when the optional `hypothesis` dependency is absent.
"""

import os
import pathlib
import subprocess
import sys

import pytest

pytest.importorskip("jax")  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops


def test_set_interpret_default_applies_after_first_call(monkeypatch):
    """Regression: the public wrappers once baked the interpret default
    into the first jit trace (``config=None`` was the static key), so a
    ``set_interpret_default()`` flip after the first call silently served
    the stale mode from the jit cache.  The resolved config must be the
    static key: each spy below must see the *live* default on every call."""
    seen = {"mm": [], "fa": [], "ssd": []}
    monkeypatch.setattr(ops, "matmul",
                        lambda a, b, config, out_dtype=None:
                        (seen["mm"].append(config.interpret), a @ b)[1])
    monkeypatch.setattr(ops, "flash_attention",
                        lambda q, k, v, causal=False, scale=None, config=None:
                        (seen["fa"].append(config.interpret), q)[1])
    monkeypatch.setattr(ops, "ssd_chunk",
                        lambda x, a, b, c, h0=None, config=None:
                        (seen["ssd"].append(config.interpret), x)[1])
    # odd shapes so no earlier test shares these jit cache keys
    a = jnp.ones((9, 7), jnp.float32)
    b = jnp.ones((7, 5), jnp.float32)
    q = jnp.ones((1, 2, 9, 8), jnp.float32)
    x = jnp.ones((1, 9, 2, 4), jnp.float32)
    aa = jnp.zeros((1, 9, 2), jnp.float32)
    bc = jnp.ones((1, 9, 3), jnp.float32)
    try:
        for flag in (True, False):
            ops.set_interpret_default(flag)
            ops.matmul_op(a, b)
            ops.attention_op(q, q, q)
            ops.ssd_chunk_op(x, aa, bc, bc)
    finally:
        ops.set_interpret_default(None)
        # the spy-traced entries must not leak into later tests
        ops._matmul_jit.clear_cache()
        ops._attention_jit.clear_cache()
        ops._ssd_chunk_jit.clear_cache()
    assert seen == {"mm": [True, False], "fa": [True, False],
                    "ssd": [True, False]}


def test_platform_decides_the_mode_at_first_use(monkeypatch):
    """No override: a TPU lowers through Mosaic, the CPU interprets, and
    any other platform is an error rather than a silent interpreter."""
    for platform, want in (("tpu", False), ("cpu", True)):
        monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
        assert ops.interpret_default() is want
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_default()


def test_importing_the_kernels_starts_no_jax_backend():
    """A spawned search worker imports these modules while the parent may
    hold the chip, and a TPU serves one process: importing must leave the
    JAX backend unstarted (it once chose the interpret mode at import)."""
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    code = ("import repro.kernels, repro.core.engine, repro.calib\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(src)] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
