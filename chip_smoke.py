#!/usr/bin/env python3
"""Run the main path of the system once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four chips: sharded training only

One process, the normal entry points, and no child processes.  Progress
goes to stdout; the last line is one JSON object naming the device:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failure exits non-zero with its cause on stderr and prints no such
line, and nothing runs at all unless JAX's default device is a TPU.

One-chip phases:

* device   -- the device kind has a profile in ``repro.core.hardware``;
* kernels  -- ``resolve_matmul_config`` tunes, against a fresh registry,
  the paper's MM 1024^3, the non-divisor 1000^3, 4096^3 and every GEMM
  shape class of smollm-135m serving (batch 4, prefill 256).  Each config
  runs through ``matmul_op`` on bf16 inputs in both k orders; then
  ``conv2d_op`` on a VGG16 layer, ``attention_op`` at smollm-135m widths
  and ``ssd_chunk_op`` at mamba2-130m widths.  Each compiled program must
  hold a ``tpu_custom_call`` (Mosaic ran, not the interpreter) and match
  its reference within the tolerances below;
* serve    -- smollm-135m at its published widths, served by the
  continuous engine, every request's tokens equal to the greedy argmax of
  one forward that uses no cache.  It runs in float32 under
  ``jax.default_matmul_precision("highest")``: with random weights the
  top logits of a 49152-word vocabulary are close enough that bf16
  rounding flips some argmaxes between the cached and cache-free paths;
* calib    -- ``measure_result(backend="measured")`` times the Pallas
  kernel of the Odyssey MM 1024^3 search winner.

``--four-chips`` trains smollm-135m at published widths for 5 steps of
the ``launch/train.py`` step on a 2x2 (data, model) mesh and on one
device, and compares the per-step losses.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import tempfile
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Relative Frobenius error ||out - ref|| / ||ref|| allowed per check.
# The matmul has exact bf16 products and f32 accumulation on both sides,
# so only the summation order differs (~1e-6); a stale partial sum or an
# unmasked edge block is off by O(1e-1).
MATMUL_RTOL = 1e-4
# Kernels whose output is bf16, or whose f32 dots the MXU may take in bf16
# passes: a few bf16 ulps (2^-8).
KERNEL_RTOL = 1e-2
# Per-step training loss, sharded mesh against one device: the same bf16
# model with reductions split across devices in another order.
LOSS_RTOL = 1e-2

MM_SHAPES = [(1024, 1024, 1024), (1000, 1000, 1000), (4096, 4096, 4096)]
VGG16_LAYER = (256, 256, 56, 56, 3, 3)          # (I, O, H, W, P, Q)


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def rel_err(out, ref) -> float:
    import numpy as np
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.all(np.isfinite(out)):
        return math.inf
    return float(np.linalg.norm(out - ref) / np.linalg.norm(ref))


def compile_on_mosaic(fn, *args):
    """``fn`` jit-compiled for ``args``; fails unless a Pallas kernel was
    lowered through Mosaic into the program."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "compiled program holds no tpu_custom_call: the Pallas kernel "
          "did not go through Mosaic")
    return compiled


def compare(name: str, out, ref, tol: float) -> None:
    check(out.shape == ref.shape,
          f"{name}: shape {out.shape}, reference {ref.shape}")
    err = rel_err(out, ref)
    log(f"  {name}: rel err {err:.3e} (tol {tol:g})")
    check(err <= tol, f"{name}: rel err {err:.3e} > {tol:g}")


# ---------------------------------------------------------------------- #
# one chip
# ---------------------------------------------------------------------- #
def phase_device():
    import jax
    from repro.core.hardware import profile_for_device
    dev = jax.devices()[0]
    hw = profile_for_device(dev.device_kind)
    log(f"device: {dev.platform} {dev.device_kind!r} x{len(jax.devices())}"
        f" -> profile {hw.name}")
    return hw


def _matmul_case(M: int, N: int, K: int, cfg, key) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (M, K), jnp.bfloat16)
    b = jax.random.normal(kb, (K, N), jnp.bfloat16)
    ref = jax.jit(lambda x, y: jnp.dot(
        x.astype(jnp.float32), y.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))(a, b)
    t0 = time.perf_counter()
    run = compile_on_mosaic(
        lambda x, y: ops.matmul_op(x, y, cfg, out_dtype=jnp.float32), a, b)
    compile_s = time.perf_counter() - t0
    order = "k-inner" if cfg.k_innermost else "k-outer"
    log(f"  mm {M}x{N}x{K} blocks ({cfg.bm},{cfg.bk},{cfg.bn}) {order}: "
        f"compiled in {compile_s:.2f}s")
    compare(f"mm {M}x{N}x{K} {order}", run(a, b), ref, MATMUL_RTOL)


def phase_kernels(registry_root: str) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels import ops, ref
    from repro.kernels.autotune import (TpuMatmulModel, TpuMatmulProblem,
                                        resolve_matmul_config)
    from repro.kernels.matmul import MatmulConfig, legal_blocks
    from repro.network.graph import model_config_graph
    from repro.registry import RegistryStore

    store = RegistryStore(registry_root)
    smollm = get_config("smollm-135m")
    shapes = MM_SHAPES + model_config_graph(
        smollm, batch=4, prefill_len=256).gemm_shapes()
    log(f"kernels: {len(shapes)} matmul shapes, registry {registry_root}")
    key = jax.random.key(0)
    for M, N, K in shapes:
        cfg = resolve_matmul_config(M, N, K, dtype_bytes=2, registry=store)
        problem = TpuMatmulProblem(TpuMatmulModel(M=M, N=N, K=K))
        g = (cfg.bm, cfg.bk, cfg.bn, cfg.k_innermost)
        check(legal_blocks(cfg.bm, cfg.bk, cfg.bn, M, K, N) == g[:3]
              and problem.model.feasible(g),
              f"tuner returned an illegal config {g} for {M}x{N}x{K}")
        # the other k order, shrunk only if its VMEM need does not fit
        flip = problem.legalize(g[:3] + (not cfg.k_innermost,))
        for bm, bk, bn, k_inner in (g, flip):
            key, sub = jax.random.split(key)
            _matmul_case(M, N, K, MatmulConfig(bm=bm, bk=bk, bn=bn,
                                               k_innermost=k_inner), sub)

    hp = jax.default_matmul_precision("highest")
    ci, co, h, w, p, q = VGG16_LAYER
    cfg = resolve_matmul_config(h * w, co, p * q * ci, dtype_bytes=2,
                                registry=store)
    kx, kw, key = jax.random.split(key, 3)
    x = jax.random.normal(kx, (1, h + p - 1, w + q - 1, ci), jnp.bfloat16)
    wt = (jax.random.normal(kw, (p, q, ci, co), jnp.float32)
          / math.sqrt(p * q * ci)).astype(jnp.bfloat16)
    run = compile_on_mosaic(lambda a, b: ops.conv2d_op(a, b, cfg), x, wt)
    with hp:
        want = ref.conv2d(x.astype(jnp.float32), wt.astype(jnp.float32))
    compare(f"conv2d vgg16 {VGG16_LAYER} blocks ({cfg.bm},{cfg.bk},"
            f"{cfg.bn})", run(x, wt), want, KERNEL_RTOL)

    H, Hkv, D = smollm.num_heads, smollm.num_kv_heads, smollm.hd
    for S in (512, 1000):
        kq, kk, kv, key = jax.random.split(key, 4)
        qa = jax.random.normal(kq, (2, H, S, D), jnp.bfloat16)
        ka = jax.random.normal(kk, (2, Hkv, S, D), jnp.bfloat16)
        va = jax.random.normal(kv, (2, Hkv, S, D), jnp.bfloat16)
        run = compile_on_mosaic(
            lambda a, b, c: ops.attention_op(a, b, c, causal=True),
            qa, ka, va)
        with hp:
            want = ref.attention(qa.astype(jnp.float32),
                                 ka.astype(jnp.float32),
                                 va.astype(jnp.float32), causal=True)
        compare(f"attention H={H} Hkv={Hkv} D={D} S={S} causal",
                run(qa, ka, va), want, KERNEL_RTOL)

    mamba = get_config("mamba2-130m")
    L, P, N = mamba.ssm_chunk, mamba.ssm_head_dim, mamba.ssm_state
    Hs = mamba.d_model * mamba.ssm_expand // P
    ks = jax.random.split(key, 6)
    xs = jax.random.normal(ks[0], (L, Hs, P), jnp.float32)
    al = -jax.nn.softplus(jax.random.normal(ks[1], (L, Hs), jnp.float32))
    bs = jax.random.normal(ks[2], (L, Hs, N), jnp.float32) / math.sqrt(N)
    cs = jax.random.normal(ks[3], (L, Hs, N), jnp.float32) / math.sqrt(N)
    h0 = jax.random.normal(ks[4], (Hs, N, P), jnp.float32)
    run = compile_on_mosaic(ops.ssd_chunk_op, xs, al, bs, cs, h0)
    y, hT = run(xs, al, bs, cs, h0)
    with hp:
        y_ref, hT_ref = ref.ssd_chunk(xs, al, bs, cs, h0)
    name = f"ssd_chunk L={L} H={Hs} P={P} N={N}"
    compare(name + " y", y, y_ref, KERNEL_RTOL)
    compare(name + " state", hT, hT_ref, KERNEL_RTOL)


def phase_serve() -> None:
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import ServeConfig, greedy_mismatches, make_engine
    from repro.serve.sim import poisson_requests

    cfg = dataclasses.replace(get_config("smollm-135m"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    eng = make_engine("continuous", model, params,
                      ServeConfig(max_batch=4, max_seq=256))
    reqs = poisson_requests(8, rate_rps=0.0, vocab_size=cfg.vocab_size,
                            prompt_len=range(2, 8), max_new_tokens=16,
                            seed=0)
    log(f"serve: {cfg.name} published widths, float32, continuous engine, "
        f"{len(reqs)} requests x 16 tokens")
    with jax.default_matmul_precision("highest"):
        outs, stats = eng.serve(reqs)
        log("  " + stats.summary())
        check([len(o) for o in outs] == [16] * len(reqs),
              f"served lengths {[len(o) for o in outs]}, wanted 16 each")
        bad = greedy_mismatches(model, params, reqs, outs)
    check(not bad, "served tokens differ from the cache-free reference: "
          + "; ".join(bad))
    log(f"  all {len(reqs)} requests match the cache-free reference")


def phase_calib() -> None:
    from repro.calib import MeasureConfig, measure_result
    from repro.core.hardware import U250
    from repro.core.tuner import tune_workload
    from repro.core.workloads import matmul

    wl = matmul(1024, 1024, 1024)
    best = tune_workload(wl, hw=U250).best
    m = measure_result(wl, best, U250, MeasureConfig(backend="measured"))
    log(f"calib: {wl.name} winner {m.design}: {m.detail}, "
        f"measured {m.measured_us:.1f} us ({m.backend}), model "
        f"{m.predicted_us:.1f} us")
    check(m.backend == "measured", f"calibration backend {m.backend!r}")
    check(math.isfinite(m.measured_us) and m.measured_us > 0,
          f"measured time {m.measured_us}")


# ---------------------------------------------------------------------- #
# four chips
# ---------------------------------------------------------------------- #
def _train_losses(model, cfg, mesh, steps: int, seq_len: int,
                  global_batch: int):
    import jax
    import jax.numpy as jnp
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.train import fresh_train_state
    from repro.parallel.sharding import axis_rules, default_rules
    from repro.train import AdamWConfig, build_train_step

    opt = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=steps,
                      state_dtype=cfg.optimizer_state_dtype)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=seq_len,
                                  global_batch=global_batch, seed=0))
    rules = default_rules(mesh)
    with mesh, axis_rules(rules):
        state = fresh_train_state(model, opt, rules)
        leaves = jax.tree.leaves(state["params"])
        total = sum(x.nbytes for x in leaves)
        per_dev = {d: 0 for d in mesh.devices.flat}
        for x in leaves:
            for s in x.addressable_shards:
                per_dev[s.device] += s.data.nbytes
        log(f"  mesh {dict(mesh.shape)}: params {total} bytes in all")
        for d, n in per_dev.items():
            stats = d.memory_stats() or {}
            log(f"    device {d.id}: params {n} bytes, "
                f"bytes_in_use {stats.get('bytes_in_use', 'n/a')}")
        if len(per_dev) > 1:
            check(max(per_dev.values()) < total,
                  "parameters are not spread across the mesh")
        step_fn = jax.jit(build_train_step(model, opt))
        losses = []
        for i in range(steps):
            batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
    log(f"  losses {losses}")
    return losses


def phase_four_chips() -> None:
    import jax
    from jax.sharding import AxisType
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.models import build_model

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    cfg = get_config("smollm-135m")
    model = build_model(cfg)
    log(f"train: {cfg.name} published widths, seq 256, global batch 8, "
        "5 steps")
    sharded = _train_losses(model, cfg, make_local_mesh(model_parallel=2),
                            steps=5, seq_len=256, global_batch=8)
    one = jax.make_mesh((1, 1), ("data", "model"), devices=devs[:1],
                        axis_types=(AxisType.Auto,) * 2)
    single = _train_losses(model, cfg, one, steps=5, seq_len=256,
                           global_batch=8)
    for i, (a, b) in enumerate(zip(sharded, single)):
        d = abs(a - b) / abs(b)
        log(f"  step {i + 1}: 2x2 {a:.6f} vs 1 device {b:.6f} "
            f"(rel diff {d:.2e}, tol {LOSS_RTOL:g})")
        check(math.isfinite(a) and d <= LOSS_RTOL,
              f"step {i + 1}: loss {a} on 2x2 vs {b} on one device")


# ---------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded training on a 2x2 mesh against "
                         "one device")
    args = ap.parse_args(argv)
    try:
        sys.path.insert(0, str(SRC))
        from repro.launch.compile_cache import enable_compile_cache
        import jax
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            raise SmokeFailure(
                f"no TPU: JAX's default device is {dev.platform!r} "
                f"({dev.device_kind}); this check runs only on the chip")
        log(f"compile cache: {enable_compile_cache()}")
        t0 = time.perf_counter()
        if args.four_chips:
            phase_four_chips()
        else:
            phase_device()
            with tempfile.TemporaryDirectory(prefix="chip-smoke-reg-") as d:
                phase_kernels(d)
            phase_serve()
            phase_calib()
        log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    except Exception as e:  # report the cause, never a result line
        traceback.print_exc()
        print(f"[chip_smoke] FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
