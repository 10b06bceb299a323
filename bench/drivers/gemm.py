"""GEMM window: every GEMM class of one model step through the tuned kernel.

Set-up runs the tuner (``kernels.autotune.resolve_matmul_config``) for
each shape class against a fresh registry in a temporary directory, makes
the inputs on the device from the seed (one activation per class, one
weight per occurrence), and compiles the pass.  A pass is one jitted
program, as a model step is: every class through ``ops.matmul_op`` with
its tuned blocks, as often as the step runs it.  (Dispatched call by call
from the host, the decode classes left the chip idle more than half the
time: the window then measured Python's dispatch, not the kernels.)
Each pass ends in ``block_until_ready``; the mix's ``in_flight`` passes
may be queued at once, so the host's dispatch of the next overlaps the
device's work on the last.  The window dispatches passes until
``--seconds`` have gone and ends when the last of them is done.  Afterwards a seeded sample of each
class's outputs from the last pass is checked against the float32
reference.

With ``--trace 1`` XLA's own dot runs on the same inputs after the
window, under a trace of its own, for ``pallas_over_xla``.
"""

from __future__ import annotations

import collections
import dataclasses
import tempfile
import time
from typing import Dict, List

from bench import counters, generate
from bench.reference import gemm as ref

XLA_PASSES = 3
CHECK_PER_CLASS = 2     # occurrences of each class checked after the window


def _pass(configs):
    """One step's GEMMs as one program, named ``bench_gemm``."""
    from repro.kernels import ops

    def bench_gemm(inputs):
        return [[ops.matmul_op(a, b, c) for b in bs]
                for (a, bs), c in zip(inputs, configs)]
    return bench_gemm


def bench_xla_dot(inputs):
    """The same GEMMs through XLA's own dot."""
    import jax.numpy as jnp
    return [[jnp.dot(a, b, preferred_element_type=jnp.float32)
             .astype(a.dtype) for b in bs] for a, bs in inputs]


def run(r) -> None:
    import jax
    from repro.kernels import ops
    from repro.kernels.autotune import TpuMatmulModel, resolve_matmul_config
    from repro.registry import RegistryStore

    cfg, mix = r.config, r.traffic
    rows = mix["batch"] * (mix["prefill_len"] if mix["stage"] == "prefill"
                           else 1)
    classes = counters.gemm_classes(cfg, rows)
    with tempfile.TemporaryDirectory(prefix="bench-registry-") as root:
        store = RegistryStore(root)
        t = time.perf_counter()
        configs = [resolve_matmul_config(M, N, K, registry=store)
                   for (M, N, K), _ in classes]
        tune_s = time.perf_counter() - t
    interpret = ops.interpret_default()
    configs = [dataclasses.replace(c, interpret=interpret) for c in configs]
    inputs = ref.make_inputs(classes, r.seed)
    step = jax.jit(_pass(configs))
    jax.block_until_ready(step(inputs))
    pick = generate.rng_for(r.seed, "check")
    keep = [sorted(pick.choice(count, min(count, CHECK_PER_CLASS),
                               replace=False).tolist())
            for _, count in classes]
    r.setup_done()

    # passes in flight: the next is dispatched before the last is waited
    # for, so the host's dispatch of a short pass overlaps the device
    in_flight = mix.get("in_flight", 1)
    queue: collections.deque = collections.deque()
    kept: List[Dict] = []

    def finish(outs) -> None:
        jax.block_until_ready(outs)
        kept[:] = [{j: outs[ci][j] for j in keep[ci]}
                   for ci in range(len(classes))]

    passes = 0
    r.open_window()
    while True:
        queue.append(step(inputs))
        if len(queue) < in_flight:
            continue
        finish(queue.popleft())
        passes += 1
        if r.elapsed() >= r.seconds:
            break
    while queue:
        finish(queue.popleft())
        passes += 1
    r.close_window()

    calls = sum(count for _, count in classes)
    flops = sum(counters.gemm_flops(s) * c for s, c in classes)
    r.attempted, r.failed = passes * calls, 0
    r.e2e["gemm_tflops"] = passes * flops / r.window_s / 1e12
    r.counts.update(
        tune_s=tune_s, passes=passes, pass_flops=flops,
        pass_min_s=sum(counters.gemm_min_seconds(s, r.peak) * c
                       for s, c in classes),
        pass_model_s=sum(
            TpuMatmulModel(M=M, N=N, K=K).latency_s(
                (g.bm, g.bk, g.bn, g.k_innermost)) * c
            for ((M, N, K), c), g in zip(classes, configs)))

    if r.trace:
        xla = jax.jit(bench_xla_dot)
        jax.block_until_ready(xla(inputs))

        def xla_passes():
            for _ in range(XLA_PASSES):
                jax.block_until_ready(xla(inputs))
        r.traced("xla", xla_passes)
        r.counts["xla_passes"] = XLA_PASSES

    triples = [(inputs[ci][0], inputs[ci][1][j], out)
               for ci, outs in enumerate(kept) for j, out in outs.items()]
    err = ref.worst_row_error(triples)["row_err"]
    if r.control:
        # the control stands in the program's place and is judged alike
        r.counts["program"] = {"row_err": err}
        err = ref.worst_row_error(triples, r.control)["row_err"]
    r.check("row_err", err, cfg["limits"]["row_err"])
