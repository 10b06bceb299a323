"""What a grid step of the k-inner matmul costs, measured on a TPU.

    PYTHONPATH=src python -m benchmarks.step_cost [--out PATH]
    PYTHONPATH=src python -m benchmarks.step_cost --gmm [--out PATH]

Runs every block shape of :data:`SWEEP` on its GEMM class through
``ops.matmul_op`` and reads each kernel's device time per call from a
profiler trace (the kernel's op carries ``matmul.kernel_name``).  It then
fits, by least squares over the compute-bound blocks (``tc >= td``), what
the measured time holds beyond ``TpuMatmulModel.pipeline_s``:

* a fixed cost per grid step (``autotune.GRID_STEP_S``);
* a cost per byte of the f32 accumulator read and written back on each
  k-inner step after the first (``autotune.ACC_RMW_S_PER_BYTE``).

A term the fit gives no positive weight is dropped and the rest refit.
The byte-bound blocks (decode, M 16) are left out of the fit: what they
lose is HBM's shortfall from its peak rate, which no per-step term
carries; they are printed beside the model's prediction as a check.
Needs a TPU.  Each row is printed with its measured time, the pipeline
and the fit; the rows and the fit go to ``--out``, and the last stdout
line is the fit as JSON.

``--gmm`` runs the grouped kernel (``ops.gmm_op``) instead, at the
blocks of :data:`GMM_SWEEP`, on the group sizes of the MoE cell
``mellum2-12b-a2.5b.moe-prefill`` at seed 0: each of its layers' tokens
routed by the program's own router, as ``bench/drivers/moe_gemm.py``
routes them (its 32768 prefill rows a layer; and the decode class, the
96 rows of one token from each of 12 sequences).  Each row is a block's
mean device time per call over the layers, beside ``TpuGmmModel``'s
charge of the weight per visit (:meth:`~TpuGmmModel.per_visit_s`, the
model before it charged it per group) and its own ``latency_s``; the
fit is of what the measured time holds beyond ``latency_s`` on the
grid steps and the group switches, over the compute-bound rows.
"""

from __future__ import annotations

import argparse
import json
import statistics
import tempfile
from typing import Dict, List, Tuple

import numpy as np

# (M, N, K) -> k-inner blocks (bm, bk, bn): the five starcoder2-7b GEMM
# classes at prefill (M 4096), from thousands of steps to a few dozen and
# VMEM limits from 9 to 94 MiB, and the up and down classes at decode (M 16)
SWEEP: Dict[Tuple[int, int, int], List[Tuple[int, int, int]]] = {
    (4096, 18432, 4608): [
        (512, 384, 1152), (512, 256, 512), (1024, 256, 768),
        (512, 512, 512), (1024, 512, 1024), (1024, 1024, 1024),
        (2048, 256, 2048), (2048, 512, 2048), (2048, 768, 2048),
        (1024, 1152, 2304), (2048, 1536, 2048), (1024, 2304, 2048),
        (2048, 2304, 1536), (1024, 4608, 1024), (512, 4608, 2048),
        (1024, 4608, 1280), (1024, 4608, 1152), (1536, 4608, 1024),
        (2048, 2304, 1024), (2048, 1536, 1536), (1024, 3072, 1536),
        (2048, 4608, 512), (1536, 2304, 1536), (1024, 2304, 2304),
        (2048, 1152, 2048)],
    (4096, 4608, 18432): [
        (1024, 384, 512), (512, 256, 512), (1024, 256, 768),
        (1024, 1024, 1024), (2048, 256, 2304), (2048, 512, 2304),
        (2048, 1024, 1152), (2048, 1536, 1536), (2048, 2048, 1536),
        (1024, 2048, 2304), (512, 4608, 1152), (1024, 4608, 1536),
        (2048, 4608, 768), (1024, 3072, 1536), (1024, 6144, 768),
        (2048, 1536, 1152), (1536, 1536, 1536), (1024, 1536, 1536),
        (2048, 1152, 1536), (2048, 2048, 1152), (1024, 4608, 1152)],
    (4096, 49152, 4608): [
        (1024, 256, 768), (1024, 1152, 2304), (2048, 768, 2048),
        (1024, 2304, 2048), (1024, 4608, 1024), (512, 4608, 2048),
        (1024, 4608, 1536)],
    (4096, 4608, 4608): [
        (512, 384, 1152), (1024, 2304, 1152), (1368, 2304, 768),
        (2048, 1152, 1152), (1024, 4608, 1024), (1024, 4608, 1152)],
    (4096, 512, 4608): [
        (1024, 512, 512), (688, 1536, 512), (1024, 1152, 512),
        (2048, 1152, 512), (4096, 1152, 512)],
    (16, 18432, 4608): [
        (16, 1536, 9216), (16, 4608, 2048), (16, 1152, 4608),
        (16, 512, 2048)],
    (16, 4608, 18432): [
        (16, 3072, 4608), (16, 1024, 4608), (16, 4608, 1152)],
}


# (R, N, K) -> grouped blocks (bm, bk, bn), whole K and whole N: the
# two classes of Mellum2's expert layer (E 64) at the MoE cell's prefill
# rows, and at 12 decode slots
GMM_CELL = ("mellum2-12b-a2.5b", "moe-prefill")
GMM_DECODE_TOKENS = 12
GMM_SWEEP: Dict[Tuple[int, int, int], List[Tuple[int, int, int]]] = {
    (32768, 896, 2304): [(bm, 2304, 896)
                         for bm in (64, 96, 128, 192, 256, 320, 424)],
    (32768, 2304, 896): [(bm, 896, 2304)
                         for bm in (64, 96, 128, 192, 256, 320, 424)],
    (96, 896, 2304): [(bm, 2304, 896) for bm in (16, 32, 40, 48, 96)],
    (96, 2304, 896): [(bm, 896, 2304) for bm in (16, 32, 40, 48, 96)],
}

CALLS = 5       # timed calls of each block; the median is its time
GMM_CALLS = 3   # timed calls of each block on each layer's groups

# the fit's terms -> the ``autotune`` constant each sets
TERMS = {"steps": "GRID_STEP_S", "acc_rmw_bytes": "ACC_RMW_S_PER_BYTE"}
# the grouped fit's terms -> what each would cost the grouped model: a
# grid step, a group switch (per n-block)
GMM_TERMS = {"steps": "gmm_step_s", "switches": "gmm_switch_s"}


def features(M: int, N: int, K: int, blocks: Tuple[int, int, int]
             ) -> Dict[str, int]:
    """The fit's terms for a k-inner call: its grid steps, and the bytes
    of the f32 accumulator read and written back on the k steps after
    the first."""
    from repro.kernels.autotune import TpuMatmulModel
    bm, bk, bn = blocks
    gm, gn, gk = TpuMatmulModel(M=M, N=N, K=K).grid((bm, bk, bn, True))
    return {"steps": gm * gn * gk,
            "acc_rmw_bytes": gm * gn * (gk - 1) * bm * bn * 4}


def fit(rows: List[Dict], base: str = "pipeline_us",
        names: Dict[str, str] = TERMS) -> Dict[str, float]:
    """Least squares of the time beyond ``base`` on the terms ``names``,
    over the compute-bound rows; a term fitted at or below zero is
    dropped and the rest refit."""
    rows = [r for r in rows if r["compute_bound"]]
    y = np.array([r["measured_us"] - r[base] for r in rows]) * 1e-6
    terms = list(names)
    while True:
        X = np.array([[r[t] for t in terms] for r in rows], float)
        coef, *_ = np.linalg.lstsq(X, y, rcond=None)
        if len(terms) == 1 or coef.min() > 0:
            break
        del terms[int(coef.argmin())]
    constants = {c: 0.0 for c in names.values()}
    constants.update({names[t]: max(float(c), 0.0)
                      for t, c in zip(terms, coef)})
    return constants


def measure() -> List[Dict]:
    import jax
    import jax.numpy as jnp
    from bench import trace
    from repro.kernels import ops
    from repro.kernels.autotune import TpuMatmulModel
    from repro.kernels.matmul import MatmulConfig, kernel_name

    if jax.default_backend() != "tpu":
        raise SystemExit("step_cost needs a TPU: device times only")
    key = jax.random.key(0)
    cases = []
    for (M, N, K), blocks in SWEEP.items():
        ka, kb, key = jax.random.split(key, 3)
        a = jax.random.normal(ka, (M, K), jnp.bfloat16)
        b = jax.random.normal(kb, (K, N), jnp.bfloat16)
        for bm, bk, bn in blocks:
            cfg = MatmulConfig(bm=bm, bk=bk, bn=bn, k_innermost=True)
            jax.block_until_ready(ops.matmul_op(a, b, cfg))   # compile
            cases.append(((M, N, K), (bm, bk, bn), cfg, a, b))

    with tempfile.TemporaryDirectory(prefix="step-cost-") as log_dir:
        trace.start(log_dir)
        for _, _, cfg, a, b in cases:
            for _ in range(CALLS):
                jax.block_until_ready(ops.matmul_op(a, b, cfg))
        devices, _ = trace.read(trace.stop(log_dir))
    per_op: Dict[str, List[int]] = {}
    for name, s, e in devices[0].ops:
        per_op.setdefault(name, []).append(e - s)

    rows = []
    for (M, N, K), blocks, _, _, _ in cases:
        name = kernel_name(M, N, K, *blocks, True)
        times = per_op.get(name, [])
        if len(times) != CALLS:
            raise SystemExit(f"{name}: {len(times)} device ops in the trace,"
                             f" expected {CALLS}")
        model = TpuMatmulModel(M=M, N=N, K=K)
        g = (*blocks, True)
        bound = max(2 * M * N * K / model.hw.flops_peak,
                    2 * (M * K + K * N + M * N) / model.hw.hbm_bw)
        t = statistics.median(times) / 1e3
        rows.append({
            "shape": [M, N, K], "blocks": list(blocks),
            **features(M, N, K, blocks),
            "vmem_limit_mib": model.vmem_limit_bytes(g) / (1 << 20),
            "compute_bound": model.block_compute_s(g)
            >= model.block_dma_s(g),
            "measured_us": t, "pipeline_us": model.pipeline_s(g) * 1e6,
            "model_us": model.latency_s(g) * 1e6,
            "roofline_pct": 100 * bound * 1e6 / t})
    return rows


def cell_group_sizes(seed: int = 0) -> Dict[int, List[np.ndarray]]:
    """Rows -> each layer's group sizes (E,) in the MoE cell at ``seed``:
    every token of the layer's slab (prefill), and one token from each
    of the first ``GMM_DECODE_TOKENS`` sequences (decode)."""
    import jax
    from bench import harness
    from bench.drivers import moe_gemm
    from bench.reference import moe_transformer as ref
    from bench.systems import moe_transformer as system

    config, traffic = GMM_CELL
    cfg = harness.load_json(harness.BENCH / "configs" / f"{config}.json")
    mix = harness.load_json(harness.BENCH / "traffic" / f"{traffic}.json")
    mcfg = system.program_config(cfg)
    E, S = cfg["num_experts"], mix["prefill_len"]
    routes = jax.jit(moe_gemm._routes(mcfg))
    out: Dict[int, List[np.ndarray]] = {}
    for li, x in enumerate(moe_gemm.make_tokens(cfg, mix, seed)):
        p = system.moe_params(ref.make_layer(ref.dims(cfg),
                                             ref.layer_key(seed, li)))
        (experts, sizes), = routes([p], [x])
        decode = np.asarray(experts)[::S][:GMM_DECODE_TOKENS].reshape(-1)
        for gs in (np.asarray(sizes), np.bincount(decode, minlength=E)):
            out.setdefault(int(gs.sum()), []).append(gs.astype(np.int32))
    return out


def tile_visits(sizes: np.ndarray, bm: int) -> int:
    """The grouped kernel's (group, m-tile) visits for ``sizes``."""
    ends = np.cumsum(sizes)
    starts = ends - sizes
    full = sizes > 0
    return int(np.sum((ends[full] - 1) // bm - starts[full] // bm + 1))


def measure_gmm() -> List[Dict]:
    import jax
    import jax.numpy as jnp
    from bench import trace
    from repro.kernels import ops
    from repro.kernels.autotune import TpuGmmModel
    from repro.kernels.gmm import GmmConfig, kernel_name

    if jax.default_backend() != "tpu":
        raise SystemExit("step_cost needs a TPU: device times only")
    groups = cell_group_sizes(0)
    key = jax.random.key(0)
    cases = []
    for (R, N, K), blocks in GMM_SWEEP.items():
        sizes = groups[R]
        E = len(sizes[0])
        ka, kb, key = jax.random.split(key, 3)
        a = jax.random.normal(ka, (R, K), jnp.bfloat16)
        b = jax.random.normal(kb, (E, K, N), jnp.bfloat16)
        gs = [jnp.asarray(s) for s in sizes]
        for bm, bk, bn in blocks:
            cfg = GmmConfig(bm=bm, bk=bk, bn=bn)
            jax.block_until_ready(ops.gmm_op(a, b, gs[0], cfg))  # compile
            cases.append(((R, N, K, E), (bm, bk, bn), cfg, a, b, gs, sizes))

    with tempfile.TemporaryDirectory(prefix="step-cost-") as log_dir:
        trace.start(log_dir)
        for _, _, cfg, a, b, gs, _ in cases:
            for g in gs:
                for _ in range(GMM_CALLS):
                    jax.block_until_ready(ops.gmm_op(a, b, g, cfg))
        devices, _ = trace.read(trace.stop(log_dir))
    per_op: Dict[str, List[int]] = {}
    for name, s, e in devices[0].ops:
        per_op.setdefault(name, []).append(e - s)

    rows = []
    for (R, N, K, E), blocks, _, _, _, gs, sizes in cases:
        bm = blocks[0]
        name = kernel_name(R, N, K, E, *blocks)
        times = per_op.get(name, [])
        if len(times) != GMM_CALLS * len(gs):
            raise SystemExit(f"{name}: {len(times)} device ops in the "
                             f"trace, expected {GMM_CALLS * len(gs)}")
        model = TpuGmmModel(R=R, N=N, K=K, E=E)
        g = (*blocks, True)
        mm = model.tiles_model(g)
        gn = model.grid(g)[1]
        # a visit's own DMA: its rows in and out, without the weight
        td_rows = mm.block_dma_s(g) - blocks[1] * blocks[2] * 2 \
            / model.hw.hbm_bw
        rows.append({
            "shape": [R, N, K, E], "blocks": list(blocks),
            "steps": gn * model.visits(bm), "switches": gn * min(E, R),
            "visits_model": model.visits(bm),
            "visits_run": statistics.mean(tile_visits(s, bm)
                                          for s in sizes),
            "groups_run": statistics.mean(int(np.sum(s > 0))
                                          for s in sizes),
            "padding": model.visits(bm) * bm / R,
            "compute_bound": mm.block_compute_s(g) >= td_rows,
            "measured_us": statistics.mean(times) / 1e3,
            "per_visit_us": model.per_visit_s(g) * 1e6,
            "model_us": model.latency_s(g) * 1e6})
    return rows


def main_gmm(out: str) -> None:
    rows = measure_gmm()
    constants = fit(rows, "model_us", GMM_TERMS)
    for r in rows:
        r["fit_us"] = r["model_us"] + 1e6 * sum(
            r[t] * constants[c] for t, c in GMM_TERMS.items())
        print("{:>20} {:>14} visits {:7.1f} (model {:4d}) padding {:4.2f}  "
              "measured {:8.2f} us  per-visit model {:8.2f}  model {:8.2f}"
              "  fit {:8.2f}".format(
                  "x".join(map(str, r["shape"])),
                  "x".join(map(str, r["blocks"])), r["visits_run"],
                  r["visits_model"], r["padding"], r["measured_us"],
                  r["per_visit_us"], r["model_us"], r["fit_us"]))
    with open(out, "w") as f:
        json.dump({"rows": rows, "fit": constants}, f, indent=1)
    print(json.dumps({"fit": constants}))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="default chiprun_out/step_cost.json, with --gmm "
                    "chiprun_out/step_cost_gmm.json")
    ap.add_argument("--gmm", action="store_true",
                    help="the grouped kernel on the MoE cell's groups")
    args = ap.parse_args()
    if args.gmm:
        main_gmm(args.out or "chiprun_out/step_cost_gmm.json")
        return
    rows = measure()
    constants = fit(rows)
    for r in rows:
        r["fit_us"] = r["pipeline_us"] + 1e6 * sum(
            r[t] * constants[c] for t, c in TERMS.items())
        print("{:>17} {:>15} steps {:5d} vmem {:5.1f} MiB  measured "
              "{:9.2f} us  pipeline {:9.2f}  fit {:9.2f}  model {:9.2f}  "
              "roofline {:5.2f}%".format(
                  "x".join(map(str, r["shape"])),
                  "x".join(map(str, r["blocks"])), r["steps"],
                  r["vmem_limit_mib"], r["measured_us"], r["pipeline_us"],
                  r["fit_us"], r["model_us"], r["roofline_pct"]))
    with open(args.out or "chiprun_out/step_cost.json", "w") as f:
        json.dump({"rows": rows, "fit": constants}, f, indent=1)
    print(json.dumps({"fit": constants}))


if __name__ == "__main__":
    main()
