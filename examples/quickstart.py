"""Quickstart: the Odyssey flow on the paper's 1024^3 matrix multiplication.

Runs the full two-stage tuner (MP seeding + hybrid-mutation evolutionary
search) over all 18 systolic-array designs, prints the leaderboard, shows
the non-divisor tiling of the winner, and compares against the
oversimplified baselines the paper quantifies (Fig. 1).

    PYTHONPATH=src python examples/quickstart.py
"""

import os
import time

from repro.core import (EvoConfig, GenomeSpace, SearchSession, SessionConfig,
                        TilingProblem, U250, baselines, evolve, mm_1024,
                        tune_workload)
from repro.registry import RegistryStore

REGISTRY_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "experiments", "registry")


def main() -> None:
    wl = mm_1024()
    print(f"workload: {wl.name}  (design space ~2^40 per the paper)")

    # persistent design registry: the sweep below is recorded, so a second
    # run of this script serves the winner from disk with zero evals
    store = RegistryStore(REGISTRY_DIR)

    t0 = time.time()
    session = SearchSession(
        wl, cfg=EvoConfig(epochs=120, population=64, seed=0),
        time_budget_s=5.0, registry=store,
        session=SessionConfig(executor="process", early_abort=True))
    report = session.run()
    if report.from_cache:
        print(f"\nserved all designs from {REGISTRY_DIR} in "
              f"{time.time() - t0:.3f}s — cached by a previous run, "
              "0 evolutionary evaluations\n")
    else:
        print(f"\ntuned all 18 designs in {time.time() - t0:.1f}s "
              f"(paper: 90% of optimal in 5s, single thread; "
              f"{sum(r.aborted for r in report.results)} dominated designs "
              f"aborted)\n")

    print(f"{'design':26s} {'GFLOP/s':>8s} {'DSP%':>5s} {'BRAM':>5s} feas")
    for r in sorted(report.results, key=lambda r: -r.throughput)[:8]:
        print(f"{r.design.label():26s} {r.throughput / 1e9:8.0f} "
              f"{100 * r.dsp // U250.dsp_available:4d}% {r.bram:5d} "
              f"{r.feasible}")

    best = report.best
    g = best.evo.best
    print(f"\nwinner: {best.design.label()}")
    print(f"  tiling (n0, n1, n2) per loop: {g.as_dict()}")
    nondiv = [l for l in wl.loop_names if wl.loop(l).bound % g.t1(l) != 0]
    print(f"  non-divisor tiles on loops: {nondiv or 'none'} "
          f"(the paper's key design-space insight)")

    print("\nlatency-vs-resources Pareto frontier:")
    for p in sorted(session.pareto(), key=lambda p: p.latency_cycles)[:6]:
        print(f"  {p.design:26s} {p.latency_cycles:12.0f} cyc "
              f"{100 * p.dsp // U250.dsp_available:4d}% DSP {p.bram:5d} BRAM")

    # the oversimplifications the paper quantifies
    space_d = GenomeSpace(wl, best.design.dataflow, divisors_only=True)
    cfg = EvoConfig(epochs=120, population=64, seed=0)
    div = baselines.divisor_only_evolutionary(space_d, best.model, cfg)
    print(f"\ndivisor-only search: "
          f"{best.latency_cycles / -best.model.fitness(div.best):.2f}x "
          f"of tuned performance (paper: 0.61x)")

    # the compiled engine (DESIGN.md §3 "JAX engine"): the whole
    # generation loop — selection, crossover, mutation, legalization,
    # fitness — runs as one jitted lax.scan, and extra search chains are
    # one vmap axis, nearly free.  (Kept after the sweep: importing jax
    # switches SearchSession off its fork-based pool.)
    space = GenomeSpace(wl, best.design.dataflow)
    prob = TilingProblem(space, best.model)
    jcfg = EvoConfig(epochs=120, population=64, seed=0)
    t0 = time.time()
    one = evolve(prob, jcfg, engine="jax")
    t_one = time.time() - t0
    t0 = time.time()
    multi = evolve(prob, jcfg, engine="jax", chains=8)
    t_multi = time.time() - t0
    print(f"\ncompiled engine (engine='jax', compile included): "
          f"1 chain {one.evals} evals in {t_one:.1f}s; "
          f"8 chains {multi.evals} evals in {t_multi:.1f}s "
          f"-> best {-multi.best_fitness:.0f} cyc "
          f"(numpy-engine winner: {best.latency_cycles:.0f})")

    # cached second run: a fresh session over the same workload is a pure
    # registry lookup — this is what every later process (or serving
    # replica pointing at the same registry dir) pays
    t0 = time.time()
    rerun = SearchSession(wl, registry=store,
                          session=SessionConfig(executor="serial")).run()
    print(f"\ncached second run: from_cache={rerun.from_cache}, "
          f"{sum(r.evo.evals for r in rerun.results)} evals, "
          f"{time.time() - t0:.3f}s "
          f"(inspect with: python -m repro.registry list --root "
          f"{os.path.relpath(REGISTRY_DIR)})")

    network_demo(store)
    serving_demo()
    tracing_demo()
    calibration_demo(store)
    chaos_demo()


def network_demo(store: RegistryStore) -> None:
    """Network-level DSE (DESIGN.md §11): tune a *whole model config*.

    Every GEMM a served transformer issues (attention projections, MLP,
    LM head; prefill and decode token dims) is extracted into a
    LayerGraph, deduped into shape classes, and swept through the
    registry-backed engine.  The second run — and any process pointing
    at the same registry — resolves every class with 0 evals.
    """
    from repro.configs import get_smoke_config
    from repro.kernels.autotune import pretune_model_config
    from repro.network import AssignConfig, NetworkSession, \
        model_config_graph

    cfg = get_smoke_config("smollm-135m")
    graph = model_config_graph(cfg, batch=4, prefill_len=64)
    s = graph.summary()
    print(f"\nnetwork DSE: {s['name']} — {s['layers']} layer GEMMs "
          f"collapse to {s['classes']} shape classes")

    for attempt in ("cold", "warm"):
        t0 = time.time()
        sess = NetworkSession(
            graph, cfg=EvoConfig(epochs=8, population=16, seed=0),
            registry=store,
            assign=AssignConfig(max_arrays=2, retune_evals=60,
                                amortize_over=16))
        rep = sess.run(k_values=(1, 2))
        print(f"  {attempt} run: {rep.total_evals} evals, "
              f"{time.time() - t0:.2f}s — uniform array at "
              f"{rep.per_layer_cycles / rep.uniform_cycles:.0%} of the "
              f"per-layer ideal")

    # the TPU-side twin: pre-resolve the Pallas matmul block config of
    # every GEMM the model issues (python -m repro.network --pretune does
    # this); the second pass is what every later process on this
    # registry pays
    from repro.kernels.autotune import reset_config_lru
    for attempt in ("first replica", "later replicas"):
        stats = pretune_model_config(cfg, batch=4, prefill_len=64,
                                     registry=store)
        print(f"  kernel pre-tune ({attempt}): {stats['shapes']} block "
              f"configs — {stats['tuned']} searched, "
              f"{stats['disk_hits'] + stats['lru_hits']} cached")
        reset_config_lru()   # later replicas have cold process memory


def serving_demo() -> None:
    """Continuous batching (DESIGN.md §10).

    A mixed stream — mostly short EOS-terminated replies plus a long
    tail — through 4 decode slots.  Each slot is recycled at its
    request's EOS, so no request waits for the slowest of a batch.
    (The deterministic forced-EOS stub model keeps this instant; swap in
    `build_model(get_smoke_config(...))` and real prompts for an actual
    LM — the engine is model-agnostic.)
    """
    from repro.serve import ContinuousServingEngine, ServeConfig
    from repro.serve.sim import countdown_model, poisson_requests

    print("\nserving: continuous batching "
          "(mixed EOS-terminated lengths, 4 slots)")
    model = countdown_model(vocab_size=64)
    params = model.init(None)
    cfg = ServeConfig(max_batch=4, max_seq=128, eos_token=0,
                      prefill_chunk=16)
    requests = poisson_requests(16, rate_rps=0, vocab_size=64,
                                max_new_tokens=64, seed=0)
    _, stats = ContinuousServingEngine(model, params, cfg).serve(requests)
    print(f"  {stats.summary()}")


def tracing_demo() -> None:
    """Observability spine (DESIGN.md §12): trace a sweep, render it.

    Every CLI takes ``--trace PATH`` (launch/serve.py, python -m
    repro.network, python -m benchmarks.run); here the same thing is
    done in-process.  The JSONL stream renders two ways:

        python -m repro.obs summarize   /tmp/quickstart.trace.jsonl
        python -m repro.obs to-perfetto /tmp/quickstart.trace.jsonl
        # -> /tmp/quickstart.perfetto.json, open at ui.perfetto.dev
    """
    from repro import obs
    from repro.core import mm_validation

    path = "/tmp/quickstart.trace.jsonl"
    if os.path.exists(path):
        os.unlink(path)                  # the sink appends
    obs.configure(path, process_name="quickstart")
    rep = SearchSession(mm_validation(),
                        cfg=EvoConfig(epochs=6, population=16, seed=0),
                        session=SessionConfig(executor="serial")).run()
    obs.disable()
    events, corrupt = obs.load_events(path)
    s = obs.summarize(events)
    print(f"\ntracing: {len(rep.results)} designs -> {len(events)} events "
          f"({corrupt} corrupt) in {path}")
    print(f"  spans: " + ", ".join(
        f"{k} x{v['count']}" for k, v in sorted(s["spans"].items())))
    print(f"  render: python -m repro.obs to-perfetto {path}")


def calibration_demo(store: RegistryStore) -> None:
    """Ground-truth calibration (DESIGN.md §14): tune → calibrate → re-rank.

    The sweep's top designs are measured as jit-compiled Pallas kernels
    in interpret mode — the CPU rung of the provenance ladder
    (measured → interpret → hlo_estimate) — the measured-vs-predicted
    pairs land in the registry record (schema v4), per-(hardware,
    family) correction factors are fitted over everything the registry
    has seen, and the Pareto frontier is re-ranked by corrected
    latency.  Inspect afterwards with::

        python -m repro.calib report --registry experiments/registry
        python -m repro.calib drift  --registry experiments/registry
    """
    from repro.calib import CalibratedModel, MeasureConfig, calibrate_report
    from repro.core import mm_validation

    wl = mm_validation()         # 64^3 — small enough to interpret-time
    session = SearchSession(
        wl, cfg=EvoConfig(epochs=12, population=32, seed=0),
        registry=store, session=SessionConfig(executor="serial"))
    report = session.run()
    cal = calibrate_report(wl, report, U250, registry=store, k=3,
                           cfg=MeasureConfig(backend="interpret"))

    backends = ", ".join(sorted({m.backend for m in cal.measurements}))
    print(f"\ncalibration: {len(cal.measurements)} designs measured "
          f"({backends}); Spearman(predicted, measured) = "
          f"{cal.spearman:+.2f}")
    for m in cal.measurements:
        err = f"{m.rel_err:+7.0%}" if m.rel_err is not None else "    n/a"
        print(f"  {m.design:26s} predicted {m.predicted_us:10.1f}us  "
              f"{m.backend} {m.measured_us:10.1f}us  rel-err {err}")

    model = CalibratedModel(cal.corrections, cal.measurements)
    frontier = sorted(session.pareto(), key=lambda p: p.latency_cycles)
    print("  frontier re-ranked by corrected latency:")
    for p in model.rerank(frontier, U250, "mm")[:4]:
        c = model.corrected_us(p, U250, "mm")
        pred = p.latency_cycles / U250.freq_hz * 1e6
        shown = f"{c:10.1f}us corrected" if c is not None \
            else f"{pred:10.1f}us model"
        print(f"    {p.design:26s} {shown}")
    print(f"  correction factors persisted to {cal.state_file}")


def chaos_demo() -> None:
    """Chaos engineering (DESIGN.md §15): the sweep survives its workers.

    A deterministic fault plan kills one pool worker mid-design
    (``os._exit``, a simulated OOM-kill) and hangs another; the engine
    rebuilds the pool, retries the lost designs, and — because every
    per-design search is seeded — lands on the bit-identical winner of
    a fault-free run.  A corrupt registry write is quarantined by the
    next reader instead of being served."""
    import tempfile

    from repro.core import matmul
    from repro.faults import FaultPlan, FaultSpec, injected
    from repro.registry import workload_fingerprint

    wl = matmul(32, 32, 32)

    def sweep():
        s = SearchSession(
            wl, cfg=EvoConfig(epochs=6, population=16, seed=0),
            session=SessionConfig(executor="process", max_workers=2,
                                  early_abort=False, hang_timeout_s=3.0))
        s.run()
        return s

    clean = sweep()
    plan = FaultPlan((
        FaultSpec("search.worker", "crash", key="3"),
        FaultSpec("search.worker", "hang", key="1", delay_s=60.0),
    ))
    print("\nchaos:" + plan.describe().replace("FaultPlan", " FaultPlan"))
    with injected(plan):
        chaotic = sweep()
    same = (chaotic.report.best.evo.best.key()
            == clean.report.best.evo.best.key())
    print(f"  recovered: {chaotic.pool_rebuilds} pool rebuild(s), "
          f"retries {dict(chaotic.design_retries)}, "
          f"best bit-identical to fault-free run: {same}")

    root = tempfile.mkdtemp(prefix="chaos-demo-")
    store = RegistryStore(root)
    fp = workload_fingerprint(wl, U250)
    with injected(FaultPlan((FaultSpec("registry.put.payload",
                                       "corrupt"),))):
        sweep_store = SearchSession(
            wl, cfg=EvoConfig(epochs=6, population=16, seed=0),
            registry=store,
            session=SessionConfig(executor="serial", early_abort=False))
        sweep_store.run()
        served = store.get(fp)
    print(f"  corrupt record served: {served!r} "
          f"(quarantined as *.corrupt — a cache must never crash "
          "its caller)")


# The process-pool engine uses the spawn context (fork is unsafe once jax's
# threads exist), and spawn re-imports __main__ in each worker — so the
# driver code must live under this guard.
if __name__ == "__main__":
    main()
