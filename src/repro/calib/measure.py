"""The measurement ladder: genome -> timed kernel or deterministic estimate.

Every measurement is stamped with its provenance backend:

  * ``measured`` — the genome's block config run as a real Pallas kernel
    on an accelerator, warmup + best-of-N wall-clock;
  * ``interpret`` — the same kernel jit-compiled in Pallas interpret
    mode on CPU.  The interpreter is staged into XLA by ``jax.jit``, so
    after the (separately recorded) compile, per-call time is real work,
    not Python dispatch;
  * ``hlo_estimate`` — no timing at all: the kernel is lowered and
    compiled, the post-optimization HLO is costed by
    ``launch/hlo_costs.analyze`` (trip-count-aware flops + buffer
    bytes), and a roofline bound ``max(flops/peak, bytes/bw)`` is the
    estimate.  Fully deterministic, and still *genome-sensitive*: the
    HLO byte traffic varies with the block shape even when flops do
    not.  When jax itself is unavailable the same roofline is fed from
    an analytic tile-traffic model (``detail="analytic"``).

``backend="auto"`` takes the highest rung that can run here, so
calibration works on a laptop CI runner and a TPU host alike and only
the provenance differs.  An explicit ``backend="measured"`` never
degrades: without a TPU it raises.

jax is imported lazily inside functions only: ``repro.calib`` must stay
importable in fork-safe jax-free processes (see ``repro.analysis``'s
fork-safety rule).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.hardware import HardwareProfile
from repro.core.workloads import Workload
from repro.obs import get_metrics, get_tracer

from .timing import time_callable

BACKENDS = ("measured", "interpret", "hlo_estimate")


def workload_family(wl) -> str:
    """Human-readable workload family ("mm", "conv", ...).

    ``Fingerprint.family`` is a structural hash — right for cache keys,
    useless for a report row.  Correction factors group by this name
    prefix instead.
    """
    name = wl.name if isinstance(wl, Workload) else str(wl)
    for fam in ("mm", "conv"):
        if name == fam or name.startswith(fam + "_"):
            return fam
    return name.split("_", 1)[0] or name


def predicted_us(result, hw: HardwareProfile) -> float:
    """The analytical model's latency for a ``DesignResult``, in µs."""
    return float(result.latency_cycles) / hw.freq_hz * 1e6


@dataclasses.dataclass(frozen=True)
class MeasureConfig:
    """How the ladder measures one genome."""

    backend: str = "auto"          # "auto" | one of BACKENDS
    warmup: int = 1
    repeats: int = 3
    # timed interpret-mode runs are capped by problem size: above this
    # MAC count the interpreter (even staged) is too slow for a smoke
    # path, so the ladder drops to the hlo_estimate rung
    interpret_max_macs: int = 1 << 21
    # force the jax-free analytic cost path (tests, jax-less hosts)
    analytic_only: bool = False


@dataclasses.dataclass
class Measurement:
    """One measured-vs-predicted pair with provenance."""

    workload: str
    family: str
    hardware: str
    design: str                    # DesignPoint.label()
    genome: Dict[str, List[int]]
    predicted_us: float
    measured_us: float
    backend: str                   # provenance: one of BACKENDS
    rel_err: Optional[float] = None  # |measured - predicted| / measured
    compile_us: Optional[float] = None
    repeats: int = 1
    detail: str = ""
    measured_at: float = 0.0

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, payload: Dict) -> "Measurement":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in payload.items() if k in fields})


# ------------------------------------------------------------------ #
# Genome -> kernel config
# ------------------------------------------------------------------ #
def _mm_dims(wl: Workload) -> Tuple[int, int, int]:
    b = wl.bounds
    return int(b["i"]), int(b["j"]), int(b["k"])


def _mm_blocks(wl: Workload, genome) -> Tuple[int, int, int]:
    """The genome's array-partitioning tiles as Pallas block shape.

    ``T1 = n1 * n2`` per loop is the paper's array-partitioning tile —
    the exact analog of the BlockSpec block (DESIGN.md §2).  Snapped to
    the block rule Mosaic accepts (``kernels.matmul.legal_blocks``), the
    same rule the TPU autotuner's genomes obey.
    """
    from repro.kernels.matmul import legal_blocks
    M, N, K = _mm_dims(wl)
    return legal_blocks(int(genome.t1("i")), int(genome.t1("k")),
                        int(genome.t1("j")), M, K, N)


def _jax_platform() -> Optional[str]:
    """The default JAX platform, or None where jax is not installed."""
    try:
        import jax
    except ImportError:
        return None
    return jax.default_backend()


# ------------------------------------------------------------------ #
# Rungs
# ------------------------------------------------------------------ #
def _build_mm(wl: Workload, genome, interpret: bool):
    """(jitted fn, operands) for the genome's matmul kernel."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.matmul import MatmulConfig, matmul

    M, N, K = _mm_dims(wl)
    bm, bk, bn = _mm_blocks(wl, genome)
    cfg = MatmulConfig(bm=bm, bk=bk, bn=bn, k_innermost=True,
                       interpret=interpret)
    key = jax.random.PRNGKey(0)
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (M, K), dtype=jnp.float32)
    b = jax.random.normal(kb, (K, N), dtype=jnp.float32)
    fn = jax.jit(lambda x, y: matmul(x, y, config=cfg))
    return fn, (a, b), (bm, bk, bn)


def _timed_rung(wl: Workload, genome, cfg: MeasureConfig,
                interpret: bool) -> Tuple[float, float, str]:
    """(measured_us, compile_us, detail) from a real timed run."""
    tr = get_tracer()
    fn, (a, b), blocks = _build_mm(wl, genome, interpret)
    with tr.span("calib.compile", cat="calib", workload=wl.name,
                 interpret=interpret):
        t0 = time.perf_counter()
        fn(a, b).block_until_ready()
        compile_us = (time.perf_counter() - t0) * 1e6
    with tr.span("calib.run", cat="calib", workload=wl.name,
                 repeats=cfg.repeats):
        res = time_callable(lambda: fn(a, b),
                            warmup=max(0, cfg.warmup - 1),
                            repeats=cfg.repeats)
    return res.best_us, compile_us, "blocks=%dx%dx%d" % blocks


def _roofline_us(flops: float, byts: float, hw: HardwareProfile) -> float:
    # FPGA profiles have no flops_peak field — each DSP is one MAC/cycle
    peak = hw.flops_peak or 2.0 * hw.dsp_available * hw.freq_hz
    bw = hw.hbm_bw or hw.dram_bw
    compute_s = flops / peak if peak > 0 else 0.0
    memory_s = byts / bw if bw > 0 else 0.0
    return max(compute_s, memory_s) * 1e6


def _analytic_costs(wl: Workload, genome) -> Tuple[float, float]:
    """(flops, bytes) from the tile-traffic model — the jax-free rung.

    Byte traffic mirrors what the k-inner kernel's HLO shows: every
    (i, j, k) grid step streams one A block and one B block from HBM,
    and each output block is written once.
    """
    M, N, K = _mm_dims(wl)
    bm, bk, bn = _mm_blocks(wl, genome)
    gm = -(-M // bm)
    gn = -(-N // bn)
    gk = -(-K // bk)
    flops = 2.0 * M * N * K
    byts = 4.0 * (gm * gn * gk * (bm * bk + bk * bn) + M * N)
    return flops, byts


def _hlo_rung(wl: Workload, genome, hw: HardwareProfile,
              cfg: MeasureConfig) -> Tuple[float, float, str]:
    """(estimate_us, compile_us, detail) — deterministic, no timing."""
    if not cfg.analytic_only and _jax_platform() is not None:
        from repro.launch.hlo_costs import analyze
        fn, (a, b), blocks = _build_mm(wl, genome, interpret=True)
        with get_tracer().span("calib.compile", cat="calib",
                               workload=wl.name, hlo=True):
            t0 = time.perf_counter()
            hlo = fn.lower(a, b).compile().as_text()
            compile_us = (time.perf_counter() - t0) * 1e6
        costs = analyze(hlo)
        return (_roofline_us(costs.flops, costs.bytes, hw), compile_us,
                "hlo blocks=%dx%dx%d flops=%g bytes=%g"
                % (blocks + (costs.flops, costs.bytes)))
    flops, byts = _analytic_costs(wl, genome)
    return (_roofline_us(flops, byts, hw), 0.0,
            "analytic flops=%g bytes=%g" % (flops, byts))


def _resolve_backend(wl: Workload, cfg: MeasureConfig) -> str:
    """The rung to run: ``measured`` exactly when asked for or, under
    ``auto``, when a TPU is here; otherwise the highest rung that can
    run here."""
    want = cfg.backend
    if want not in BACKENDS + ("auto",):
        raise ValueError(f"unknown backend {want!r}; "
                         f"expected one of {BACKENDS + ('auto',)}")
    fam = workload_family(wl)
    plat = None if cfg.analytic_only else _jax_platform()
    if want == "measured":
        if fam != "mm" or plat != "tpu":
            raise RuntimeError(
                "backend='measured' times the Pallas matmul on a TPU; "
                f"here the workload family is {fam!r} and the JAX "
                f"platform is {plat or 'unavailable'}")
        return "measured"
    timable = fam == "mm" and plat is not None
    if want == "auto" and timable and plat == "tpu":
        return "measured"
    if want in ("auto", "interpret") and timable and \
            wl.total_macs() <= cfg.interpret_max_macs:
        return "interpret"
    return "hlo_estimate"


# ------------------------------------------------------------------ #
# Entry points
# ------------------------------------------------------------------ #
def measure_result(wl: Workload, result, hw: HardwareProfile,
                   cfg: Optional[MeasureConfig] = None) -> Measurement:
    """Run the ladder for one ``DesignResult``'s best genome."""
    cfg = cfg or MeasureConfig()
    tr = get_tracer()
    genome = result.evo.best
    backend = _resolve_backend(wl, cfg)
    pred = predicted_us(result, hw)
    with tr.span("calib.measure", cat="calib", workload=wl.name,
                 design=result.design.label(), backend=backend):
        if backend == "measured":
            meas, compile_us, detail = _timed_rung(wl, genome, cfg,
                                                   interpret=False)
        elif backend == "interpret":
            meas, compile_us, detail = _timed_rung(wl, genome, cfg,
                                                   interpret=True)
        else:
            meas, compile_us, detail = _hlo_rung(wl, genome, hw, cfg)
    rel_err = abs(meas - pred) / meas if meas > 0 else None
    m = get_metrics()
    m.counter("calib.measurements")
    if rel_err is not None:
        m.observe("calib.rel_err", rel_err)
    return Measurement(
        workload=wl.name, family=workload_family(wl), hardware=hw.name,
        design=result.design.label(),
        genome={l: list(t) for l, t in genome.as_dict().items()},
        predicted_us=pred, measured_us=meas, backend=backend,
        rel_err=rel_err, compile_us=compile_us, repeats=cfg.repeats,
        detail=detail, measured_at=time.time())


def measure_top_k(wl: Workload, results: Sequence, hw: HardwareProfile,
                  cfg: Optional[MeasureConfig] = None) -> List[Measurement]:
    """Measure each result; emits the ``calibration`` counter track."""
    tr = get_tracer()
    out: List[Measurement] = []
    counts = {b: 0 for b in BACKENDS}
    for r in results:
        meas = measure_result(wl, r, hw, cfg)
        out.append(meas)
        counts[meas.backend] += 1
        if tr.enabled:
            tr.counter("calibration", **counts)
    return out
