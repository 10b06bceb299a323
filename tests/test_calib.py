"""Calibration layer (DESIGN.md §14): ladder, fit, re-rank, drift, CLI.

Everything here runs on the jax-free analytic rung (deterministic,
milliseconds) — the timed interpret rung and the HLO rung are exercised
by ``benchmarks/calibration.py`` in CI, where a jax compile is
affordable.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

import pytest

from repro.calib import (CalibratedModel, CalibrationState, CorrectionFactor,
                         MeasureConfig, Measurement, calibrate_report,
                         check_drift, factor_key, fit_corrections,
                         measure_result, predicted_us, spearman,
                         time_callable, top_k_results, workload_family)
from repro.calib.calibrate import state_path
from repro.calib.measure import _analytic_costs, _mm_blocks, _resolve_backend
from repro.calib.session import calibrate_session, registry_measurements
from repro.core.engine import ParetoPoint, SearchSession, SessionConfig
from repro.core.evolutionary import EvoConfig
from repro.core.hardware import U250
from repro.core.tuner import tune_design
from repro.core.workloads import matmul
from repro.core.design_space import enumerate_designs
from repro.registry import RegistryStore, workload_fingerprint

_ANALYTIC = MeasureConfig(analytic_only=True)
_EVO = EvoConfig(epochs=6, population=32, seed=0)


def _tiny_result(n=16):
    wl = matmul(n, n, n)
    df, perm = enumerate_designs(wl)[0]
    return wl, tune_design(wl, df, perm, cfg=_EVO)


def _session(wl, **kw):
    return SearchSession(wl, hw=U250, cfg=_EVO,
                         session=SessionConfig(executor="serial",
                                               early_abort=False), **kw)


# --------------------------------------------------------------------- #
# timing harness
# --------------------------------------------------------------------- #
def test_time_callable_warmup_and_best_of_n():
    calls = []

    def fn():
        calls.append(1)
        return len(calls)

    res = time_callable(fn, warmup=2, repeats=3)
    assert len(calls) == 5                      # 2 warmup + 3 timed
    assert res.out == 5 and res.repeats == 3
    assert res.best_us == min(res.runs_us) <= res.mean_us
    assert res.warmup_us is not None and res.warmup_us >= 0


def test_time_callable_single_shot_and_validation():
    res = time_callable(lambda: 7, warmup=0, repeats=1)
    assert res.out == 7 and res.warmup_us is None and len(res.runs_us) == 1
    with pytest.raises(ValueError):
        time_callable(lambda: 0, repeats=0)


def test_time_callable_syncs_device_work():
    class Lazy:
        waited = False

        def block_until_ready(self):
            Lazy.waited = True
            return self

    time_callable(lambda: Lazy(), warmup=0, repeats=1)
    assert Lazy.waited


# --------------------------------------------------------------------- #
# measurement ladder (analytic rung)
# --------------------------------------------------------------------- #
def test_workload_family_names():
    assert workload_family(matmul(8, 8, 8)) == "mm"
    assert workload_family("mm_64x64x64") == "mm"
    assert workload_family("conv_i3_o64") == "conv"
    assert workload_family("weird") == "weird"


def test_ladder_degrades_to_hlo_estimate_without_jax():
    wl, res = _tiny_result()
    for want in ("auto", "interpret", "hlo_estimate"):
        cfg = MeasureConfig(backend=want, analytic_only=True)
        assert _resolve_backend(wl, cfg) == "hlo_estimate"
    with pytest.raises(RuntimeError, match="measured"):
        _resolve_backend(wl, MeasureConfig(backend="measured",
                                           analytic_only=True))
    with pytest.raises(ValueError):
        _resolve_backend(wl, MeasureConfig(backend="vibes"))


def test_measured_backend_raises_without_a_chip():
    """An explicit ``measured`` request never degrades to interpret or an
    estimate: on a host whose JAX platform is not a TPU it raises, and
    nothing is timed or stamped."""
    jax = pytest.importorskip("jax")
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached: measured is available here")
    wl, res = _tiny_result()
    with pytest.raises(RuntimeError, match="measured.*TPU"):
        measure_result(wl, res, U250, MeasureConfig(backend="measured"))
    # auto still runs on the CPU, stamped with the rung it used
    m = measure_result(wl, res, U250, MeasureConfig(backend="auto"))
    assert m.backend == "interpret"


def test_analytic_measurement_is_deterministic_and_stamped():
    wl, res = _tiny_result()
    m1 = measure_result(wl, res, U250, _ANALYTIC)
    m2 = measure_result(wl, res, U250, _ANALYTIC)
    assert m1.backend == "hlo_estimate" and "analytic" in m1.detail
    assert m1.measured_us == m2.measured_us > 0
    assert m1.predicted_us == pytest.approx(predicted_us(res, U250))
    assert m1.rel_err == pytest.approx(
        abs(m1.measured_us - m1.predicted_us) / m1.measured_us)
    assert m1.family == "mm" and m1.hardware == "u250"
    assert m1.genome == {l: list(t)
                         for l, t in res.evo.best.as_dict().items()}
    # round-trips through JSON
    assert Measurement.from_json(
        json.loads(json.dumps(m1.to_json()))).measured_us == m1.measured_us


def test_analytic_costs_are_genome_sensitive():
    wl = matmul(64, 64, 64)
    df, perm = enumerate_designs(wl)[0]
    res = tune_design(wl, df, perm, cfg=_EVO)
    g = res.evo.best
    flops, byts = _analytic_costs(wl, g)
    assert flops == 2 * 64 ** 3
    # a different blocking must move the byte traffic (the roofline's
    # genome sensitivity) even though flops are invariant
    small = dataclasses.replace(res)
    bm, bk, bn = _mm_blocks(wl, g)
    other = {l: (64 // 4, 2, 2) for l in ("i", "j", "k")}
    from repro.core.design_space import Genome
    flops2, byts2 = _analytic_costs(wl, Genome(other))
    assert flops2 == flops
    if (bm, bk, bn) != (4, 4, 4):
        assert byts2 != byts


# --------------------------------------------------------------------- #
# fit + re-rank + drift
# --------------------------------------------------------------------- #
def test_spearman_basics():
    assert spearman([1, 2, 3, 4], [10, 20, 30, 40]) == pytest.approx(1.0)
    assert spearman([1, 2, 3, 4], [4, 3, 2, 1]) == pytest.approx(-1.0)
    assert spearman([1, 1, 1], [1, 2, 3]) == 0.0          # no x variance
    assert spearman([1.0], [2.0]) == 0.0                  # degenerate
    assert 0.0 < spearman([1, 2, 2, 3], [1, 2, 3, 4]) < 1.0   # avg ties
    with pytest.raises(ValueError):
        spearman([1, 2], [1])


def _meas(pred, meas, family="mm", backend="hlo_estimate", design="d",
          genome=None, at=1.0):
    return Measurement(workload="mm_t", family=family, hardware="u250",
                       design=design, genome=genome or {"i": [1, 2, 4]},
                       predicted_us=pred, measured_us=meas, backend=backend,
                       rel_err=None, measured_at=at)


def test_fit_corrections_geometric_mean():
    factors = fit_corrections([_meas(1.0, 2.0), _meas(1.0, 8.0)], now=5.0)
    cf = factors[factor_key("u250", "mm", "hlo_estimate")]
    assert cf.factor == pytest.approx(4.0)                # geomean(2, 8)
    assert cf.n == 2 and cf.fitted_at == 5.0
    assert cf.log_std == pytest.approx(math.log(2.0))
    # non-positive pairs are dropped, buckets split by backend
    factors = fit_corrections([_meas(1.0, 0.0), _meas(2.0, 4.0),
                               _meas(1.0, 3.0, backend="interpret")])
    assert factors[factor_key("u250", "mm", "hlo_estimate")].n == 1
    assert factors[factor_key("u250", "mm", "interpret")].factor == \
        pytest.approx(3.0)


def _point(design, cycles, tiling=None):
    return ParetoPoint(design=design, latency_cycles=cycles,
                       throughput_gflops=1.0, dsp=1, bram=1, feasible=True,
                       tiling=tiling or {"i": (1, 2, 4)})


def test_rerank_is_identity_without_measurements():
    pts = [_point("a", 300.0), _point("b", 100.0), _point("c", 200.0)]
    out = CalibratedModel({}).rerank(pts, U250, "mm")
    assert out == pts and all(x is y for x, y in zip(out, pts))
    # a factor for a *different* bucket is still the identity
    cf = CorrectionFactor("tpu_v5e", "mm", "interpret", 2.0, 0.0, 3)
    out = CalibratedModel({cf.key: cf}).rerank(pts, U250, "mm")
    assert out == pts


def test_rerank_uses_measurements_over_factors():
    g_a, g_b = {"i": (1, 2, 4)}, {"i": (2, 2, 2)}
    pts = [_point("a", 100.0, g_a), _point("b", 200.0, g_b)]
    # model says a < b, but ground truth says a is 10x slower
    us_a = 100.0 / U250.freq_hz * 1e6
    m = _meas(us_a, us_a * 10, design="a",
              genome={"i": [1, 2, 4]})
    model = CalibratedModel({}, measurements=[m])
    out = model.rerank(pts, U250, "mm")
    assert [p.design for p in out] == ["b", "a"]
    # a pure per-family factor is order-preserving by construction
    cf = CorrectionFactor("u250", "mm", "hlo_estimate", 5.0, 0.0, 4)
    out = CalibratedModel({cf.key: cf}).rerank(pts, U250, "mm")
    assert [p.design for p in out] == ["a", "b"]


def test_calibrated_model_backend_priority():
    lo = CorrectionFactor("u250", "mm", "hlo_estimate", 2.0, 0.0, 9)
    hi = CorrectionFactor("u250", "mm", "measured", 3.0, 0.0, 2)
    model = CalibratedModel({lo.key: lo, hi.key: hi})
    assert model.factor_for("u250", "mm").backend == "measured"
    assert model.factor_for("u250", "conv") is None


def test_state_round_trip_and_corruption(tmp_path):
    cf = CorrectionFactor("u250", "mm", "interpret", 1.5, 0.1, 4, 9.0)
    path = str(tmp_path / "reg" / "calibration.json")
    CalibrationState(factors={cf.key: cf}, n_measurements=4,
                     fitted_at=9.0).save(path)
    state = CalibrationState.load(path)
    assert state is not None and state.n_measurements == 4
    assert state.factors[cf.key] == cf
    assert CalibrationState.load(str(tmp_path / "missing.json")) is None
    with open(path, "w") as f:
        f.write("{nope")
    assert CalibrationState.load(path) is None


def test_drift_rule_is_symmetric_and_gated_on_n():
    base = {factor_key("u250", "mm", "interpret"):
            CorrectionFactor("u250", "mm", "interpret", 2.0, 0.0, 4)}

    def fresh(factor, n=4):
        return {factor_key("u250", "mm", "interpret"):
                CorrectionFactor("u250", "mm", "interpret", factor, 0.0, n)}

    assert not check_drift(base, fresh(2.2))              # within 25%
    up = check_drift(base, fresh(3.0))
    down = check_drift(base, fresh(2.0 / 1.5))
    assert len(up) == len(down) == 1                      # symmetric in log
    assert up[0].ratio == pytest.approx(1.5)
    assert not check_drift(base, fresh(9.0, n=1))         # 1 point != drift
    assert not check_drift({}, fresh(9.0))                # no baseline
    with pytest.raises(ValueError):
        check_drift(base, fresh(3.0), threshold=0.0)


# --------------------------------------------------------------------- #
# session orchestration + engine hook + registry v4
# --------------------------------------------------------------------- #
def test_top_k_filters_and_orders():
    wl = matmul(16, 16, 16)
    s = _session(wl)
    report = s.run()
    top = top_k_results(report, k=3)
    assert len(top) == 3
    lats = [r.latency_cycles for r in top]
    assert lats == sorted(lats)
    assert all(r.feasible and not r.aborted for r in top)
    assert s.top_k(3) == top                    # engine hook agrees
    with pytest.raises(ValueError):
        top_k_results(report, k=0)
    with pytest.raises(ValueError):
        s.top_k(0)


def test_top_k_requires_run():
    with pytest.raises(RuntimeError):
        _session(matmul(8, 8, 8)).top_k()


def test_calibrate_report_records_v4_and_fits(tmp_path):
    wl = matmul(16, 16, 16)
    store = RegistryStore(str(tmp_path / "reg"))
    s = _session(wl, registry=store)
    s.run()
    cal = calibrate_report(wl, s.report, U250, registry=store, k=2,
                           cfg=_ANALYTIC)
    assert cal.recorded and len(cal.measurements) == 2
    assert cal.spearman == spearman(
        [m.predicted_us for m in cal.measurements],
        [m.measured_us for m in cal.measurements])
    rec = store.get(workload_fingerprint(wl, U250))
    assert rec.schema_version == 4
    assert len(rec.measurements) == 2
    assert rec.measured_us is not None
    assert rec.measure_backend == "hlo_estimate"
    # best design's measurement is the summary
    assert rec.measurements[0]["design"] == s.report.best.design.label()
    # state persisted beside the registry root, fit over the history
    state = CalibrationState.load(state_path(store.root))
    assert state is not None
    assert factor_key("u250", "mm", "hlo_estimate") in state.factors
    assert [m.measured_us for m in registry_measurements(store)] == \
        [m.measured_us for m in cal.measurements]
    # a second pass appends, never clobbers
    calibrate_report(wl, s.report, U250, registry=store, k=1, cfg=_ANALYTIC)
    assert len(store.get(workload_fingerprint(wl, U250)).measurements) >= 2


def test_search_session_calibration_hook(tmp_path):
    wl = matmul(16, 16, 16)
    store = RegistryStore(str(tmp_path / "reg"))
    base = _session(wl).run()
    hooked = _session(wl, registry=store,
                      calibration=lambda s: calibrate_session(
                          s, k=2, cfg=_ANALYTIC))
    report = hooked.run()
    cal = hooked.calibration_report
    assert cal is not None and len(cal.measurements) == 2
    assert cal.recorded
    # the hook never perturbs the search itself
    assert report.best.evo.best.key() == base.best.evo.best.key()
    assert [r.latency_cycles for r in report.results] == \
        [r.latency_cycles for r in base.results]
    # cached re-run (exact hit) skips both the sweep and the hook
    again = _session(wl, registry=store,
                     calibration=lambda s: (_ for _ in ()).throw(
                         AssertionError("hook ran on a cached report")))
    assert again.run().from_cache


def test_calibrate_report_without_registry():
    wl, res = _tiny_result()
    from repro.core.tuner import TuneReport
    report = TuneReport(workload=wl.name, results=[res])
    cal = calibrate_report(wl, report, U250, k=1, cfg=_ANALYTIC)
    assert not cal.recorded and cal.state_file is None
    assert len(cal.measurements) == 1 and cal.corrections


# --------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------- #
def _main(argv):
    from repro.calib.__main__ import main
    return main(argv)


def test_cli_report_and_drift(tmp_path, capsys):
    root = str(tmp_path / "reg")
    wl = matmul(16, 16, 16)
    store = RegistryStore(root)
    s = _session(wl, registry=store)
    s.run()
    calibrate_report(wl, s.report, U250, registry=store, k=2, cfg=_ANALYTIC)

    assert _main(["report", "--registry", root]) == 0
    out = capsys.readouterr().out
    assert "mm" in out and "correction factors" in out

    # stored fit vs itself: no drift
    assert _main(["drift", "--registry", root]) == 0
    assert "no drift" in capsys.readouterr().out

    # shift the stored factors: drift must be detected and exit 1
    state = CalibrationState.load(state_path(root))
    shifted = {k: dataclasses.replace(f, factor=f.factor * 3.0)
               for k, f in state.factors.items()}
    CalibrationState(factors=shifted,
                     n_measurements=state.n_measurements).save(
        state_path(root))
    assert _main(["drift", "--registry", root]) == 1
    assert "DRIFT" in capsys.readouterr().out


def test_cli_report_empty_registry(tmp_path, capsys):
    assert _main(["report", "--registry", str(tmp_path / "empty")]) == 0
    assert "no measurements" in capsys.readouterr().out


def test_cli_drift_without_state(tmp_path, capsys):
    assert _main(["drift", "--registry", str(tmp_path / "empty")]) == 0
    assert "no stored calibration" in capsys.readouterr().out
