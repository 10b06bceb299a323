"""Observability spine (ISSUE 7): tracer, metrics, Perfetto conversion,
bit-identity under tracing, fork/spawn process safety, CLI surfaces.

The load-bearing contracts:

  * tracing ON must be *bit-identical* to tracing OFF for every search
    engine (same genome stream, same evals, same trace records) — the
    hooks observe, they never steer;
  * a multi-process sweep streams every worker's events into one JSONL
    without interleaving corruption, under fork AND spawn start methods;
  * the Perfetto export is structurally valid Chrome trace-event JSON
    (pid/tid/ph/ts on every event, span nesting balances by containment).
"""

import json
import multiprocessing
import os
import subprocess
import sys

import pytest

from repro import obs
from repro.core import (EvoConfig, GenomeSpace, PerformanceModel,
                        SearchSession, SessionConfig, TilingProblem, U250,
                        build_descriptor, evolve, mm_validation,
                        pruned_permutations)
from repro.core.perf_model import BatchPerformanceModel
from repro.obs import Histogram, Metrics, percentile

CFG = EvoConfig(epochs=6, population=16, seed=0)


@pytest.fixture(autouse=True)
def _tracer_disabled():
    """Every test starts and ends with the global tracer disabled."""
    obs.disable()
    yield
    obs.disable()


def _trace_file(tmp_path, name="run.trace.jsonl"):
    return str(tmp_path / name)


def _problem():
    wl = mm_validation()
    df = ("i", "j")
    perm = pruned_permutations(wl)[0]
    desc = build_descriptor(wl, df, perm)
    model = PerformanceModel(desc, U250)
    return wl, df, perm, model, BatchPerformanceModel(desc, U250), \
        GenomeSpace(wl, df)


# --------------------------------------------------------------------- #
# tracer primitives
# --------------------------------------------------------------------- #
def test_tracer_event_stream(tmp_path):
    path = _trace_file(tmp_path)
    tr = obs.configure(path, process_name="test")
    with tr.span("outer", cat="t", depth=0):
        with tr.span("inner", cat="t", depth=1):
            tr.instant("tick", cat="t", n=1)
        tr.counter("load", busy=3, free=1)
    obs.disable()
    events, corrupt = obs.load_events(path)
    assert corrupt == 0
    kinds = [e["ev"] for e in events]
    assert kinds == ["meta", "instant", "span", "counter", "span"]
    for ev in events:
        assert ev["pid"] == os.getpid()
        assert "tid" in ev
    spans = {e["name"]: e for e in events if e["ev"] == "span"}
    # emitted at exit: inner closes (and lands) before outer, and outer's
    # interval contains inner's
    outer, inner = spans["outer"], spans["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner["args"] == {"depth": 1}


def test_disabled_tracer_is_noop(tmp_path):
    tr = obs.get_tracer()
    assert not tr.enabled
    with tr.span("x", a=1):
        tr.instant("y")
        tr.counter("z", v=1)
    # no file, no error — and the span object is the shared singleton
    assert tr.span("a") is tr.span("b")


def test_load_events_tolerates_torn_lines(tmp_path):
    path = _trace_file(tmp_path)
    tr = obs.configure(path)
    tr.instant("ok")
    obs.disable()
    with open(path, "a") as f:
        f.write('{"ev": "instant", "name": "torn", "ts"')  # crashed writer
    events, corrupt = obs.load_events(path)
    assert len(events) == 1 and corrupt == 1


# --------------------------------------------------------------------- #
# metrics
# --------------------------------------------------------------------- #
def test_histogram_empty_is_all_zero():
    h = Histogram("x")
    assert h.summary() == {"count": 0, "mean": 0.0, "min": 0.0, "max": 0.0,
                           "p50": 0.0, "p95": 0.0, "p99": 0.0}


def test_histogram_windowed_percentiles():
    h = Histogram("x", window=10)
    h.extend(range(100))           # only 90..99 retained
    assert h.count == 100          # lifetime count survives the window
    assert h.percentile(0.0) == 90.0
    assert h.percentile(1.0) == 99.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.5


def test_metrics_snapshot_roundtrip():
    m = Metrics()
    m.counter("hits")
    m.counter("hits", 2)
    m.gauge("depth", 7)
    m.observe("lat_s", 0.5)
    snap = m.snapshot()
    assert snap["counters"]["hits"] == 3
    assert snap["gauges"]["depth"] == 7.0
    assert snap["histograms"]["lat_s"]["count"] == 1
    m.reset()
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


# --------------------------------------------------------------------- #
# bit-identity: tracing must observe, never steer
# --------------------------------------------------------------------- #
def _evolve_result(engine, cfg):
    _, _, _, model, batch_model, space = _problem()
    if engine == "object":
        return evolve(TilingProblem(space, model, batch=False), cfg)
    if engine == "numpy":
        return evolve(TilingProblem(space, model, batch_model=batch_model),
                      cfg)
    return evolve(TilingProblem(space, model, batch_model=batch_model),
                  cfg, engine="jax")


@pytest.mark.parametrize("engine", ["object", "numpy", "jax"])
def test_tracing_is_bit_identical(engine, tmp_path):
    if engine == "jax":
        from repro.core import jax_engine_unavailable_reason
        reason = jax_engine_unavailable_reason()
        if reason is not None:
            pytest.skip(reason)
    off = _evolve_result(engine, CFG)
    obs.configure(_trace_file(tmp_path))
    on = _evolve_result(engine, CFG)
    obs.disable()
    assert on.best.key() == off.best.key()
    assert on.best_fitness == off.best_fitness
    assert on.evals == off.evals
    # whole trace, not just the winner (seconds excluded: wall-clock)
    assert [(t.evals, t.best_fitness) for t in on.trace] \
        == [(t.evals, t.best_fitness) for t in off.trace]


def test_traced_sweep_report_is_bit_identical(tmp_path):
    wl = mm_validation()
    sess = SessionConfig(executor="serial", early_abort=False)
    off = SearchSession(wl, cfg=CFG, session=sess).run()
    obs.configure(_trace_file(tmp_path))
    on = SearchSession(wl, cfg=CFG, session=sess).run()
    obs.disable()
    assert [(r.design.label(), r.latency_cycles, r.evo.evals)
            for r in on.results] \
        == [(r.design.label(), r.latency_cycles, r.evo.evals)
            for r in off.results]


# --------------------------------------------------------------------- #
# process safety: one JSONL sink across a pool's workers
# --------------------------------------------------------------------- #
def _pool_trace(tmp_path, start_method):
    path = _trace_file(tmp_path, f"{start_method}.trace.jsonl")
    obs.configure(path, process_name="sweep")
    rep = SearchSession(
        mm_validation(), cfg=CFG,
        session=SessionConfig(executor="process", max_workers=2,
                              early_abort=False,
                              start_method=start_method)).run()
    obs.disable()
    return path, rep


# run in a fresh interpreter: forking is only safe while the parent is
# jax-free, and earlier tests in this module import jax
_FORK_SWEEP = """
import os, sys
from repro import obs
from repro.core import EvoConfig, SearchSession, SessionConfig, mm_validation
obs.configure(sys.argv[1], process_name="sweep")
rep = SearchSession(
    mm_validation(), cfg=EvoConfig(epochs=6, population=16, seed=0),
    session=SessionConfig(executor="process", max_workers=2,
                          early_abort=False, start_method="fork")).run()
obs.disable()
print(len(rep.results), os.getpid())
"""


@pytest.mark.parametrize("start_method", ["fork", "spawn"])
def test_pool_workers_share_one_sink(start_method, tmp_path):
    if start_method not in multiprocessing.get_all_start_methods():
        pytest.skip(f"{start_method} unavailable")
    path = _trace_file(tmp_path, f"{start_method}.trace.jsonl")
    if start_method == "fork":
        out = _run_cli(["-c", _FORK_SWEEP, path])
        assert out.returncode == 0, out.stderr
        n_designs, parent_pid = map(int, out.stdout.split())
    else:
        path, rep = _pool_trace(tmp_path, start_method)
        n_designs, parent_pid = len(rep.results), os.getpid()
    # every line parses: O_APPEND atomic writes, no interleaving tears
    events, corrupt = obs.load_events(path)
    assert corrupt == 0
    with open(path) as f:
        for line in f:
            json.loads(line)       # raises on torn lines
    pids = {e["pid"] for e in events}
    assert parent_pid in pids      # parent (sweep span, instants)
    assert len(pids) >= 2          # and at least one worker
    spans = [e for e in events if e["ev"] == "span"]
    per_design = [e for e in spans if e["name"] == "design"]
    assert len(per_design) == n_designs
    # worker events carry the emitting process, not the parent
    assert {e["pid"] for e in per_design} - {parent_pid}


# --------------------------------------------------------------------- #
# Perfetto export: structural validation on real runs
# --------------------------------------------------------------------- #
def _assert_perfetto_valid(doc):
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["traceEvents"], "empty trace"
    by_track = {}
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "i", "C", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            continue
        assert ev["ts"] >= 0.0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
            by_track.setdefault((ev["pid"], ev["tid"]), []).append(ev)
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    # span nesting balances: within a track, sorted complete events must
    # strictly nest or be disjoint — a partial overlap means an unbalanced
    # (torn) span pair
    for track in by_track.values():
        track.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for ev in track:
            end = ev["ts"] + ev["dur"]
            while stack and ev["ts"] >= stack[-1] - 1e-6:
                stack.pop()
            if stack:
                assert end <= stack[-1] + 1e-6, \
                    f"span {ev['name']} overlaps its parent"
            stack.append(end)


def test_perfetto_from_real_sweep(tmp_path):
    path, rep = _pool_trace(tmp_path, None)    # auto-picked start method
    events, _ = obs.load_events(path)
    doc = obs.to_perfetto(events)
    _assert_perfetto_valid(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"sweep", "design", "evolve.gen"} <= names
    # process_name metadata emitted once per pid
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(metas) == len({m["pid"] for m in metas}) >= 1


def test_perfetto_from_real_serving_run(tmp_path):
    from repro.serve import ServeConfig, make_engine
    from repro.serve.sim import countdown_model, poisson_requests
    path = _trace_file(tmp_path, "serve.trace.jsonl")
    obs.configure(path, process_name="serve")
    model = countdown_model(32, work_dim=32)
    eng = make_engine("continuous", model, model.init(None),
                      ServeConfig(max_batch=2, max_seq=64, eos_token=0,
                                  prefill_chunk=4))
    reqs = poisson_requests(4, rate_rps=0.0, vocab_size=32,
                            prompt_len=range(2, 6), max_new_tokens=8,
                            seed=0)
    outs, stats = eng.serve(reqs)
    obs.disable()
    events, corrupt = obs.load_events(path)
    assert corrupt == 0
    doc = obs.to_perfetto(events)
    _assert_perfetto_valid(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"serve.prefill_chunk", "serve.decode_tick", "serve.slots",
            "serve.queue_depth", "serve.admit", "serve.finish"} <= names
    finishes = [e for e in doc["traceEvents"] if e["name"] == "serve.finish"]
    assert len(finishes) == len(stats.requests) == 4
    # the summarizer renders the same stream without raising
    text = obs.format_summary(obs.summarize(events))
    assert "serve.decode_tick" in text


def test_summarize_aggregates_counters_and_categories(tmp_path):
    """Counter series get min/max/count/last digests and spans roll up
    per category, so `calib` time is visible next to `search`/`serve`."""
    path = _trace_file(tmp_path)
    tr = obs.configure(path, process_name="t")
    with tr.span("design.evolve", cat="search"):
        pass
    with tr.span("calib.measure", cat="calib"):
        with tr.span("calib.run", cat="calib"):
            pass
    tr.counter("calibration", measured=0, interpret=1)
    tr.counter("calibration", measured=2, interpret=1)
    tr.counter("calibration", measured=3, interpret=5)
    obs.disable()
    events, corrupt = obs.load_events(path)
    assert corrupt == 0
    summary = obs.summarize(events)
    cats = summary["categories"]
    assert cats["search"]["count"] == 1 and cats["calib"]["count"] == 2
    assert cats["calib"]["total_us"] >= cats["calib"]["mean_us"] >= 0
    series = summary["counters"]["calibration"]
    assert series["measured"] == {"min": 0.0, "max": 3.0, "count": 3,
                                  "last": 3.0}
    assert series["interpret"]["count"] == 3
    assert series["interpret"]["last"] == 5.0
    text = obs.format_summary(summary)
    assert "by category:" in text and "calib=" in text and "search=" in text
    assert "n=3 last=3" in text
    # the perfetto export still renders the same stream
    _assert_perfetto_valid(obs.to_perfetto(events))


# --------------------------------------------------------------------- #
# serving stats (satellite 1)
# --------------------------------------------------------------------- #
def test_serve_stats_zero_requests_is_well_formed():
    from repro.serve.stats import ServeStats
    stats = ServeStats(scheduler="continuous", requests=[], wall_s=0.0,
                       engine="ContinuousServingEngine")
    d = stats.to_dict()
    assert d["requests"] == 0
    assert d["throughput_tps"] == 0.0
    assert d["ttft_s_p50"] == d["ttft_s_p95"] == 0.0
    assert d["rolling"]["ttft_s"]["count"] == 0
    assert d["finish_reasons"] == {} and d["per_request"] == []
    assert json.loads(json.dumps(d)) == d      # finite, serializable
    assert "0 requests" in stats.summary()


def test_serve_stats_provenance_and_rolling():
    from repro.serve.stats import RequestMetrics, ServeStats
    reqs = [RequestMetrics(request_id=i, prompt_len=4, new_tokens=8,
                           queue_wait_s=0.01, ttft_s=0.02 * (i + 1),
                           decode_s=0.07, finish_reason="length")
            for i in range(5)]
    stats = ServeStats(scheduler="continuous", requests=reqs, wall_s=1.0,
                       engine="ContinuousServingEngine")
    d = stats.to_dict()
    assert d["engine"] == "ContinuousServingEngine"
    assert all(r["scheduler"] == "continuous"
               and r["engine"] == "ContinuousServingEngine"
               for r in d["per_request"])
    roll = stats.rolling(window=3)             # only the last 3 retained
    assert roll["ttft_s"]["count"] == 5
    assert roll["ttft_s"]["min"] == pytest.approx(0.06)
    assert roll["decode_tps"]["p50"] == pytest.approx(7 / 0.07)


def test_decode_tps_never_inf():
    from repro.serve.stats import RequestMetrics
    m = RequestMetrics(request_id=0, prompt_len=1, new_tokens=5,
                       queue_wait_s=0.0, ttft_s=0.0, decode_s=0.0,
                       finish_reason="length")
    assert m.decode_tps == 0.0


# --------------------------------------------------------------------- #
# CLI surfaces
# --------------------------------------------------------------------- #
def _run_cli(args, cwd="/root/repo"):
    env = dict(os.environ, PYTHONPATH=os.path.join(cwd, "src"))
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, env=env, cwd=cwd)


def test_obs_cli_summarize_and_to_perfetto(tmp_path):
    path = _trace_file(tmp_path)
    tr = obs.configure(path, process_name="cli")
    with tr.span("work", cat="t"):
        tr.counter("x", v=1)
    obs.disable()
    out = _run_cli(["-m", "repro.obs", "summarize", path])
    assert out.returncode == 0 and "work" in out.stdout
    out = _run_cli(["-m", "repro.obs", "to-perfetto", path,
                    "--out", str(tmp_path / "out.json")])
    assert out.returncode == 0
    doc = json.load(open(tmp_path / "out.json"))
    _assert_perfetto_valid(doc)
    out = _run_cli(["-m", "repro.obs", "summarize",
                    str(tmp_path / "missing.jsonl")])
    assert out.returncode == 1


def test_bench_only_unknown_name_fails(tmp_path):
    out = _run_cli(["-m", "benchmarks.run", "--only", "not_a_bench"])
    assert out.returncode != 0
    assert "unknown bench" in out.stderr
    assert "search_speed" in out.stderr      # lists the valid names


def test_registry_list_stats_column(tmp_path):
    from repro.registry import RegistryStore
    root = str(tmp_path / "reg")
    store = RegistryStore(root)
    sess = SearchSession(mm_validation(), cfg=CFG, registry=store,
                         session=SessionConfig(executor="serial",
                                               early_abort=False))
    sess.run()
    SearchSession(mm_validation(), cfg=CFG, registry=store,
                  session=SessionConfig(executor="serial",
                                        early_abort=False)).run()  # 1 hit
    out = _run_cli(["-m", "repro.registry", "list", "--stats",
                    "--root", root])
    assert out.returncode == 0
    header, row = out.stdout.splitlines()[:2]
    assert "engine" in header and "hits" in header
    assert "numpy" in row
    assert "# hits: total=1" in out.stdout
    # without --stats the classic layout is unchanged
    out = _run_cli(["-m", "repro.registry", "list", "--root", root])
    assert "engine" not in out.stdout.splitlines()[0]
