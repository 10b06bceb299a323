"""The program's own names on the profiler's clock.

* ``repro.obs`` spans and instants land on the host plane of a JAX
  profiler trace while a session is active, and the disabled span stays
  the shared no-op object;
* the compile counter records JAX's compile events, stamped, in
  ``obs.Metrics``: a fresh jit and a persistent-cache retrieval;
* the tuner counts its sources and times its resolutions in
  ``obs.Metrics`` and spans each resolution;
* the tuned GEMM's custom call is named per class
  (``matmul_{M}x{N}x{K}_{bm}x{bk}x{bn}_{ki|ko}``);
* the benchmark's readers of these names and counters.

``bench/tests/data/tpu_v5e_named.xplane.pb`` was recorded on one TPU v5e
with the named kernels: one layer of starcoder2-7b at M 4096 (its five
GEMM classes, seven calls a pass through ``ops.matmul_op`` in one jitted
``bench_gemm``), compiled before the profiler started; inside the trace,
the five classes resolved again against a fresh registry (the
``tuner.resolve`` spans), then three passes, each waited for.
"""

import json
import sys
import time
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:       # the benchmark's package, ``bench``
    sys.path.insert(0, str(REPO))

from bench import counters, trace  # noqa: E402
from bench.device import PEAKS  # noqa: E402
from bench.harness import Run  # noqa: E402
from bench.metrics import (compile_s, matmul_class_roofline_min,  # noqa: E402
                           matmul_roofline, tuner_resolve_s)
from repro import obs  # noqa: E402
from repro.kernels import MatmulConfig, ops  # noqa: E402
from repro.kernels.autotune import (reset_config_lru,  # noqa: E402
                                    resolve_matmul_config, tuner_counts)
from repro.kernels.matmul import kernel_name  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.registry import RegistryStore  # noqa: E402

DATA = REPO / "bench" / "tests" / "data"
NAMED = DATA / "tpu_v5e_named.xplane.pb"
PROBE = DATA / "tpu_v5e_probe.xplane.pb"
STARCODER2 = json.loads((REPO / "bench" / "configs" /
                         "starcoder2-7b.json").read_text())
PREFILL = json.loads((REPO / "bench" / "traffic" /
                      "gemm-prefill.json").read_text())
# the five GEMM classes (M, N, K) of a starcoder2-7b prefill step
PREFILL_CLASSES = [(4096, 4608, 4608), (4096, 512, 4608),
                   (4096, 18432, 4608), (4096, 4608, 18432),
                   (4096, 49152, 4608)]


@pytest.fixture(autouse=True)
def _tracer_disabled():
    obs.disable()
    yield
    obs.disable()


def _host_events(path):
    """(name, stats) of every host-plane event of a profiler trace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out += [(e.name, dict(e.stats)) for e in line.events]
    return out


# --------------------------------------------------------------------- #
# obs -> profiler sink
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("jsonl", [False, True], ids=["profiler", "both"])
def test_span_lands_on_host_plane_only_in_a_session(tmp_path, jsonl):
    if jsonl:
        obs.configure(str(tmp_path / "run.trace.jsonl"))
    tr = obs.get_tracer()
    with tr.span("obs.test.before"):
        pass
    log_dir = str(tmp_path / "prof")
    trace.start(log_dir)
    try:
        with tr.span("obs.test.span", cat="t", M=4096) as span:
            span.set(source="tuned")
            tr.instant("obs.test.instant", n=2)
    finally:
        path = trace.stop(log_dir)
    with tr.span("obs.test.after"):
        pass
    events = _host_events(path)
    names = [n for n, _ in events]
    assert names.count("obs.test.span") == 1
    assert names.count("obs.test.instant") == 1
    assert "obs.test.before" not in names and "obs.test.after" not in names
    stats = dict(events)["obs.test.span"]
    assert stats["M"] == 4096 and stats["source"] == "tuned"
    # the benchmark's reduction sees the span on the Python thread
    _, host = trace.read(path)
    assert "obs.test.span" in {h[0] for h in host}
    if jsonl:
        obs.disable()
        spans = [e for e in obs.load_events(str(tmp_path /
                                                "run.trace.jsonl"))[0]
                 if e["ev"] == "span"]
        assert [e["name"] for e in spans] == [
            "obs.test.before", "obs.test.span", "obs.test.after"]
        assert spans[1]["args"] == {"M": 4096, "source": "tuned"}


def test_disabled_span_is_the_null_object():
    tr = obs.get_tracer()
    assert not tr.enabled
    span = tr.span("x", cat="c", a=1)
    assert span is obs_trace._NULL_SPAN is tr.span("y")
    span.set(b=2)                       # no-op, never raises
    tr.instant("z", n=1)


# --------------------------------------------------------------------- #
# compile counter
# --------------------------------------------------------------------- #
def _count(name):
    h = obs.get_metrics().histograms.get(name)
    return h.count if h is not None else 0


def test_compile_counter_records_a_fresh_jit():
    obs.install_compile_listener()
    names = ("compile.jaxpr_trace_s", "compile.jaxpr_to_mlir_module_s",
             "compile.backend_compile_s")
    before = {n: _count(n) for n in names}
    t0 = time.perf_counter()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    t1 = time.perf_counter()
    for n in names:
        assert _count(n) > before[n], n
        stamp, seconds = obs.get_metrics().histograms[n].stamped()[-1]
        assert t0 <= stamp <= t1 and 0 <= seconds <= t1 - t0


def test_compile_counter_records_a_persistent_cache_retrieval(tmp_path):
    from jax._src import compilation_cache
    obs.install_compile_listener()
    prior = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    try:
        def f(x):
            return jnp.sin(x) * 5.25 + 0.5
        jax.jit(f)(jnp.arange(5.0)).block_until_ready()     # written
        before = _count("compile.cache_retrieval_s")
        jax.clear_caches()
        jax.jit(f)(jnp.arange(5.0)).block_until_ready()     # read back
        assert _count("compile.cache_retrieval_s") > before
    finally:
        jax.config.update("jax_compilation_cache_dir", prior[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prior[1])
        compilation_cache.reset_cache()


# --------------------------------------------------------------------- #
# tuner spans and counters
# --------------------------------------------------------------------- #
def test_tuner_counts_the_cell_classes_by_source(tmp_path):
    reset_config_lru()
    store = RegistryStore(str(tmp_path))
    timed0 = _count("tuner.resolve_s")
    first = tuner_counts()
    for M, N, K in PREFILL_CLASSES:
        resolve_matmul_config(M, N, K, registry=store)
    second = tuner_counts()
    for M, N, K in PREFILL_CLASSES:
        resolve_matmul_config(M, N, K, registry=store)
    third = tuner_counts()
    spent = second["evals"] - first["evals"]
    assert spent > 0
    assert {c: second[c] - first[c] for c in first} == \
        {"tuned": 5, "disk_hits": 0, "lru_hits": 0, "evals": spent}
    assert {c: third[c] - second[c] for c in first} == \
        {"tuned": 0, "disk_hits": 0, "lru_hits": 5, "evals": 0}
    assert _count("tuner.resolve_s") == timed0 + 10


def test_tuner_resolve_span_carries_source_and_evals(tmp_path):
    path = str(tmp_path / "tuner.trace.jsonl")
    reset_config_lru()
    store = RegistryStore(str(tmp_path / "reg"))
    obs.configure(path)
    resolve_matmul_config(512, 512, 512, registry=store, evals=300)
    reset_config_lru()
    resolve_matmul_config(512, 512, 512, registry=store, evals=300)
    cfg = resolve_matmul_config(512, 512, 512, registry=store, evals=300)
    obs.disable()
    spans = [e for e in obs.load_events(path)[0]
             if e["ev"] == "span" and e["name"] == "tuner.resolve"]
    assert len(spans) == 3
    steps = -(-512 // cfg.bm) * -(-512 // cfg.bn) * -(-512 // cfg.bk)
    assert [s["args"]["steps"] for s in spans] == [steps] * 3
    assert [s["args"]["source"] for s in spans] == \
        ["tuned", "disk_hits", "lru_hits"]
    assert spans[0]["args"]["evals"] > 0
    assert [s["args"]["evals"] for s in spans[1:]] == [0, 0]
    assert spans[0]["args"]["M"] == 512 and spans[0]["cat"] == "tuner"


# --------------------------------------------------------------------- #
# kernel names
# --------------------------------------------------------------------- #
def test_kernel_name_of_a_class():
    assert kernel_name(4096, 4608, 4608, 512, 384, 1152, True) == \
        "matmul_4096x4608x4608_512x384x1152_ki"
    assert kernel_name(16, 512, 4608, 16, 4608, 512, False) == \
        "matmul_16x512x4608_16x4608x512_ko"


def test_two_classes_lower_with_their_names():
    """The scope reaches the lowered program of the CPU's interpreted
    kernel too; ``test_chip_compile`` checks the instruction names that
    the TPU compiler gives the custom calls."""
    one = MatmulConfig(bm=8, bk=128, bn=128, interpret=True)
    two = MatmulConfig(bm=16, bk=128, bn=256, k_innermost=False,
                       interpret=True)

    def step(a, b, c):
        return ops.matmul_op(a, b, one), ops.matmul_op(a, c, two)
    args = (jnp.ones((16, 256), jnp.bfloat16),
            jnp.ones((256, 128), jnp.bfloat16),
            jnp.ones((256, 256), jnp.bfloat16))
    text = jax.jit(step).lower(*args).as_text(debug_info=True)
    assert "matmul_16x128x256_8x128x128_ki" in text
    assert "matmul_16x256x256_16x128x256_ko" in text


# --------------------------------------------------------------------- #
# the benchmark's readers
# --------------------------------------------------------------------- #
def _run(summary=None, passes=3, layers=1, t_process=0.0, setup_s=0.0):
    cfg = dict(STARCODER2, num_hidden_layers=layers)
    return Run(workload="starcoder2-7b.gemm-prefill", seed=0, seconds=1.0,
               trace=True, config=cfg, traffic=PREFILL, t_process=t_process,
               peak=PEAKS["TPU v5 lite"], setup_s=setup_s, summary=summary,
               counts={"passes": passes})


def test_class_roofline_on_the_named_v5e_trace():
    assert NAMED.stat().st_size <= 100_000
    summary = trace.reduce_file(str(NAMED))
    run = _run(summary)
    run.counts["pass_min_s"] = sum(
        counters.gemm_min_seconds(s, run.peak) * c
        for s, c in counters.gemm_classes(run.config, 4096))
    shares = matmul_class_roofline_min.class_rooflines(run)
    # k/v (512 columns) holds under 5% of one layer's kernel time
    assert set(shares) >= {(4096, 4608, 4608), (4096, 18432, 4608),
                           (4096, 4608, 18432), (4096, 49152, 4608)}
    assert all(0 < v <= 100 for v in shares.values())
    low = matmul_class_roofline_min.read(run)
    whole = matmul_roofline.read(run)
    assert low == min(shares.values()) <= whole
    # the classes' time-weighted mean is the whole kernels' roofline
    times = matmul_class_roofline_min.class_times(summary)
    kept = sum(times[s] for s in shares)
    mean = sum(shares[s] * times[s] for s in shares) / kept
    assert abs(mean - whole) <= 1.5 + 100 * (1 - kept / sum(times.values()))


def test_tuner_spans_on_the_named_v5e_trace():
    _, host = trace.read(str(NAMED))
    resolves = [h for h in host if h[0] == "tuner.resolve"]
    assert len(resolves) == 5 and all(e > s for _, s, e in resolves)


def test_class_roofline_is_none_without_kernel_names():
    assert matmul_class_roofline_min.read(
        _run(trace.reduce_file(str(PROBE)))) is None
    assert matmul_class_roofline_min.read(_run(None)) is None


def test_compile_and_tuner_readers_read_nothing_from_a_bare_program(
        monkeypatch):
    metrics = obs.Metrics()             # a program that records none
    monkeypatch.setattr(obs, "get_metrics", lambda: metrics)
    run = _run(t_process=0.0, setup_s=20.0)
    assert compile_s.read(run) is None
    assert tuner_resolve_s.read(run) is None
    metrics.observe("tuner.resolve_s", 0.25)
    metrics.observe("tuner.resolve_s", 0.5)
    assert tuner_resolve_s.read(run) == pytest.approx(0.75)


def test_compile_seconds_are_a_union_of_nested_events(monkeypatch):
    # a trace holding a nested trace; a backend compile holding its
    # cache retrieval; a gap between them; one compile after set-up.
    # Each observation is (end stamp, seconds).
    class Stamped:
        def __init__(self, *pairs):
            self.pairs = list(pairs)

        def stamped(self):
            return self.pairs

    histograms = {
        "compile.jaxpr_trace_s": Stamped((10.0, 2.0), (9.0, 0.5)),
        "compile.backend_compile_s": Stamped((14.0, 3.0), (25.0, 1.0)),
        "compile.cache_retrieval_s": Stamped((13.5, 1.0)),
        "tuner.resolve_s": Stamped((12.0, 4.0)),    # not a compile
    }
    monkeypatch.setattr(obs, "get_metrics",
                        lambda: type("M", (), {"histograms": histograms}))
    assert compile_s.read(_run(setup_s=20.0)) == pytest.approx(5.0)
    assert compile_s.read(_run(setup_s=5.0)) == 0.0


def test_compile_reader_sees_this_process_compiles():
    obs.install_compile_listener()
    t0 = time.perf_counter()
    jax.jit(lambda x: x - 7)(jnp.arange(3.0)).block_until_ready()
    t1 = time.perf_counter()
    before = compile_s.read(_run(setup_s=t0)) or 0.0
    after = compile_s.read(_run(setup_s=t1))
    assert 0 < after - before <= t1 - t0
