"""Design registry: store round-trip, migration, fingerprints, fast paths.

Covers the DESIGN.md §9 contracts: records survive a round-trip, corrupt
and old-schema records never crash a lookup, fingerprints are stable
across processes, an exact hit runs zero evolutionary evaluations, a
transfer-seeded warm start reaches 90%-of-best in at most half the
cold-start evaluations, and two sessions in separate processes share
results through the on-disk store.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.core import (EvoConfig, SearchSession, SessionConfig, U250,
                        TPU_V5E, matmul, tune_design, pruned_permutations)
from repro.registry import (Record, RegistryStore, SCHEMA_VERSION,
                            TuningService, matmul_block_fingerprint,
                            report_from_record, transfer_seeds,
                            workload_fingerprint)

CFG = EvoConfig(epochs=6, population=16, parents=8, elites=2, seed=0)


def tiny_session(wl, store, cfg=CFG, **kw):
    return SearchSession(wl, cfg=cfg, use_mp_seed=False, registry=store,
                         session=SessionConfig(executor="serial"), **kw)


@pytest.fixture
def store(tmp_path):
    return RegistryStore(str(tmp_path / "registry"))


# ------------------------------------------------------------------ #
# Store: round-trip, corruption, migration, eviction
# ------------------------------------------------------------------ #
def make_record(digest="ab" * 32, workload="wl", latency=100.0,
                **overrides) -> Record:
    payload = dict(
        fingerprint=digest, family="fam", features=[6.0, 6.0, 6.0],
        workload=workload, kind="systolic", hardware="u250",
        best={"latency_cycles": latency, "feasible": True},
        pareto=[], evals=10, seconds=0.5)
    payload.update(overrides)
    return Record(**payload)


def test_store_round_trip(store):
    rec = store.put(make_record())
    got = store.get(rec.fingerprint)
    assert got is not None
    assert got.to_json() == rec.to_json()
    assert len(store) == 1 and store.keys() == [rec.fingerprint]


def test_store_keep_best_merge(store):
    store.put(make_record(latency=50.0, evals=99))
    kept = store.put(make_record(latency=80.0, evals=10))
    assert kept.best["latency_cycles"] == 50.0      # better record survives
    assert kept.evals == 99
    worse_gone = store.put(make_record(latency=20.0), keep_best=True)
    assert worse_gone.best["latency_cycles"] == 20.0

    # an infeasible incumbent never beats a feasible newcomer
    store2 = RegistryStore(os.path.join(store.root, "sub"))
    store2.put(make_record(latency=1.0,
                           best={"latency_cycles": 1.0, "feasible": False}))
    merged = store2.put(make_record(latency=500.0))
    assert merged.best["feasible"]


def test_corrupt_record_is_quarantined(store):
    rec = store.put(make_record())
    path = store._path(rec.fingerprint)
    with open(path, "w") as f:
        f.write("{not json")
    assert store.get(rec.fingerprint) is None       # no crash
    assert os.path.exists(path + ".corrupt")        # evidence preserved
    assert store.get(rec.fingerprint) is None       # still clean


def test_old_schema_record_is_migrated(store):
    rec = make_record()
    payload = rec.to_json()
    payload["schema_version"] = 1
    del payload["pareto"], payload["hits"]          # v1 predates both
    path = store._path(rec.fingerprint)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f)
    got = store.get(rec.fingerprint)
    assert got is not None
    assert got.schema_version == SCHEMA_VERSION
    assert got.pareto == [] and got.hits == 0
    assert got.engine == "numpy"                    # v3 provenance default


def test_v2_record_migrates_engine_default(store):
    """v2 records (pre compiled-engine) gain engine='numpy' on read, and
    the provenance round-trips from a report through the record."""
    rec = make_record()
    payload = rec.to_json()
    payload["schema_version"] = 2
    del payload["engine"]                           # v2 predates the field
    path = store._path(rec.fingerprint)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f)
    got = store.get(rec.fingerprint)
    assert got is not None
    assert got.schema_version == SCHEMA_VERSION
    assert got.engine == "numpy"


def test_engine_provenance_round_trips_through_registry(store):
    """A sweep's evaluator provenance lands in the record and survives
    the exact-hit reconstruction back into a report."""
    wl = matmul(64, 64, 64)
    sess = tiny_session(wl, store)
    report = sess.run()
    assert report.engine == "numpy"                 # default engine
    fp = workload_fingerprint(wl, U250)
    rec = store.get(fp)
    assert rec is not None and rec.engine == "numpy"
    cached = report_from_record(rec, wl, U250)
    assert cached.from_cache and cached.engine == "numpy"


def test_future_schema_record_is_quarantined(store):
    rec = make_record()
    payload = rec.to_json()
    payload["schema_version"] = SCHEMA_VERSION + 7
    path = store._path(rec.fingerprint)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f)
    assert store.get(rec.fingerprint) is None
    assert os.path.exists(path + ".corrupt")


def test_v2_record_migrates_full_chain_in_one_get(store):
    """A v2 payload walks v2->v3->v4 on a single read: engine default
    from the v3 step, empty measurement history from the v4 step."""
    rec = make_record()
    payload = rec.to_json()
    payload["schema_version"] = 2
    for field in ("engine", "measurements", "measured_us",
                  "measure_backend", "rel_err"):
        del payload[field]                          # v2 predates all four
    path = store._path(rec.fingerprint)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f)
    got = store.get(rec.fingerprint)
    assert got is not None
    assert got.schema_version == SCHEMA_VERSION == 4
    assert got.engine == "numpy"                    # v2->v3
    assert got.measurements == []                   # v3->v4
    assert got.measured_us is None and got.measure_backend == ""
    assert got.rel_err is None


def _measurement(us=42.0, backend="interpret"):
    return {"workload": "wl", "family": "mm", "hardware": "u250",
            "design": "[i,j] <[i,j],k>", "genome": {"i": [1, 2, 4]},
            "predicted_us": 40.0, "measured_us": us, "backend": backend,
            "rel_err": abs(us - 40.0) / us, "measured_at": 1.0}


def test_keep_best_merge_preserves_measurements(store):
    """Ground truth survives the merge in both directions: a better
    unmeasured record must not drop the loser's measurement history or
    its measured_us summary, and vice versa."""
    measured = make_record(latency=80.0, measurements=[_measurement()],
                           measured_us=42.0, measure_backend="interpret",
                           rel_err=0.05)
    store.put(measured)
    merged = store.put(make_record(latency=50.0))   # better, unmeasured
    assert merged.best["latency_cycles"] == 50.0    # newcomer wins...
    assert merged.measurements == [_measurement()]  # ...truth survives
    assert merged.measured_us == 42.0
    assert merged.measure_backend == "interpret"
    assert merged.rel_err == 0.05
    # losing *incoming* record: its new measurements union in, the
    # incumbent keeps its own summary
    newer = _measurement(us=55.0, backend="hlo_estimate")
    worse = make_record(latency=90.0, measurements=[newer],
                        measured_us=55.0, measure_backend="hlo_estimate")
    merged2 = store.put(worse)
    assert merged2.best["latency_cycles"] == 50.0   # incumbent survives
    assert merged2.measurements == [_measurement(), newer]
    assert merged2.measured_us == 42.0              # own summary kept
    # duplicates collapse, disk round-trip keeps provenance intact
    store.put(worse)
    again = store.get(measured.fingerprint)
    assert again.measurements == [_measurement(), newer]
    assert again.schema_version == SCHEMA_VERSION


def test_evict_and_lru_trim(store):
    for i in range(4):
        store.put(make_record(digest=f"{i:02d}" * 32, workload=f"wl{i}"))
    assert store.evict("00" * 32) and not store.evict("00" * 32)
    dropped = store.evict_lru(max_records=2)
    assert len(dropped) == 1 and len(store) == 2


# ------------------------------------------------------------------ #
# Fingerprints
# ------------------------------------------------------------------ #
def test_fingerprint_stability_across_processes():
    fp = workload_fingerprint(matmul(64, 64, 64), U250)
    code = ("import sys; sys.path.insert(0, 'src'); "
            "from repro.core import matmul, U250; "
            "from repro.registry import workload_fingerprint; "
            "print(workload_fingerprint(matmul(64, 64, 64), U250).digest)")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.stdout.strip() == fp.digest


def test_fingerprint_sensitivity():
    fp = workload_fingerprint(matmul(64, 64, 64), U250)
    # bounds change identity but not the transfer family
    near = workload_fingerprint(matmul(128, 64, 64), U250)
    assert near.digest != fp.digest and near.family == fp.family
    assert near.distance(fp) == pytest.approx(1.0)
    # dtype and hardware change the family: never comparable
    assert workload_fingerprint(matmul(64, 64, 64, dtype="bf16"),
                                U250).family != fp.family
    assert workload_fingerprint(matmul(64, 64, 64),
                                TPU_V5E).family != fp.family
    # different kinds never collide either
    assert matmul_block_fingerprint(64, 64, 64, 4, U250).family != fp.family


# ------------------------------------------------------------------ #
# Exact-hit fast path + transfer warm start
# ------------------------------------------------------------------ #
def test_exact_hit_runs_zero_evals(store):
    wl = matmul(64, 64, 64)
    cold = tiny_session(wl, store).run()
    assert not cold.from_cache

    hit = tiny_session(wl, store).run()
    assert hit.from_cache
    assert sum(r.evo.evals for r in hit.results) == 0
    assert hit.best.latency_cycles == cold.best.latency_cycles
    assert hit.best.design.label() == cold.best.design.label()
    # hits are accounted on the stored record
    assert store.get(workload_fingerprint(wl, U250)).hits == 1


def _evals_to_quality(trace, target_fitness):
    for entry in trace:
        if entry.best_fitness >= target_fitness:
            return entry.evals
    return float("inf")


def test_transfer_seeded_warm_start_halves_evals_to_90(store):
    wl1 = matmul(1024, 1024, 1024)
    tiny_session(wl1, store,
                 cfg=EvoConfig(epochs=30, population=32, parents=8,
                               seed=0)).run()

    # the paper's 1024^3 winner warm-starts the neighboring 1000-row MM
    wl2 = matmul(1000, 1024, 1024)
    fp2 = workload_fingerprint(wl2, U250)
    seeds = transfer_seeds(store, fp2, wl2)
    assert seeds, "the 64^3 record must seed the neighboring 80^3 search"

    # warm-start the design the cached winner used
    from repro.registry.transfer import design_key
    best = store.get(workload_fingerprint(wl1, U250)).best
    from repro.core import Permutation
    df = tuple(best["dataflow"])
    perm = Permutation(outer=tuple(best["perm_outer"]),
                       inner=tuple(best["perm_inner"]))
    extra = tuple(seeds.get(design_key(df, perm), ()))
    assert extra, "winner design must carry over"

    cfg = EvoConfig(epochs=40, population=32, parents=8, seed=5)
    cold = tune_design(wl2, df, perm, cfg=cfg, use_mp_seed=False)
    warm = tune_design(wl2, df, perm, cfg=cfg, use_mp_seed=False,
                       extra_seeds=extra)
    best_f = max(cold.evo.best_fitness, warm.evo.best_fitness)
    target = best_f / 0.9                       # fitness = -latency
    cold_evals = _evals_to_quality(cold.evo.trace, target)
    warm_evals = _evals_to_quality(warm.evo.trace, target)
    assert warm_evals <= 0.5 * cold_evals, (warm_evals, cold_evals)


def test_cross_process_sessions_share_store(tmp_path):
    """Two SearchSessions in separate processes share the on-disk store."""
    root = str(tmp_path / "shared")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys; sys.path.insert(0, 'src');\n"
        "from repro.core import EvoConfig, SearchSession, SessionConfig, "
        "matmul\n"
        "from repro.registry import RegistryStore\n"
        f"store = RegistryStore({root!r})\n"
        "report = SearchSession(matmul(64, 64, 64),\n"
        "    cfg=EvoConfig(epochs=6, population=16, parents=8, elites=2,"
        " seed=0),\n"
        "    use_mp_seed=False, registry=store,\n"
        "    session=SessionConfig(executor='serial')).run()\n"
        "print('FROM_CACHE', report.from_cache)\n")
    first = subprocess.run([sys.executable, "-c", code], check=True,
                           capture_output=True, text=True, cwd=repo)
    assert "FROM_CACHE False" in first.stdout

    # second run, this process: a pure lookup
    report = tiny_session(matmul(64, 64, 64), RegistryStore(root)).run()
    assert report.from_cache
    assert sum(r.evo.evals for r in report.results) == 0


# ------------------------------------------------------------------ #
# TuningService
# ------------------------------------------------------------------ #
def test_service_lookup_and_background_tune(store):
    svc = TuningService(store)
    wl = matmul(32, 32, 32)
    assert svc.lookup(wl) is None
    assert svc.get_or_tune(wl, cfg=CFG, block=False,
                           use_mp_seed=False) is None
    assert svc.flush(timeout=120), "background worker must drain"
    rec = svc.lookup(wl)
    assert rec is not None and rec.evals > 0
    report = svc.get_or_tune(wl, cfg=CFG, block=False)
    assert report is not None and report.from_cache
    assert svc.stats["lru_hits"] >= 1
    svc.close()


def test_service_blocking_tune_records(store):
    svc = TuningService(store)
    wl = matmul(32, 32, 32)
    report = svc.get_or_tune(wl, cfg=CFG, block=True, use_mp_seed=False)
    assert report is not None and not report.from_cache
    again = svc.get_or_tune(wl, cfg=CFG)
    assert again.from_cache
    assert again.best.latency_cycles == report.best.latency_cycles


def test_report_reconstruction_matches_model(store):
    """Cached metrics must agree with a fresh model evaluation."""
    wl = matmul(64, 64, 64)
    cold = tiny_session(wl, store).run()
    rec = store.get(workload_fingerprint(wl, U250))
    report = report_from_record(rec, wl, U250)
    for r in report.results:
        assert r.model.latency_cycles(r.evo.best) == \
            pytest.approx(r.latency_cycles)
    assert report.best.latency_cycles == \
        pytest.approx(cold.best.latency_cycles)


# ------------------------------------------------------------------ #
# TPU block-shape resolution
# ------------------------------------------------------------------ #
def test_resolve_matmul_config_hits_registry(store):
    from repro.kernels.autotune import (_config_lru, resolve_matmul_config,
                                        tune_matmul)
    _config_lru.clear()
    cfg = resolve_matmul_config(512, 512, 512, registry=store, evals=300)
    fp = matmul_block_fingerprint(512, 512, 512, 2, TPU_V5E)
    rec = store.get(fp)
    assert rec is not None and rec.kind == "tpu_block"
    assert rec.best["bm"] == cfg.bm and rec.evals > 0

    _config_lru.clear()                  # force the disk path
    again = resolve_matmul_config(512, 512, 512, registry=store, evals=300)
    assert again == cfg
    assert store.get(fp).hits == 1

    _config_lru.clear()                  # neighbor seeds a nearby shape
    near = resolve_matmul_config(500, 512, 512, registry=store, evals=300)
    assert near is not None
    assert store.get(matmul_block_fingerprint(500, 512, 512, 2,
                                              TPU_V5E)) is not None
    assert tune_matmul(512, 512, 512, evals=300) == cfg  # legacy API intact


def test_tpu_block_records_of_an_older_model_are_not_served(store):
    """A ``tpu_block`` record tuned under an older latency model (its
    structure has no model version) is neither an exact hit nor a
    warm-start neighbor: the shape is tuned afresh."""
    import dataclasses
    from repro.kernels.autotune import _config_lru, resolve_matmul_config
    from repro.registry.fingerprint import FINGERPRINT_VERSION, _digest
    old = {"kind": "tpu_block", "version": FINGERPRINT_VERSION,
           "dtype_bytes": 2, "hw": dataclasses.asdict(TPU_V5E)}
    stale = {"bm": 128, "bk": 128, "bn": 128, "k_innermost": True,
             "latency_s": 1e-9, "feasible": True}
    for dims in ((512, 512, 512), (512, 512, 384)):
        store.put(Record(
            fingerprint=_digest(dict(old, dims=list(dims))),
            family=_digest(old), features=[9.0, 9.0, 9.0],
            workload="mm_old", kind="tpu_block", hardware=TPU_V5E.name,
            best=stale, pareto=[], evals=1))
    fp = matmul_block_fingerprint(512, 512, 512, 2, TPU_V5E)
    assert fp.family != _digest(old)
    assert store.get(fp) is None
    assert store.neighbors(fp, k=2) == []

    _config_lru.clear()
    resolve_matmul_config(512, 512, 512, registry=store, evals=300)
    assert store.get(fp).evals > 0          # tuned here, not served


def test_gmm_model_version_moves_gmm_digests_not_matmul_digests():
    """Grouped records carry a model version of their own: the grouped
    model's change re-tunes ``tpu_gmm_block`` records and leaves every
    ``tpu_block`` record valid."""
    import dataclasses
    from repro.registry import gmm_block_fingerprint
    from repro.registry.fingerprint import (FINGERPRINT_VERSION,
                                            TPU_BLOCK_MODEL_VERSION, _digest)
    # the digest of (4096, 18432, 4608) bf16 under matmul model 2
    assert matmul_block_fingerprint(4096, 18432, 4608, 2, TPU_V5E).digest \
        == "551484dcd51e19c25790caca03abb613482e6330c13b779a6f7755541fe30cf8"
    shared = {"kind": "tpu_gmm_block", "version": FINGERPRINT_VERSION,
              "model": TPU_BLOCK_MODEL_VERSION, "dtype_bytes": 2,
              "hw": dataclasses.asdict(TPU_V5E)}
    fp = gmm_block_fingerprint(32768, 896, 2304, 64, 2, TPU_V5E)
    assert fp.family != _digest(shared)
    assert fp.digest != _digest(dict(shared, dims=[32768, 896, 2304, 64]))


def test_gmm_records_of_the_shared_model_version_are_not_served(store):
    """A ``tpu_gmm_block`` record tuned when grouped records shared the
    matmul's model version is neither served nor a warm-start seed."""
    import dataclasses
    from repro.kernels.autotune import reset_config_lru, resolve_gmm_config
    from repro.registry import gmm_block_fingerprint
    from repro.registry.fingerprint import (FINGERPRINT_VERSION,
                                            TPU_BLOCK_MODEL_VERSION, _digest)
    old = {"kind": "tpu_gmm_block", "version": FINGERPRINT_VERSION,
           "model": TPU_BLOCK_MODEL_VERSION, "dtype_bytes": 2,
           "hw": dataclasses.asdict(TPU_V5E)}
    stale = {"bm": 424, "bk": 256, "bn": 256, "k_innermost": True,
             "latency_s": 1e-9, "feasible": True}
    store.put(Record(
        fingerprint=_digest(dict(old, dims=[512, 256, 256, 8])),
        family=_digest(old), features=[9.0, 8.0, 8.0, 3.0],
        workload="gmm_old", kind="tpu_gmm_block", hardware=TPU_V5E.name,
        best=stale, pareto=[], evals=1))
    fp = gmm_block_fingerprint(512, 256, 256, 8, 2, TPU_V5E)
    assert store.get(fp) is None
    assert store.neighbors(fp, k=2) == []

    reset_config_lru()
    cfg = resolve_gmm_config(512, 256, 256, 8, registry=store, evals=300)
    assert store.get(fp).evals > 0          # tuned here, not served
    assert (cfg.bm, cfg.bk, cfg.bn) != (424, 256, 256)


# ------------------------------------------------------------------ #
# CLI
# ------------------------------------------------------------------ #
def test_cli_list_show_evict_export(store, tmp_path, capsys):
    from repro.registry.__main__ import main
    rec = store.put(make_record())
    assert main(["--root", store.root, "list"]) == 0
    out = capsys.readouterr().out
    assert rec.fingerprint[:12] in out and "1 record(s)" in out

    assert main(["--root", store.root, "show", rec.fingerprint[:8]]) == 0
    shown = json.loads(capsys.readouterr().out)
    assert shown["fingerprint"] == rec.fingerprint

    export = str(tmp_path / "dump.json")
    assert main(["--root", store.root, "export", "--out", export]) == 0
    capsys.readouterr()
    with open(export) as f:
        assert json.load(f)[0]["fingerprint"] == rec.fingerprint

    assert main(["--root", store.root, "evict", rec.fingerprint[:8]]) == 0
    capsys.readouterr()
    assert len(store) == 0
    assert main(["--root", store.root, "show", "doesnotexist"]) == 1


def test_divisors_only_is_a_separate_cache_family(store):
    """A divisor-restricted search must never be served (or seeded) from
    an unrestricted record, and vice versa."""
    wl = matmul(64, 64, 64)
    full = tiny_session(wl, store).run()
    assert not full.from_cache
    restricted = tiny_session(wl, store, divisors_only=True).run()
    assert not restricted.from_cache          # unrestricted hit not reused
    for r in restricted.results:
        g = r.evo.best
        for loop in wl.loop_names:
            assert wl.loop(loop).bound % g.t1(loop) == 0
    # both variants now cached, independently
    assert tiny_session(wl, store).run().from_cache
    assert tiny_session(wl, store, divisors_only=True).run().from_cache
    fp_full = workload_fingerprint(wl, U250)
    fp_div = workload_fingerprint(wl, U250,
                                  variant={"divisors_only": True})
    assert fp_full.family != fp_div.family


def test_partial_design_sweep_bypasses_registry(store):
    """A sweep over a hand-picked design subset neither records under the
    workload fingerprint nor serves from it."""
    from repro.core import enumerate_designs
    wl = matmul(64, 64, 64)
    subset = enumerate_designs(wl)[:2]
    partial = tiny_session(wl, store, designs=subset).run()
    assert not partial.from_cache
    assert len(store) == 0                     # nothing recorded
    full = tiny_session(wl, store).run()       # not served from a partial
    assert not full.from_cache and len(store) == 1


def test_exact_hit_reconstructs_full_sweep(store):
    """A hit returns one result per swept design (not just the frontier)."""
    wl = matmul(64, 64, 64)
    cold = tiny_session(wl, store).run()
    hit = tiny_session(wl, store).run()
    assert hit.from_cache
    assert len(hit.results) == len(cold.results) == 18
    cold_labels = sorted(r.design.label() for r in cold.results)
    assert sorted(r.design.label() for r in hit.results) == cold_labels


def test_refresh_reruns_and_keeps_best(store):
    wl = matmul(64, 64, 64)
    first = tiny_session(wl, store).run()
    # a cheaper refresh re-runs the sweep but cannot clobber the winner
    worse_cfg = EvoConfig(epochs=1, population=8, parents=4, seed=9)
    refreshed = tiny_session(wl, store, cfg=worse_cfg, refresh=True).run()
    assert not refreshed.from_cache
    rec = store.get(workload_fingerprint(wl, U250))
    assert rec.best["latency_cycles"] <= first.best.latency_cycles


def test_transfer_seeds_respect_divisors_only(store):
    """Seeds handed to a divisor-constrained search are divisor-legal."""
    wl1 = matmul(48, 48, 48)
    tiny_session(wl1, store, divisors_only=True).run()
    wl2 = matmul(50, 50, 50)
    fp2 = workload_fingerprint(wl2, U250,
                               variant={"divisors_only": True})
    seeds = transfer_seeds(store, fp2, wl2, divisors_only=True)
    assert seeds
    for genomes in seeds.values():
        for g in genomes:
            for loop in wl2.loop_names:
                assert wl2.loop(loop).bound % g.t1(loop) == 0, \
                    (loop, g.as_dict())


def test_resolve_lru_is_per_registry_root(store):
    """A registry-less resolution must not satisfy (and starve) a later
    registry-backed call for the same shape: the in-memory LRU is keyed
    by registry root, so the store is always reached at least once."""
    from repro.kernels.autotune import (_config_lru, resolve_matmul_config,
                                        tuner_counts)
    _config_lru.clear()
    no_reg = resolve_matmul_config(384, 384, 384, evals=300)   # no registry
    before = tuner_counts()
    with_reg = resolve_matmul_config(384, 384, 384, registry=store,
                                     evals=300)
    after = tuner_counts()
    assert after["lru_hits"] == before["lru_hits"]  # LRU did not cross-talk
    assert with_reg == no_reg                     # same deterministic search
    fp = matmul_block_fingerprint(384, 384, 384, 2, TPU_V5E)
    assert store.get(fp) is not None              # fleet store was populated


def test_touch_never_rewrites_the_record(store):
    """Hit accounting must not clobber a concurrently-improved record:
    touch only writes the .hits sidecar and bumps the file mtime."""
    rec = store.put(make_record(latency=100.0))
    path = store._path(rec.fingerprint)
    before = open(path).read()
    store.touch(rec.fingerprint)
    store.touch(rec.fingerprint)
    assert open(path).read() == before            # record bytes untouched
    assert store.get(rec.fingerprint).hits == 2   # counted via sidecar
    # counts survive a put (sidecar is independent of the record rewrite)
    store.put(make_record(latency=50.0))
    assert store.get(rec.fingerprint).hits == 2
    store.evict(rec.fingerprint)
    assert not os.path.exists(path + ".hits")
