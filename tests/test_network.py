"""Network-level DSE: graph IR, GEMM extraction parity, assignment
optimality, session composition, and the serving pre-tune."""

import random

import numpy as np
import pytest

from repro.core import EvoConfig, U250, conv2d
from repro.network import (ArrayGeometry, AssignConfig, NetworkSession,
                           brute_force_partition, conv_graph,
                           geometry_from_result, model_config_graph,
                           partition_dp, resnet50_graph, retune_tiling,
                           vgg16_graph)
from repro.network.graph import LayerGraph, layer_gemm_slots

TOY_LAYERS = [(8, 16, 16, 16, 3, 3, 1), (32, 32, 8, 8, 3, 3, 1),
              (64, 64, 4, 4, 3, 3, 2)]
TINY = EvoConfig(epochs=5, population=16, seed=0)
TINY_ASSIGN = AssignConfig(max_arrays=3, retune_evals=60,
                           reconfig_cycles=1e4)


# ---------------------------------------------------------------------- #
# Graph IR
# ---------------------------------------------------------------------- #
def test_vgg16_graph_dedup():
    g = vgg16_graph()
    assert len(g) == 13                       # one node per CONV layer
    classes = g.classes()
    assert len(classes) == 9                  # duplicate shapes collapse
    assert sum(c.count for c in classes.values()) == 13
    assert g.total_macs() == sum(n.wl.total_macs() for n in g.nodes)


def test_resnet50_graph_covers_stride2_cores():
    g = resnet50_graph()
    assert len(g) == 16
    strided = [n for n in g.nodes if n.wl.name.endswith("_s2")]
    assert len(strided) == 3                  # conv3_1 / conv4_1 / conv5_1
    # stride-2 cores are distinct shape classes from their stride-1 twins
    assert len(g.classes()) == 7


def test_model_graph_collapses_layers():
    from repro.configs import get_config
    cfg = get_config("qwen3-14b")             # 40 identical dense layers
    g = model_config_graph(cfg, batch=2, prefill_len=128)
    assert sum(n.count for n in g.nodes) >= 2 * 40 * 4   # stages x L x GEMMs
    assert len(g.classes()) <= 14             # ...collapse to a handful
    prefill = g.subset("prefill")
    assert all(n.wl.bounds["i"] == 2 * 128 for n in prefill.nodes)
    decode = g.subset("decode")
    assert all(n.wl.bounds["i"] == 2 for n in decode.nodes)


def test_gemm_shapes_rejects_conv_graphs():
    with pytest.raises(ValueError):
        vgg16_graph().gemm_shapes()


# ---------------------------------------------------------------------- #
# GEMM extraction parity vs the actual models/ parameters
# ---------------------------------------------------------------------- #
def _param_gemm_multiset(cfg):
    """{(K, N): occurrences} of every dense weight the forward pass uses,
    from the real parameter tree (jax.eval_shape — nothing allocated)."""
    jax = pytest.importorskip("jax")
    from repro.models import build_model
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    names = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
             "in_proj", "out_proj", "router"}
    out = {}

    def add(shape, times):
        key = (shape[0], shape[1])
        out[key] = out.get(key, 0) + times

    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in leaves:
        last = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
        if last in names:
            lead = 1
            for d in leaf.shape[:-2]:
                lead *= d
            add(leaf.shape[-2:], lead)
        elif last == "lm_head" or (last == "embed" and cfg.tie_embeddings):
            # stored (vocab, d); used as x @ W.T => GEMM weight (d, vocab)
            add((leaf.shape[1], leaf.shape[0]), 1)
    return out


def _graph_gemm_multiset(cfg):
    """{(K, N): occurrences} from the extractor's slot table."""
    out = {}
    for _, n_dim, k_dim, times in layer_gemm_slots(cfg):
        out[(k_dim, n_dim)] = out.get((k_dim, n_dim), 0) + times
    return out


@pytest.mark.parametrize("arch", ["smollm-135m", "mamba2-130m"])
def test_gemm_extraction_matches_model_params(arch):
    """Every GEMM weight shape the graph extracts exists in the real
    parameter tree with the same multiplicity (transformer + mamba)."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config(arch)
    assert _graph_gemm_multiset(cfg) == _param_gemm_multiset(cfg)


@pytest.mark.parametrize("arch", ["smollm-135m", "mamba2-130m"])
def test_gemm_extraction_token_dims(arch):
    """Prefill GEMMs see batch*seq token rows, decode GEMMs batch rows —
    the M dims the serving engine actually issues."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config(arch)
    B, S = 3, 32
    g = model_config_graph(cfg, batch=B, prefill_len=S)
    assert {n.wl.bounds["i"] for n in g.subset("prefill").nodes} == {B * S}
    assert {n.wl.bounds["i"] for n in g.subset("decode").nodes} == {B}


# ---------------------------------------------------------------------- #
# Assignment: DP optimality and edge cases
# ---------------------------------------------------------------------- #
def test_partition_dp_matches_brute_force():
    rng = random.Random(7)
    for _ in range(60):
        L, C = rng.randint(1, 6), rng.randint(1, 4)
        cost = np.array([[rng.uniform(1, 100) for _ in range(C)]
                         for _ in range(L)])
        # sprinkle infeasibility, keeping every layer somewhere-feasible
        for l in range(L):
            for c in range(C):
                if rng.random() < 0.2:
                    cost[l, c] = np.inf
            if not np.isfinite(cost[l]).any():
                cost[l, rng.randrange(C)] = rng.uniform(1, 100)
        counts = [rng.randint(1, 3) for _ in range(L)]
        reconfig = rng.choice([0.0, 7.5, 1e7])
        k = rng.randint(1, L)
        try:
            a = partition_dp(cost, counts, reconfig, k)
        except ValueError:
            # K segments cannot cover the infeasibility pattern — the
            # exhaustive reference must agree there is no assignment
            with pytest.raises(ValueError):
                brute_force_partition(cost, counts, reconfig, k)
            continue
        b = brute_force_partition(cost, counts, reconfig, k)
        assert a.latency_cycles == pytest.approx(b.latency_cycles)
        assert a.n_arrays <= k


def test_partition_k1_reduces_to_uniform():
    cost = np.array([[10.0, 1.0], [10.0, 50.0], [10.0, 1.0]])
    a = partition_dp(cost, [1, 1, 1], reconfig_cycles=5.0, max_arrays=1)
    assert a.n_arrays == 1
    assert a.reconfig_cycles == 0.0
    assert a.latency_cycles == 30.0           # best single candidate


def test_partition_reconfig_edge_cases():
    cost = np.array([[10.0, 1.0], [1.0, 10.0], [10.0, 1.0]])
    # free reconfiguration: every layer picks its own optimum
    free = partition_dp(cost, [1, 1, 1], reconfig_cycles=0.0, max_arrays=3)
    assert free.latency_cycles == 3.0 and free.n_arrays == 3
    # prohibitive reconfiguration: collapses to the uniform array
    uni = partition_dp(cost, [1, 1, 1], reconfig_cycles=1e9, max_arrays=3)
    assert uni.n_arrays == 1 and uni.latency_cycles == 12.0
    # moderate: one switch is worth it, two are not
    mid = partition_dp(cost, [1, 1, 1], reconfig_cycles=8.0, max_arrays=3)
    assert mid.latency_cycles == min(12.0,              # uniform
                                     1 + 1 + 1 + 16,    # three segments
                                     1 + 10 + 1 + 8,    # cand 1 then switch
                                     10 + 1 + 1 + 8,
                                     1 + 1 + 10 + 8)
    # occurrence counts scale layer cost, not reconfiguration
    cnt = partition_dp(cost, [5, 1, 1], reconfig_cycles=0.0, max_arrays=3)
    assert cnt.latency_cycles == 5 * 1 + 1 + 1


def test_assign_config_amortizes_reconfiguration():
    """Steady-state serving shares one fabric switch across a pipeline of
    inferences; batch-1 (amortize_over=1) pays it in full."""
    single = AssignConfig(reconfig_cycles=3e5, amortize_over=1)
    pipelined = AssignConfig(reconfig_cycles=3e5, amortize_over=16)
    assert single.effective_reconfig_cycles == 3e5
    assert pipelined.effective_reconfig_cycles == pytest.approx(3e5 / 16)


def test_retune_respects_geometry():
    """The fixed-geometry re-tune may only move the free schedule dims."""
    from repro.core import pruned_permutations
    wl = conv2d(16, 32, 8, 8, 3, 3)
    perm = [p for p in pruned_permutations(wl)
            if set(p.inner) == {"i", "p", "q"}][0]
    geom = ArrayGeometry(dataflow=("o", "h"), perm=perm,
                         pe_dims=(16, 4), simd=8)
    fit = retune_tiling(wl, geom, evals=120, seed=1)
    g = fit.genome
    assert g.triples["o"][1] == 16 and g.triples["h"][1] == 4
    assert g.t2("i") <= 8                     # simd clamped to the array's
    # a layer smaller than the array runs on the clamped sub-array
    small = conv2d(16, 8, 2, 8, 3, 3)
    fit2 = retune_tiling(small, geom, evals=120, seed=1)
    assert fit2.genome.triples["o"][1] == 8   # bound < 16 PE rows
    assert fit2.genome.triples["h"][1] == 2


# ---------------------------------------------------------------------- #
# NetworkSession composition + registry warm start
# ---------------------------------------------------------------------- #
def test_network_session_composes(tmp_path):
    from repro.registry import RegistryStore
    g = conv_graph("toy", TOY_LAYERS)
    store = RegistryStore(str(tmp_path / "reg"))
    sess = NetworkSession(g, cfg=TINY, registry=store, assign=TINY_ASSIGN)
    rep = sess.run(k_values=(1, 2, 3))
    assert rep.total_evals > 0
    # monotone: more arrays never hurt; nothing beats the per-layer ideal
    lat = {k: a["latency_cycles"] for k, a in rep.assignments.items()}
    assert lat[3] <= lat[2] <= lat[1]
    assert rep.per_layer_cycles <= lat[3] + 1e-9 * rep.per_layer_cycles
    assert rep.assignments[1]["n_arrays"] == 1
    assert rep.pareto                          # non-empty frontier
    # warm second session: every class sweep served from the registry
    sess2 = NetworkSession(g, cfg=TINY, registry=store, assign=TINY_ASSIGN)
    rep2 = sess2.run(k_values=(1, 2))
    assert rep2.total_evals == 0
    assert all(c["from_cache"] for c in rep2.classes.values())
    assert rep2.per_layer_cycles == pytest.approx(rep.per_layer_cycles)


def test_kernel_pretune_warm_run_zero_evals(tmp_path):
    """One network pass resolves every Pallas block config; the second
    pass is served entirely by the registry (0 search evals)."""
    from repro.configs import get_smoke_config
    from repro.kernels.autotune import (pretune_model_config,
                                        reset_config_lru)
    from repro.registry import RegistryStore
    cfg = get_smoke_config("smollm-135m")
    store = RegistryStore(str(tmp_path / "reg"))
    reset_config_lru()
    cold = pretune_model_config(cfg, batch=2, prefill_len=32,
                                registry=store, evals=150)
    assert cold["tuned"] == cold["shapes"] > 0 and cold["evals"] > 0
    reset_config_lru()   # drop process memory: only the disk store remains
    warm = pretune_model_config(cfg, batch=2, prefill_len=32,
                                registry=store, evals=150)
    assert warm["tuned"] == warm["evals"] == 0
    assert warm["disk_hits"] == warm["shapes"] == cold["shapes"]


def test_network_session_time_budget_rollover():
    """A NetworkSession wall-clock budget flows class -> class with the
    same rollover rule as SearchSession: classes that finish under their
    slice leave the remainder to the classes still queued, so the run
    completes well under budget without starving any class."""
    import time as _time
    g = conv_graph("toy", TOY_LAYERS)
    budget = 120.0   # enormous vs the tiny epoch counts: all classes end early
    sess = NetworkSession(g, cfg=TINY, time_budget_s=budget)
    t0 = _time.perf_counter()
    reports = sess.tune_classes()
    elapsed = _time.perf_counter() - t0
    assert len(reports) == len(g.classes())
    assert elapsed < budget
    # every class actually searched (budget never collapsed to zero)
    assert all(sum(r.evo.evals for r in rep.results) > 0
               for rep in reports.values())
