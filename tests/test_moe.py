"""Routed experts and mixed attention (Mellum2's layers) on the CPU.

* the grouped Pallas matmul (``ops.gmm_op``, interpret mode) against a
  per-group product: ragged groups, empty groups, one group taking every
  row, tiles that straddle groups; its gradient against ``ragged_dot``'s;
* the tuner's grouped workload: legal blocks within the VMEM bound, and
  keys of its own;
* the dropless expert layer against the float32 reference
  (``bench/reference/moe_transformer.py``), with no token dropped when
  every token picks the same experts; the capacity path only under a
  mesh whose 'model' axis is above 1;
* a smoke Mellum2 through the continuous serving engine, and prefill
  then decode through the cache, against the reference's full forward
  where the sliding window binds and across a full YaRN layer;
* the layer graph's expert GEMMs at the mean routed rows.
"""

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:       # the benchmark's package, ``bench``
    sys.path.insert(0, str(REPO))

from bench.reference import moe_transformer as ref  # noqa: E402
from bench.systems import moe_transformer as system  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.kernels import GmmConfig, ops  # noqa: E402
from repro.kernels.autotune import (TpuGmmModel, resolve_gmm_config,  # noqa: E402
                                    resolve_matmul_config)
from repro.kernels.gmm import group_metadata, max_tile_visits  # noqa: E402
from repro.kernels.matmul import (VMEM_LIMIT_MAX, legal_blocks,  # noqa: E402
                                  vmem_bytes, vmem_limit_bytes)
from repro.models import layers  # noqa: E402

MELLUM = json.loads((REPO / "bench" / "configs" /
                     "mellum2-12b-a2.5b.json").read_text())
# Mellum2's file at toy widths: one period of 3 sliding : 1 full layers,
# a window of 8 and YaRN over a 32-position original context
TINY = dict(MELLUM, name="tiny-mellum", hidden_size=64, head_dim=16,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=128, moe_intermediate_size=32, num_experts=8,
            num_experts_per_tok=2, num_hidden_layers=4, vocab_size=256,
            sliding_window=8, torch_dtype="float32",
            rope_parameters={
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                    "original_max_position_embeddings": 32, "beta_fast": 32,
                    "beta_slow": 1, "attention_factor": 1.1386},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 10000}})


def _per_group(lhs, rhs, sizes):
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    start = 0
    for g, n in enumerate(sizes):
        out[start:start + n] = np.asarray(lhs[start:start + n], np.float32) \
            @ np.asarray(rhs[g], np.float32)
        start += n
    return out


# (rows, K, N, sizes, bm, bk, bn)
GMM_CASES = {
    "ragged": (100, 200, 130, [30, 0, 51, 19], 16, 128, 128),
    "empty_experts": (64, 128, 256, [0, 40, 0, 0, 24, 0], 32, 128, 128),
    "one_expert_all_rows": (72, 96, 128, [0, 0, 72], 16, 96, 128),
    "straddling_tiles": (37, 128, 128, [3, 9, 1, 20, 4], 8, 128, 128),
    "single_tile_many_groups": (24, 64, 128, [5, 6, 7, 6], 24, 64, 128),
}


@pytest.mark.parametrize("case", list(GMM_CASES))
def test_gmm_matches_per_group_product(case):
    R, K, N, sizes, bm, bk, bn = GMM_CASES[case]
    rng = np.random.default_rng(len(case))
    lhs = jnp.asarray(rng.standard_normal((R, K)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), K, N)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    cfg = GmmConfig(bm=bm, bk=bk, bn=bn, interpret=True)
    out = np.asarray(ops.gmm_op(lhs, rhs, gs, cfg))
    np.testing.assert_allclose(out, _per_group(lhs, rhs, sizes),
                               rtol=1e-5, atol=1e-4)


def test_gmm_bfloat16_and_gradient():
    R, K, N, sizes = 40, 128, 128, [7, 0, 25, 8]
    rng = np.random.default_rng(3)
    lhs = jnp.asarray(rng.standard_normal((R, K)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((4, K, N)), jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    cfg = GmmConfig(bm=16, bk=128, bn=128, interpret=True)
    out = np.asarray(ops.gmm_op(lhs, rhs, gs, cfg), np.float32)
    want = _per_group(lhs, rhs, sizes)
    assert np.max(np.abs(out - want)) <= 0.01 * np.max(np.abs(want))

    def loss(fn):
        return lambda a, b: jnp.sum(fn(a, b).astype(jnp.float32) ** 2)
    lhs32, rhs32 = lhs.astype(jnp.float32), rhs.astype(jnp.float32)
    got = jax.grad(loss(lambda a, b: ops.gmm_op(
        a, b, gs, dataclasses.replace(cfg))), (0, 1))(lhs32, rhs32)
    exp = jax.grad(loss(lambda a, b: jax.lax.ragged_dot(a, b, gs)),
                   (0, 1))(lhs32, rhs32)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=1e-4, atol=1e-2)


def test_group_metadata_visits_tiles_in_order():
    sizes = jnp.asarray([3, 0, 9, 1, 20, 0, 4], jnp.int32)
    R, bm = 37, 8
    offsets, gids, tids, visits = group_metadata(sizes, R, bm)
    n = int(visits)
    assert n <= max_tile_visits(R, 7, bm)
    pairs = list(zip(np.asarray(gids)[:n].tolist(),
                     np.asarray(tids)[:n].tolist()))
    want = []
    for g, (a, b) in enumerate(zip(np.asarray(offsets)[:-1],
                                   np.asarray(offsets)[1:])):
        want += [(g, t) for t in range(a // bm, (b - 1) // bm + 1) if b > a]
    assert pairs == want
    # an empty group has no visit, so its weight is never read
    assert {g for g, _ in pairs} == {0, 2, 3, 4, 6}


@pytest.mark.parametrize("shape", [(32768, 896, 2304, 64),
                                   (32768, 2304, 896, 64),
                                   (96, 896, 2304, 64), (100, 130, 200, 4)])
def test_resolve_gmm_config_legal_within_vmem(shape):
    R, N, K, E = shape
    cfg = resolve_gmm_config(R, N, K, E)
    assert legal_blocks(cfg.bm, cfg.bk, cfg.bn, R, K, N) == \
        (cfg.bm, cfg.bk, cfg.bn)
    need = vmem_bytes(cfg.bm, cfg.bk, cfg.bn, 2, True, K % cfg.bk != 0)
    assert vmem_limit_bytes(need) <= VMEM_LIMIT_MAX
    model = TpuGmmModel(R=R, N=N, K=K, E=E)
    # partial tiles at group boundaries are charged: one more tile per
    # group but the first (at most one a row)
    assert model.grid((cfg.bm, cfg.bk, cfg.bn, True))[0] == \
        -(-R // cfg.bm) + min(E, R) - 1


def test_gmm_and_matmul_resolutions_do_not_collide(tmp_path):
    from repro.registry import (RegistryStore, gmm_block_fingerprint,
                                matmul_block_fingerprint)
    from repro.core.hardware import TPU_V5E
    a = gmm_block_fingerprint(256, 128, 128, 4, 2, TPU_V5E)
    b = matmul_block_fingerprint(256, 128, 128, 2, TPU_V5E)
    assert a.family != b.family and a.digest != b.digest
    store = RegistryStore(str(tmp_path))
    g = resolve_gmm_config(256, 128, 128, 4, registry=store)
    m = resolve_matmul_config(256, 128, 128, registry=store)
    assert isinstance(g, GmmConfig) and hasattr(m, "k_innermost")
    kinds = sorted(r.kind for r in store.iter_records())
    assert kinds == ["tpu_block", "tpu_gmm_block"]


# --------------------------------------------------------------------- #
# the expert layer
# --------------------------------------------------------------------- #
def _tiny_layer(seed=0, k=2):
    cfg = dict(TINY, num_experts_per_tok=k)
    w = system.moe_params(ref.make_layer(ref.dims(cfg),
                                         ref.layer_key(seed, 0)))
    return system.program_config(cfg), w


def _program_block(mcfg, w, x):
    B, S, D = x.shape
    return np.asarray(layers.moe_dropless(w, mcfg, x), np.float32
                      ).reshape(B * S, D)


def test_dropless_block_matches_reference():
    mcfg, w = _tiny_layer()
    x = jax.random.normal(jax.random.key(4), (3, 20, 64), jnp.float32)
    got = _program_block(mcfg, w, x)
    want, _ = ref.moe_block(x.reshape(60, 64), w, 2)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-5)


def test_dropless_keeps_every_token_when_all_pick_the_same_experts():
    """Every token routes to experts 0..k-1: the capacity dispatch would
    keep 1.25 * k * S / E of them per batch row; dropless keeps all."""
    mcfg, w = _tiny_layer(k=3)
    router = np.zeros((64, 8), np.float32)
    router[:, :3] = [4.0, 3.0, 2.0]
    w = dict(w, router=jnp.asarray(router))
    x = jnp.abs(jax.random.normal(jax.random.key(5), (2, 16, 64))) + 0.5
    _, experts = layers.moe_route(w, mcfg, x.reshape(32, 64))
    assert set(np.asarray(experts).ravel().tolist()) == {0, 1, 2}
    got = _program_block(mcfg, w, x)
    want, _ = ref.moe_block(x.reshape(32, 64), w, 3)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-5)
    dropped = np.asarray(layers.moe_capacity(w, mcfg, x)).reshape(32, 64)
    assert np.abs(dropped - np.asarray(want)).max(1).max() > 1e-2


def test_capacity_path_only_under_a_sharded_model_axis(monkeypatch):
    mcfg, w = _tiny_layer()
    x = jnp.ones((1, 4, 64), jnp.float32)
    seen = []
    monkeypatch.setattr(layers, "moe_capacity",
                        lambda *a: seen.append("capacity") or x)
    monkeypatch.setattr(layers, "moe_dropless",
                        lambda *a: seen.append("dropless") or x)

    class Rules:
        def __init__(self, tp):
            self.tp = tp

        def axis_size(self, axis):
            return self.tp if axis == "model" else 1
    for rules, path in ((None, "dropless"), (Rules(1), "dropless"),
                        (Rules(2), "capacity")):
        monkeypatch.setattr(layers, "current_rules", lambda r=rules: r)
        layers.moe_forward(w, mcfg, x)
        assert seen[-1] == path


# --------------------------------------------------------------------- #
# the whole model: sliding window, YaRN, serving
# --------------------------------------------------------------------- #
def _tiny_model(seed=11):
    weights = ref.make_weights(TINY, seed)
    weights = {k: v.astype(jnp.float32) for k, v in weights.items()}
    model = system.program_model(TINY)
    return weights, model, system.program_params(TINY, weights)


def _cached_logits(model, params, tokens, chunk):
    """Prefill in ``chunk``-token steps, then one token a step, through
    the cache: the logits at every position."""
    T = len(tokens)
    step = jax.jit(model.decode_step)
    cache = model.init_cache(1, T + chunk, dtype=jnp.float32)
    out, pos = [], 0
    while pos + chunk <= T // 2:
        lg, cache = step(params, cache,
                         jnp.asarray(tokens[None, pos:pos + chunk]),
                         jnp.asarray([pos], jnp.int32))
        out.append(lg[0])
        pos += chunk
    for t in range(pos, T):
        lg, cache = step(params, cache, jnp.asarray(tokens[None, t:t + 1]),
                         jnp.asarray([t], jnp.int32))
        out.append(lg[0])
    return np.asarray(jnp.concatenate(out), np.float32)


def test_prefill_then_decode_matches_reference_where_window_binds():
    weights, model, params = _tiny_model()
    tokens = np.random.default_rng(2).integers(1, 256, 40).astype(np.int32)
    got = _cached_logits(model, params, tokens, chunk=8)
    rows = np.arange(len(tokens))
    want = np.asarray(ref.logits_at(TINY, weights, tokens, rows))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=2e-4)
    # the comparison sees the window and YaRN: without either, the
    # reference moves by far more than the tolerance
    unbound = dict(TINY, sliding_window=1 << 20)
    plain = dict(TINY, rope_parameters=dict(
        TINY["rope_parameters"],
        full_attention=TINY["rope_parameters"]["sliding_attention"]))
    for other in (unbound, plain):
        moved = np.asarray(ref.logits_at(other, weights, tokens, rows))
        assert np.abs(moved - want)[8:].max() > 1e-2


def test_yarn_frequencies_follow_the_ramp():
    rp = MELLUM["rope_parameters"]["full_attention"]
    inv = ref.yarn_inv_freq(128, rp)
    base = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    # beta_fast 32 -> dim 18 (floor), beta_slow 1 -> dim 35 (ceil)
    np.testing.assert_allclose(inv[:19], base[:19], rtol=1e-12)
    np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=1e-12)
    assert np.all((inv[19:35] < base[19:35]) & (inv[19:35] > base[19:35] / 16))
    prog = layers.yarn_inv_freq(128, 5e5, 16.0, 8192, 32.0, 1.0)
    np.testing.assert_allclose(prog, inv, rtol=1e-12)


def test_mellum2_serves_through_the_continuous_engine():
    from repro.serve import Request, ServeConfig, make_engine
    weights, model, params = _tiny_model(seed=12)
    engine = make_engine("continuous", model, params,
                         ServeConfig(max_batch=2, max_seq=64,
                                     prefill_chunk=8, eos_token=None))
    rng = np.random.default_rng(9)
    reqs = [Request(prompt=rng.integers(1, 256, n).astype(np.int32),
                    max_new_tokens=m, request_id=i)
            for i, (n, m) in enumerate(((21, 12), (13, 9), (30, 6)))]
    outs, _ = engine.serve(reqs)
    assert [len(o) for o in outs] == [12, 9, 6]
    pairs = [(q.prompt, o) for q, o in zip(reqs, outs)]
    # float32 program against the float32 reference: a served token is
    # the reference's best up to summation order
    assert ref.widest_gap(TINY, weights, pairs) < 1e-3


def test_mellum2_config_counts():
    cfg = get_config("mellum2-12b-a2.5b")
    assert abs(cfg.param_count() / 12.15e9 - 1) < 0.01
    assert abs(cfg.active_param_count() / 2.44e9 - 1) < 0.01
    smoke = get_smoke_config("mellum2-12b-a2.5b")
    assert layers.layer_kinds(smoke) == ("sliding", "sliding", "sliding",
                                         "full")


def test_graph_sizes_expert_gemms_by_mean_routed_rows():
    from repro.network.graph import model_config_graph
    cfg = get_config("mellum2-12b-a2.5b")
    g = model_config_graph(cfg, batch=16, prefill_len=256, decode_batch=12)
    names = {n.name for n in g.nodes}
    # 16 x 256 tokens x 8 / 64 experts = 512 rows an expert; decode
    # ceil(12 x 8 / 64) = 2
    for stage, m in (("prefill", 512), ("decode", 2)):
        assert {f"{stage}:mm_{m}x896x2304",
                f"{stage}:mm_{m}x2304x896"} <= names
