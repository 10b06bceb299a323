"""Counters against hand arithmetic and against the program's graph."""

import json

from bench import counters
from bench.tests.conftest import REPO


def _cfg(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())


def test_starcoder2_layer():
    cfg = _cfg("starcoder2-7b")
    # 4608*4608*2 (q, o) + 4608*512*2 (k, v) + 2*4608*18432 (up, down)
    assert counters.layer_params(cfg) == 217_055_232
    total = 16 * 217_055_232 + 2 * 49152 * 4608
    assert abs(2 * total / 1e9 - 7.85) < 0.01          # GB in bf16


def test_smollm_layer():
    cfg = _cfg("smollm-135m")
    d, f = 576, 1536
    assert counters.layer_params(cfg) == \
        d * 576 * 2 + d * 192 * 2 + 3 * d * f


def test_prefill_pass_flops():
    cfg = _cfg("starcoder2-7b")
    classes = counters.gemm_classes(cfg, 16 * 256)
    flops = sum(counters.gemm_flops(s) * c for s, c in classes)
    assert flops == 2 * 4096 * (16 * 217_055_232 + 49152 * 4608)
    assert abs(flops / 1e12 - 30.3) < 0.05
    assert sum(c for _, c in classes) == 97


def test_classes_match_program_graph():
    """The benchmark's own shape classes equal the program's graph."""
    from repro.network.graph import model_config_graph
    from bench.systems.dense_transformer import program_model
    cfg = _cfg("starcoder2-7b")
    mcfg = program_model(cfg).cfg
    for stage, rows in (("prefill", 4096), ("decode", 16)):
        g = model_config_graph(mcfg, batch=16, prefill_len=256,
                               stages=(stage,))
        graph = {(n.wl.bounds["i"], n.wl.bounds["j"], n.wl.bounds["k"]):
                 n.count for n in g.nodes}
        assert graph == dict(counters.gemm_classes(cfg, rows))


def test_roofline_bound():
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # decode: bytes bound; prefill: FLOP bound
    s = (16, 4608, 4608)
    assert counters.gemm_min_seconds(s, peak) == \
        2 * (16 * 4608 * 2 + 4608 * 4608) / 819e9
    s = (4096, 4608, 4608)
    assert counters.gemm_min_seconds(s, peak) == 2 * 4096 * 4608 ** 2 / 197e12


def test_request_flops_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
           "num_hidden_layers": 3, "vocab_size": 10,
           "hidden_act": "silu"}
    lp = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert counters.layer_params(cfg) == lp
    # prompt 3, 2 new tokens: 4 tokens fed at contexts 1..4, head twice
    want = 3 * (2 * lp * 4 + 4 * 2 * 4 * (1 + 2 + 3 + 4)) + 2 * 10 * 8 * 2
    assert counters.request_flops(cfg, 3, 2) == want
