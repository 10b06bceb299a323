"""The MoE cell on the CPU at a tiny size: a whole run through the
harness, the readers of its new metrics on a run's records, and a grouped
kernel altered underneath that makes the run not ``correct``.

The tiny configuration keeps Mellum2's file layout (every key the
driver, the system glue and the reference read) at toy widths.
"""

import json
import os
import subprocess
import sys

import pytest

from bench.tests.conftest import HERE, REPO, add_cell, run_cell

SEED = str(2 ** 33 + 17)
MELLUM = json.loads((REPO / "bench" / "configs" /
                     "mellum2-12b-a2.5b.json").read_text())
TINY_MOE = dict(
    MELLUM, name="tiny-moe", hidden_size=64, head_dim=16,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    num_hidden_layers=4, vocab_size=256, sliding_window=8,
    serve={"max_batch": 4, "max_seq": 128, "prefill_chunk": 16},
    limits={"row_err": 0.02, "logit_gap": 0.01})
TINY_MOE_PREFILL = dict(
    json.loads((REPO / "bench" / "traffic" / "moe-prefill.json"
                ).read_text()), batch=2, prefill_len=24)
MOE_METRICS = ("gemm_tflops", "gemm_mfu", "tune_s", "tuner_resolve_s",
               "compile_s", "gmm_roofline", "gmm_over_xla",
               "moe_dispatch_share")
# a wrapper around ``cpu_cell.py`` that alters the grouped kernel's
# output in one row before the cell runs
ALTER_ONE_ROW = """
import runpy, sys
sys.path[0:0] = [sys.argv[1], sys.argv[1] + "/src"]
from repro.kernels import ops
base = ops.gmm_op
ops.gmm_op = lambda *a, **kw: base(*a, **kw).at[0].add(1)
sys.argv = [{cell!r}] + sys.argv[1:]
runpy.run_path({cell!r}, run_name="__main__")
"""


@pytest.fixture
def moe_checkout(checkout):
    add_cell(checkout, "tiny.moe", TINY_MOE, "tiny-moe-prefill",
             TINY_MOE_PREFILL)
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in MOE_METRICS and "tiny.moe" not in m["workloads"]:
            m["workloads"].append("tiny.moe")
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    return checkout


def test_moe_cell(moe_checkout):
    rc, line, err = run_cell(moe_checkout, "--workload", "tiny.moe",
                             "--seed", SEED, "--seconds", "1", "--trace",
                             "0")
    assert rc == 0, err
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"gemm_tflops", "setup_s"}
    assert line["checks"]["row_err"]["value"] < 0.02


def test_altered_grouped_kernel_is_not_correct(moe_checkout, tmp_path):
    wrapper = tmp_path / "alter.py"
    wrapper.write_text(ALTER_ONE_ROW.format(cell=str(HERE / "cpu_cell.py")))
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FAULT="",
               BENCH_CONTROL="")
    proc = subprocess.run(
        [sys.executable, str(wrapper), str(moe_checkout), "--workload",
         "tiny.moe", "--seed", SEED, "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    c = line["checks"]["row_err"]
    assert c["value"] > c["limit"]


def test_moe_readers():
    """The three readers on a run's records: the kernels' device time
    from op names, XLA's ragged_dot from its module, the dispatch share
    from the scoped ops."""
    from bench.device import PEAKS
    from bench.harness import Run
    from bench.metrics import gmm_over_xla, gmm_roofline, moe_dispatch_share
    from bench.trace import Summary
    run = Run(workload="tiny.moe", seed=1, seconds=1.0, trace=True,
              config=TINY_MOE, traffic=TINY_MOE_PREFILL, t_process=0.0,
              peak=PEAKS["TPU v5 lite"])
    for reader in (gmm_roofline, gmm_over_xla, moe_dispatch_share):
        assert reader.read(run) is None
    run.summary = Summary(busy_s=1.0, modules={"jit_bench_moe": (1.0, 10)},
                          device_ops=[
                              ("jit_bench_moe/gmm_96x32x64_g8_96x64x32", 0.3),
                              ("jit_bench_moe/gmm_96x64x32_g8_96x32x64", 0.2),
                              ("jit_bench_moe/fusion", 0.4)],
                          idle_gaps=[])
    run.counts.update(passes=10, gmm_min_s=0.02, xla_passes=2)
    run.extra["xla_ragged"] = Summary(
        busy_s=0.2, modules={"jit_bench_xla_ragged": (0.2, 6)},
        device_ops=[], idle_gaps=[])
    run.extra["moe_scopes"] = (0.06, 0.3)
    assert gmm_roofline.read(run) == pytest.approx(100 * 0.02 * 10 / 0.5)
    assert gmm_over_xla.read(run) == pytest.approx((0.2 / 2) / (0.5 / 10))
    assert moe_dispatch_share.read(run) == pytest.approx(20.0)


def test_scoped_instructions():
    from bench.drivers.moe_gemm import scoped_instructions
    hlo = "\n".join([
        '  %fusion.3 = f32[8] fusion(%p), metadata={op_name="jit(bench_moe)'
        '/moe.route/exp" stack_frame_id=3}',
        '  %sort.1 = s32[8] sort(%a), metadata={op_name="jit(bench_moe)/'
        'moe.dispatch/jit(argsort)/sort"}',
        '  %gmm_8x8x8_g2_8x8x8.1 = bf16[8,8] custom-call(%r), '
        'metadata={op_name="jit(bench_moe)/gmm_8x8x8_g2_8x8x8/pallas_call"}',
        '  ROOT %gather.2 = bf16[8,8] gather(%o), metadata={op_name='
        '"jit(bench_moe)/moe.combine/gather"}'])
    assert scoped_instructions(hlo) == {"fusion.3", "sort.1", "gather.2"}
