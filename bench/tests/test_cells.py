"""Whole runs of tiny cells on the CPU, with the look for a chip skipped.

The ``checkout`` fixture adds a configuration, two mixes and two cells
as new files and new entries only; these tests show the harness runs
them unchanged, reads a per-layer metric added the same way, and that
``correct`` comes out false when the timed path is broken underneath.
"""

import json
import os
import subprocess
import sys

import pytest

from bench.tests.conftest import REPO, run_cell

SEED = str(2 ** 31 + 99)


def test_serve_cell(checkout):
    rc, line, err = run_cell(checkout, "--workload", "tiny.chat", "--seed",
                             SEED, "--seconds", "2", "--trace", "0")
    assert rc == 0, err
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "ttft_p95_ms",
                                    "tpot_p95_ms", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check logit_gap:")


def test_gemm_cell(checkout):
    rc, line, err = run_cell(checkout, "--workload", "tiny.gemm", "--seed",
                             SEED, "--seconds", "1", "--trace", "0")
    assert rc == 0, err
    assert line["correct"] is True
    assert set(line["metrics"]) == {"gemm_tflops", "setup_s"}
    assert line["checks"]["row_err"]["value"] < 0.01


def test_new_metric_file_is_read(checkout):
    (checkout / "bench" / "metrics" / "tiny_requests.py").write_text(
        "def read(run):\n    return float(len(run.stats.requests))\n")
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "tiny_requests", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "serve scheduler",
        "moves": "tokens_per_s", "workloads": ["tiny.chat"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    rc, line, err = run_cell(checkout, "--workload", "tiny.chat", "--seed",
                             "5", "--seconds", "2", "--trace", "1")
    assert rc == 0, err
    assert line["metrics"]["tiny_requests"]["value"] == line["attempted"]
    assert {"queue_wait_p95_ms", "slot_occupancy",
            "serve_mfu"} <= set(line["metrics"])
    assert "busy_s" in line["device"] and "window_s" in line["device"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell,fault,check", [
    ("tiny.chat", "token", "logit_gap"),
    ("tiny.chat", "state", "logit_gap"),
    ("tiny.gemm", "answer", "row_err"),
])
def test_broken_timed_path_is_not_correct(checkout, cell, fault, check):
    rc, line, err = run_cell(checkout, "--workload", cell, "--seed", SEED,
                             "--seconds", "1", "--trace", "0", fault=fault)
    assert rc == 0, err
    assert line["correct"] is False
    c = line["checks"][check]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell,check", [
    ("tiny.chat", "logit_gap"),
    ("tiny.gemm", "row_err"),
])
def test_fp8_control_run_is_not_correct(checkout, cell, check):
    """The fp8 control, judged in the program's place through a whole
    run, comes out as not correct."""
    rc, line, err = run_cell(checkout, "--workload", cell, "--seed", SEED,
                             "--seconds", "1", "--trace", "0",
                             control="fp8")
    assert rc == 0, err
    assert line["correct"] is False
    c = line["checks"][check]
    assert c["value"] > c["limit"]


def test_no_tpu_no_result(tmp_path):
    """The real entry, on a machine without a TPU: non-zero, no line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_without_the_program_no_result(checkout):
    os.unlink(checkout / "src")
    rc, line, err = run_cell(checkout, "--workload", "tiny.chat", "--seed",
                             "1", "--seconds", "1", "--trace", "0")
    assert rc != 0 and line is None
