"""Fixtures for the benchmark's self-tests.

These run on the CPU at tiny sizes, by their own path (the repository's
``testpaths`` is ``tests``)::

    python -m pytest -q bench/tests

A run here skips the harness's look for a chip: ``cpu_cell.py`` replaces
``device.require_chips`` in a child process, which is the only way a
cell can run without a TPU.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
# the self-tests run on the CPU, never on a chip
os.environ["JAX_PLATFORMS"] = "cpu"

TINY_CONFIG = {
    "name": "tiny-dense", "source": "test", "system": "dense_transformer",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "hidden_act": "gelu_pytorch_tanh",
    "torch_dtype": "bfloat16", "reduced": [],
    "serve": {"max_batch": 4, "max_seq": 128, "prefill_chunk": 16},
    "limits": {"logit_gap": 0.008, "row_err": 0.01},
}
TINY_CHAT = {
    "driver": "serve", "why": "test",
    "arrivals": {"process": "mmpp", "rate_rps": 8.0, "burst_factor": 3.0,
                 "burst_share": 0.25, "burst_len": 2},
    "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.8,
                   "min": 4, "max": 96},
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                   "min": 2, "max": 24},
}
TINY_GEMM = {"driver": "gemm", "why": "test", "stage": "prefill",
             "batch": 2, "prefill_len": 16, "in_flight": 2}


SERVE_METRICS = {
    "end_to_end": [
        {"name": "tokens_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.1, "source": "host_clock"},
        {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock"},
        {"name": "tpot_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.15, "source": "host_clock"}],
    "per_layer": [
        {"name": "queue_wait_p95_ms", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "serve scheduler",
         "moves": "ttft_p95_ms"},
        {"name": "slot_occupancy", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "serve scheduler",
         "moves": "tokens_per_s"},
        {"name": "decode_tick_ms", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "model step",
         "moves": "tpot_p95_ms"},
        {"name": "prefill_chunk_ms", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "model step",
         "moves": "ttft_p95_ms"},
        {"name": "serve_mfu", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "device",
         "moves": "tokens_per_s"}],
}
GEMM_METRICS = ("gemm_tflops", "tune_s", "tuner_model_ratio",
                "matmul_roofline", "pallas_over_xla", "gemm_mfu")


def add_cell(root: Path, name: str, config: dict, traffic_name: str,
             traffic: dict) -> None:
    """Add a configuration, a mix and a cell as new files and new
    entries only, as a later change would: a serving cell brings the
    serving metrics where the benchmark has none yet."""
    (root / "bench" / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (root / "bench" / "traffic" / f"{traffic_name}.json").write_text(
        json.dumps(traffic))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": config["name"],
                               "traffic": traffic_name, "chips": 1,
                               "why": "test"})
    if traffic["driver"] == "serve":
        wanted = {m["name"]: m for kind in SERVE_METRICS
                  for m in SERVE_METRICS[kind]}
        for kind, entries in SERVE_METRICS.items():
            have = {m["name"] for m in bench[kind]}
            bench[kind] += [dict(m, workloads=[]) for m in entries
                            if m["name"] not in have]
    else:
        wanted = dict.fromkeys(GEMM_METRICS)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in wanted and "workloads" in m:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.fixture
def checkout(tmp_path):
    """A copy of the benchmark's files with tiny cells added, and the
    program beside it."""
    root = tmp_path / "co"
    root.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", root)
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "src", root / "src")
    add_cell(root, "tiny.chat", TINY_CONFIG, "tiny-chat", TINY_CHAT)
    add_cell(root, "tiny.gemm", TINY_CONFIG, "tiny-gemm", TINY_GEMM)
    return root


def run_cell(root: Path, *args: str, fault: str = "",
             control: str = "") -> tuple:
    """Run a cell of ``root`` on the CPU; (rc, last stdout line, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FAULT=fault,
               BENCH_CONTROL=control)
    proc = subprocess.run(
        [sys.executable, str(HERE / "cpu_cell.py"), str(root), *args],
        capture_output=True, text=True, env=env, timeout=600)
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return proc.returncode, last, proc.stderr
