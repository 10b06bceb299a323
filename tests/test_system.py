"""End-to-end behaviour tests: train -> checkpoint -> crash -> resume ->
serve, plus sharding-rule and dry-run integration (subprocess, multi-dev)."""

import os
import subprocess
import sys

import pytest

pytest.importorskip("jax")  # noqa: E402
import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import latest_checkpoint, restore_checkpoint, \
    save_checkpoint
from repro.configs import get_smoke_config
from repro.data import DataConfig, SyntheticLM
from repro.models import build_model
from repro.runtime import RestartPolicy, run_with_restarts
from repro.serve import ContinuousServingEngine, ServeConfig
from repro.train import AdamWConfig, build_train_step, create_train_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_crash_resume_serve(tmp_path):
    """The full production loop on a reduced config: training crashes after
    a few steps, the supervisor resumes from the checkpoint, and the final
    weights serve."""
    cfg = get_smoke_config("smollm-135m")
    model = build_model(cfg)
    opt = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=100,
                      weight_decay=0.0)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  global_batch=8, seed=0))
    step = jax.jit(build_train_step(model, opt))
    ckpt_dir = str(tmp_path / "ckpt")
    total_steps = 12
    crash_at = {6}
    final_state = {}

    def run(resume):
        if resume is None:
            state = create_train_state(model, opt, jax.random.key(0))
            start = 0
        else:
            template = jax.eval_shape(
                lambda: create_train_state(model, opt, jax.random.key(0)))
            state = restore_checkpoint(resume, template)
            start = int(state["opt_state"]["step"])
        for i in range(start, total_steps):
            batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
            state, metrics = step(state, batch)
            save_checkpoint(ckpt_dir, i + 1, state)
            if (i + 1) in crash_at and resume is None:
                raise RuntimeError("simulated node failure")
        final_state["state"] = state

    restarts = run_with_restarts(run, lambda: latest_checkpoint(ckpt_dir),
                                 RestartPolicy(max_failures=3, backoff_s=0))
    assert restarts == 1
    assert int(final_state["state"]["opt_state"]["step"]) == total_steps

    eng = ContinuousServingEngine(model, final_state["state"]["params"],
                                  ServeConfig(max_batch=2))
    out = eng.generate([np.array([1, 2, 3], np.int32)], max_new_tokens=4)
    assert len(out[0]) == 4


def test_sharding_rules_multidevice_subprocess():
    """param_specs under a real 8-device mesh (subprocess so the 8-device
    XLA flag does not leak into this process)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import PartitionSpec as P
from repro.parallel.sharding import default_rules, infer_param_spec
mesh = jax.make_mesh((2, 4), ("data", "model"))
rules = default_rules(mesh)
s = infer_param_spec("layers/l0/attn/wq", (4096, 4096), rules)
assert "model" in str(s[-1]), s          # column-parallel QKV
s = infer_param_spec("layers/l0/attn/wo", (4096, 4096), rules)
assert "model" in str(s[0]), s           # row-parallel out proj
s = infer_param_spec("embed", (50304, 4096), rules)
assert "model" in str(s[0]), s           # vocab-parallel embedding
s = infer_param_spec("layers/l0/moe/w_up", (8, 1024, 4096), rules)
assert "model" in str(s[0]), s           # expert-parallel stack
s = infer_param_spec("layers/l0/attn/wk", (4096, 1024), rules)
assert "model" not in str(s), s          # KV weights replicate on model
s = infer_param_spec("layers/l0/ln1", (4096,), rules)
assert all(x is None for x in s), s      # small tensors replicate
print("sharding-rules-ok")
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "sharding-rules-ok" in out.stdout, out.stderr[-2000:]


def test_dryrun_single_cell_subprocess(tmp_path):
    """Integration: one full dry-run cell (lower+compile on the 512-device
    production mesh) succeeds from a clean process."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "smollm-135m", "--shape", "decode_32k", "--out-dir",
         str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=560)
    assert "dry-run complete" in out.stdout, \
        out.stdout[-2000:] + out.stderr[-2000:]


def test_shard_tuner_subprocess():
    """Beyond-paper distributed DSE: one variant scores end-to-end on the
    production mesh (smollm keeps the compile fast)."""
    code = (
        "from repro.parallel.shard_tuner import score_variant\n"
        "r = score_variant('smollm-135m', 1)\n"
        "assert r['step_time_model_s'] > 0 and r['compute_s'] > 0\n"
        "print('shard-tuner-ok', round(r['step_time_model_s'], 3))\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert "shard-tuner-ok" in out.stdout, \
        out.stdout[-1000:] + out.stderr[-1000:]
