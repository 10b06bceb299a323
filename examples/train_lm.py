"""End-to-end driver: train the SmolLM-135M architecture on the synthetic
LM task with checkpoint/restart, then serve from the trained weights.

The full 135M config trains on CPU but slowly; ``--full`` selects it.  The
default is a width-reduced SmolLM (same family/code path) sized for this
container, trained for a few hundred steps — the loss drops well below the
uniform-entropy baseline because the synthetic stream is a learnable affine
Markov process.

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--full]
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM
from repro.models import build_model
from repro.serve import ContinuousServingEngine, ServeConfig
from repro.train import AdamWConfig, build_train_step, create_train_state

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--full", action="store_true",
                help="train the full 135M config (slow on CPU)")
ap.add_argument("--seq-len", type=int, default=128)
ap.add_argument("--global-batch", type=int, default=16)
args = ap.parse_args()

cfg = get_config("smollm-135m")
if not args.full:
    cfg = dataclasses.replace(cfg, num_layers=6, d_model=192, num_heads=6,
                              num_kv_heads=2, head_dim=32, d_ff=512,
                              vocab_size=4096, name="smollm-19m")
model = build_model(cfg)
n_params = cfg.param_count()
print(f"arch: {cfg.name}  params ~{n_params / 1e6:.1f}M")

opt = AdamWConfig(lr=1e-2 if not args.full else 3e-3,
                  warmup_steps=20, total_steps=args.steps)
data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                              seq_len=args.seq_len,
                              global_batch=args.global_batch, seed=0))
state = create_train_state(model, opt, jax.random.key(0))
step = jax.jit(build_train_step(model, opt))

uniform = float(np.log(cfg.vocab_size))
print(f"uniform-entropy baseline loss: {uniform:.3f}")
t0 = time.time()
for i in range(args.steps):
    batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
    state, metrics = step(state, batch)
    if (i + 1) % max(1, args.steps // 10) == 0 or i == 0:
        print(f"step {i + 1:4d}  loss {float(metrics['loss']):.4f}  "
              f"({(i + 1) * args.global_batch * args.seq_len / (time.time() - t0):,.0f} tok/s)",
              flush=True)

print(f"\ntrained {args.steps} steps in {time.time() - t0:.1f}s")
eng = ContinuousServingEngine(model, state["params"],
                              ServeConfig(max_batch=4))
prompt = data.batch(9999)["tokens"][0, :8].astype(np.int32)
out = eng.generate([prompt], max_new_tokens=8)[0]
expect = [(data.a * t + data.b) % cfg.vocab_size for t in
          [prompt[-1]] + list(out[:-1])]
print(f"prompt tail: {prompt[-4:].tolist()}")
print(f"generated  : {out.tolist()}")
print("(after enough steps the model tracks the affine next-token map)")
