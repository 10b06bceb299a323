"""Production training launcher.

``python -m repro.launch.train --arch smollm-135m --steps 100 ...``

Single-process form of the per-host launcher: builds the local mesh, the
sharded train state, the synthetic data pipeline, and runs the step loop
under the restart supervisor with periodic async checkpoints and straggler
telemetry.  On a real multi-host pod each host runs this binary with
``jax.distributed.initialize`` (the mesh/rules/specs code is identical;
see DESIGN.md §5)."""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.ckpt import AsyncCheckpointer, latest_checkpoint, \
    restore_checkpoint
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.parallel import plan as plan_lib
from repro.parallel.sharding import axis_rules, default_rules
from repro.runtime import RestartPolicy, StragglerDetector, \
    run_with_restarts
from repro.train import AdamWConfig, build_train_step, create_train_state
from repro.train.step import abstract_train_state


def state_shardings(model, opt: AdamWConfig, rules,
                    ef_compression: bool = False):
    """(abstract train state, its NamedShardings on ``rules.mesh``): the
    one layout of the state, for a fresh start and a resume alike."""
    template = abstract_train_state(model, opt, ef_compression)
    specs = plan_lib.train_state_specs(template, rules)
    return template, plan_lib.to_named(specs, rules)


def fresh_train_state(model, opt: AdamWConfig, rules,
                      ef_compression: bool = False, seed: int = 0):
    """A new train state, created directly in its sharded layout."""
    _, shardings = state_shardings(model, opt, rules, ef_compression)
    return jax.jit(lambda: create_train_state(
        model, opt, jax.random.key(seed), ef_compression),
        out_shardings=shardings)()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ef-compression", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20),
                      total_steps=args.steps,
                      state_dtype=cfg.optimizer_state_dtype)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq_len,
                                  global_batch=args.global_batch, seed=0))
    mesh = make_local_mesh(args.model_parallel)
    rules = default_rules(mesh)
    ckpt = AsyncCheckpointer(args.ckpt_dir, keep=3)
    straggler = StragglerDetector()

    def run(resume):
        with mesh, axis_rules(rules):
            step_fn = jax.jit(build_train_step(
                model, opt, use_ef_compression=args.ef_compression))
            if resume:
                template, shardings = state_shardings(
                    model, opt, rules, args.ef_compression)
                state = restore_checkpoint(resume, template, shardings)
                start = int(state["opt_state"]["step"])
                print(f"[resume] from step {start}")
            else:
                state = fresh_train_state(model, opt, rules,
                                          args.ef_compression)
                start = 0
            for i in range(start, args.steps):
                t0 = time.perf_counter()
                batch = {k: jnp.asarray(v)
                         for k, v in data.batch(i).items()}
                state, metrics = step_fn(state, batch)
                dt = time.perf_counter() - t0
                straggler.record(jax.process_index(), dt)
                if (i + 1) % args.log_every == 0 or i == start:
                    print(f"step {i + 1:5d} loss {float(metrics['loss']):.4f}"
                          f" gnorm {float(metrics['grad_norm']):.3f}"
                          f" lr {float(metrics['lr']):.2e} {dt:.2f}s",
                          flush=True)
                if (i + 1) % args.ckpt_every == 0 or i + 1 == args.steps:
                    ckpt.save(i + 1, state)
            ckpt.wait()

    run_with_restarts(run, lambda: latest_checkpoint(args.ckpt_dir),
                      RestartPolicy(max_failures=3, backoff_s=1.0))
    print("training complete")


if __name__ == "__main__":
    raise SystemExit(main())
