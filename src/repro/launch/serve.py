"""Serving launcher: load a checkpoint (or init), schedule requests, decode.

``python -m repro.launch.serve --arch smollm-135m --smoke --requests 8``

``--scheduler continuous`` (default) admits requests into free decode slots
mid-stream; ``--scheduler wave`` is the wave-synchronous baseline.
``--poisson-rate R`` replays a Poisson arrival trace at R requests/sec
instead of queueing everything at t=0.
"""

from __future__ import annotations

import argparse
import os

import jax

from repro.ckpt import latest_checkpoint, restore_params
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve import SCHEDULERS, ServeConfig, make_engine
from repro.serve.sim import poisson_requests


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--scheduler", default="continuous",
                    choices=sorted(SCHEDULERS))
    ap.add_argument("--eos-token", type=int, default=None,
                    help="stop decoding at this token id (default: decode "
                         "the full budget)")
    ap.add_argument("--poisson-rate", type=float, default=0.0,
                    help="request arrivals per second (0 = all queued at "
                         "t=0)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="stream spans/counters to this .trace.jsonl "
                         "(render with python -m repro.obs to-perfetto)")
    ap.add_argument("--registry-dir", default=None,
                    help="shared design-registry root; replicas pointing at "
                         "the same dir share tuned kernels (default: "
                         "$REPRO_REGISTRY_DIR if set, else disabled)")
    ap.add_argument("--pretune", action="store_true",
                    help="resolve every GEMM block config of the model's "
                         "layer graph (prefill + decode) through the "
                         "registry before serving; a replica against a "
                         "warm registry resolves all of them with 0 evals")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.trace:
        from repro import obs
        obs.configure(args.trace, process_name="serve")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    if args.ckpt_dir:
        path = latest_checkpoint(args.ckpt_dir)
        if path:
            params = restore_params(path, params)
            print(f"[serve] restored {path}")

    tuning = None
    from repro.registry import DEFAULT_ROOT_ENV
    registry_dir = args.registry_dir or os.environ.get(DEFAULT_ROOT_ENV)
    if registry_dir:
        from repro.registry import RegistryStore, TuningService
        tuning = TuningService(RegistryStore(registry_dir))

    if args.pretune:
        from repro.kernels.autotune import pretune_model_config
        stats = pretune_model_config(
            cfg, batch=args.max_batch, prefill_len=args.max_seq,
            registry=tuning.store if tuning is not None else None)
        print(f"[serve] pretune: {stats['shapes']} layer GEMM shapes — "
              f"{stats['tuned']} tuned, {stats['disk_hits']} from "
              f"registry, {stats['lru_hits']} from LRU")
        if tuning is None:
            print("[serve] pretune warning: no --registry-dir, configs "
                  "live only in this process's LRU")

    eng = make_engine(args.scheduler, model, params,
                      ServeConfig(max_batch=args.max_batch,
                                  max_seq=args.max_seq,
                                  eos_token=args.eos_token),
                      tuning=tuning)
    if tuning is not None:
        print(f"[serve] registry {registry_dir}: resolved "
              f"{len(eng.kernel_configs)} GEMM block shapes "
              f"({eng.kernel_stats['shared']} shared from other replicas, "
              f"{eng.kernel_stats['tuned']} tuned here)")

    requests = poisson_requests(args.requests, rate_rps=args.poisson_rate,
                                vocab_size=cfg.vocab_size,
                                prompt_len=range(2, 8),
                                max_new_tokens=args.max_new_tokens,
                                seed=args.seed)
    outs, stats = eng.serve(requests)
    print(stats.summary())
    for i, o in enumerate(outs[:4]):
        print(f"  req{i}: prompt={requests[i].prompt.tolist()} "
              f"-> {o.tolist()}")


if __name__ == "__main__":
    raise SystemExit(main())
