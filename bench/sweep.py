#!/usr/bin/env python3
"""Knee sweep of a serving cell, on the chip: the highest rate it sustains.

    python3 bench/sweep.py --workload <cell> --rates 5 10 20 40 \\
        --seconds 15 --seed 1 [--out chiprun_out/sweep.jsonl]

One process builds the cell's engine once and serves the cell's mix at
each rate in turn (same lengths and burst pattern, another mean rate).
Per rate it prints the offered and completed output tokens/s, how far the
drain ran past the window, and the tails.  The knee is the highest rate
whose backlog does not grow: the drain stays a small part of the window
and completed tokens/s keep up with offered.  The cell's fixed rate in
``traffic/<mix>.json`` is about four fifths of it.
"""

import json
import sys
import time

import argparse  # noqa: E402
import os  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
# libtpu would log under a fixed /tmp path otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import device, generate, harness
    from bench.drivers import serve
    ns = argparse.Namespace(workload=args.workload, seed=args.seed,
                            seconds=args.seconds, trace=0)
    run = harness.prepare(ns, ROOT, time.perf_counter())
    device.require_chips(run.counts["chips"])
    cfg = run.config
    engine, _, _ = serve.build(cfg, args.seed)
    rows = []
    for rate in args.rates:
        mix = json.loads(json.dumps(run.traffic))
        mix["arrivals"]["rate_rps"] = rate
        reqs = serve.trace_requests(mix, args.seed, args.seconds,
                                    cfg["vocab_size"])
        offer = generate.offered(mix, args.seconds)
        outs, stats = engine.serve(reqs)
        e2e = serve.served(reqs, outs, stats)
        done = e2e.pop("done")
        row = dict(rate_rps=rate, requests=len(reqs),
                   failed=done.count(False),
                   offered_tokens_per_s=offer["output_tokens"] / args.seconds,
                   drain_s=stats.wall_s - reqs[-1].arrival_s,
                   wall_s=stats.wall_s,
                   queue_wait_p95_ms=serve.p95(
                       [m.queue_wait_s for m in stats.requests]) * 1e3,
                   **e2e)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if row["drain_s"] > args.seconds:
            break                     # far past the knee: stop here
    if args.out:
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps(dict(row, workload=args.workload)) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
