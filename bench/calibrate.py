#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 101 102 ... \\
        --seconds 8 [--control fp8] [--out chiprun_out/calib.jsonl]

One process runs the cell's own window (at its own load, over a short
``--seconds``) once per seed and prints, per seed, each number compared
for ``correct`` as the program gives it and, with ``--control``, as the
plain reference computed in that lower precision gives it at the same
positions.  With ``--control`` the control is judged in the program's
place, as a run would judge the program, and each seed's ``correct``
is that verdict: the command exits 1 where a control run reads correct,
or where a run without it does not.  The limits in
``configs/<config>.json`` lie between the largest program reading and
the smallest control reading.  The benchmark's own runs never run the
control.
"""

import json
import sys
import time

T_PROCESS = time.perf_counter()

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
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--control", default=None)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import device, harness
    rows = []
    for seed in args.seeds:
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0)
        run = harness.prepare(ns, ROOT, time.perf_counter())
        run.devices = device.require_chips(run.counts["chips"])
        run.peak = device.peak(run.devices)
        run.control = args.control
        harness.drive(run)
        judged = {k: c["value"] for k, c in run.checks.items()}
        row = {"seed": seed, "correct": run.correct,
               "attempted": run.attempted, "failed": run.failed,
               "program": run.counts.get("program", judged),
               "control": judged if args.control else None,
               "e2e": run.e2e, "window_s": run.window_s,
               "memory_peak_bytes": run.memory_peak_bytes}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del run
    if args.out:
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps(dict(row, workload=args.workload)) + "\n")
    for name in rows[0]["program"]:
        prog = [r["program"][name] for r in rows]
        line = f"{name}: program max {max(prog)!r} over {len(prog)} seeds"
        ctrl = [r["control"][name] for r in rows if r["control"]]
        if ctrl:
            line += f", control min {min(ctrl)!r} over {len(ctrl)} seeds"
        print(line, flush=True)
    wrong = [r["seed"] for r in rows if r["correct"] == bool(args.control)]
    if wrong:
        print(f"{'control' if args.control else 'program'} read "
              f"{'correct' if args.control else 'not correct'} on seeds "
              f"{wrong}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
