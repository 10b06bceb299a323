# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One bench function per paper table/figure (see DESIGN.md §6 for the index)
plus the TPU-side roofline/autotune benches.  Each emits
``name,us_per_call,derived`` CSV rows and writes richer JSON artifacts to
``experiments/bench/``.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (substring match)")
    ap.add_argument("--skip-slow", action="store_true",
                    help="skip the multi-minute network studies")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="stream spans/counters to this .trace.jsonl "
                         "(render with python -m repro.obs to-perfetto)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.trace:
        from repro import obs
        obs.configure(args.trace, process_name="benchmarks")

    # module:function, imported lazily per selected bench — a filtered
    # run must not import the others' dependencies (e.g. the TPU benches
    # pull in jax, whose threads would force the search-speed sweep's
    # process pool onto the expensive spawn start method)
    benches = [
        ("search_speed", "search_speed:bench_search_speed"),
        ("registry_warmstart", "registry_warmstart:bench_registry_warmstart"),
        ("serving_throughput", "serving_throughput:bench_serving_throughput"),
        ("network_dse", "network_dse:bench_network_dse"),
        ("obs_trace", "trace_demo:bench_obs_trace"),
        ("calibration", "calibration:bench_calibration"),
        ("chaos", "chaos:bench_chaos"),
        ("table2", "paper_mm:bench_table2"),
        ("fig1_fig15", "paper_mm:bench_fig1_fig15"),
        ("table3", "paper_mm:bench_table3"),
        ("table4_fig5", "paper_mm:bench_table4_fig5"),
        ("fig6", "paper_cnn:bench_fig6"),
        ("fig7_8_9", "paper_mm:bench_fig7_8_9"),
        ("fig10_table6", "paper_mm:bench_fig10_table6"),
        ("fig11_13_14_table7", "paper_cnn:bench_fig11_13_14"),
        ("roofline_table", "roofline:bench_roofline_table"),
        ("kernel_autotune", "roofline:bench_kernel_autotune"),
    ]
    # network_dse runs the whole-graph studies: multi-minute, like the
    # fig11_13_14 network sweeps (its CI entry is the --smoke CLI)
    slow = {"fig11_13_14_table7", "fig7_8_9", "network_dse"}

    if args.only:
        # every comma token must select at least one bench — a typo'd
        # --only would otherwise "pass" by silently running nothing
        known = [name for name, _ in benches]
        bad = [tok for tok in args.only.split(",")
               if not any(tok in name for name in known)]
        if bad:
            print(f"unknown bench name(s): {', '.join(bad)}\n"
                  f"valid names: {', '.join(known)}", file=sys.stderr)
            raise SystemExit(2)

    print("name,us_per_call,derived")
    failures = []
    for name, spec in benches:
        if args.only and not any(tok in name
                                 for tok in args.only.split(",")):
            continue
        if args.skip_slow and name in slow:
            continue
        t0 = time.time()
        try:
            import importlib
            mod_name, fn_name = spec.split(":")
            fn = getattr(importlib.import_module(f"benchmarks.{mod_name}"),
                         fn_name)
            fn()
        except Exception as e:  # noqa: BLE001 — report, keep benching
            failures.append((name, repr(e)))
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    raise SystemExit(main())
