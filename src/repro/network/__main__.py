"""Network-level DSE CLI.

CONV networks (systolic-array DSE over the whole layer graph):

    python -m repro.network --model vgg16 --k 1 2 4 --json out.json
    python -m repro.network --model resnet50 --registry-dir /tmp/reg

Model configs (GEMM graph; ``--pretune`` resolves the Pallas block config
of every GEMM the model issues through the shared registry, so a later
process against the same registry resolves them with 0 evals):

    python -m repro.network --model smollm-135m --smoke --batch 4 \
        --prefill 256 --pretune --registry-dir /tmp/reg
"""

from __future__ import annotations

import argparse
import json
import os

from repro.core import EvoConfig

from .assign import AssignConfig
from .graph import model_config_graph, resnet50_graph, vgg16_graph
from .session import NetworkSession

CONV_MODELS = ("vgg16", "resnet50")


def build_graph(args):
    if args.model == "vgg16":
        g = vgg16_graph()
    elif args.model == "resnet50":
        g = resnet50_graph()
    else:
        from repro.configs import ARCH_IDS, get_config, get_smoke_config
        if args.model not in ARCH_IDS:
            raise SystemExit(
                f"unknown model {args.model!r}; expected one of "
                f"{CONV_MODELS + tuple(ARCH_IDS)}")
        cfg = get_smoke_config(args.model) if args.smoke \
            else get_config(args.model)
        return model_config_graph(cfg, batch=args.batch,
                                  prefill_len=args.prefill)
    if args.smoke:
        g = type(g)(name=g.name + ":smoke", nodes=g.nodes[:4])
    return g


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.network")
    ap.add_argument("--model", default="vgg16",
                    help="vgg16 | resnet50 | any --arch id from "
                         "repro.configs")
    ap.add_argument("--smoke", action="store_true",
                    help="small graph / smoke model config")
    ap.add_argument("--k", type=int, nargs="+", default=[1, 2, 4],
                    help="array-count budgets to solve")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--prefill", type=int, default=512)
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--population", type=int, default=40)
    ap.add_argument("--retune-evals", type=int, default=240)
    ap.add_argument("--reconfig-cycles", type=float, default=3.0e5,
                    help="fabric switch cost (~1 ms at 300 MHz)")
    ap.add_argument("--amortize-over", type=int, default=16,
                    help="inferences pipelined through each segment per "
                         "reconfiguration sweep")
    ap.add_argument("--registry-dir", default=None,
                    help="persistent design registry root (warm second "
                         "runs resolve every class with 0 evals)")
    ap.add_argument("--pretune", action="store_true",
                    help="model configs only: resolve every Pallas matmul "
                         "block config through the registry and exit")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="stream spans/counters to this .trace.jsonl "
                         "(render with python -m repro.obs to-perfetto)")
    ap.add_argument("--json", default=None, help="write the report here")
    args = ap.parse_args(argv)

    if args.trace:
        from repro import obs
        obs.configure(args.trace, process_name="network")

    registry = None
    if args.registry_dir:
        from repro.registry import RegistryStore
        registry = RegistryStore(args.registry_dir)

    graph = build_graph(args)
    print(f"[network] {graph.name}: {sum(n.count for n in graph.nodes)} "
          f"layers, {len(graph.classes())} shape classes")

    if args.pretune:
        if args.model in CONV_MODELS:
            raise SystemExit("--pretune applies to model configs "
                             "(Pallas GEMM blocks), not CONV networks")
        from repro.kernels.autotune import pretune_gemms
        stats = pretune_gemms(graph.gemm_shapes(), registry=registry)
        print(f"[network] pretune: {stats['shapes']} shapes — "
              f"{stats['tuned']} tuned, {stats['disk_hits']} from "
              f"registry, {stats['lru_hits']} from LRU, "
              f"{stats['evals']} search evals")
        if args.json:
            with open(args.json, "w") as f:
                json.dump(stats, f, indent=2)
        return 0

    sess = NetworkSession(
        graph,
        cfg=EvoConfig(epochs=args.epochs, population=args.population,
                      seed=0),
        registry=registry,
        assign=AssignConfig(max_arrays=max(args.k),
                            reconfig_cycles=args.reconfig_cycles,
                            amortize_over=args.amortize_over,
                            retune_evals=args.retune_evals))
    report = sess.run(k_values=args.k)

    print(f"[network] per-layer ideal: {report.per_layer_cycles:.3e} cyc, "
          f"evals spent: {report.total_evals}")
    for k, a in sorted(report.assignments.items()):
        frac = report.per_layer_cycles / a["latency_cycles"]
        print(f"[network] K={k}: {a['latency_cycles']:.3e} cyc "
              f"({a['n_arrays']} arrays, {frac:.2%} of ideal)")
    for p in report.pareto:
        print(f"[network] pareto {p.label}: lat={p.latency_cycles:.3e} "
              f"dsp={p.dsp} bram={p.bram}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report.as_json(), f, indent=2, default=str)
        print(f"[network] wrote {os.path.abspath(args.json)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
