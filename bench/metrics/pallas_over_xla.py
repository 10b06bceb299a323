"""Kernels: XLA's own dot on the same inputs over the tuned kernel, in
device time per pass (above 1: the tuned kernel is faster)."""


def read(run):
    xla = run.extra.get("xla")
    if run.summary is None or xla is None:
        return None
    pallas, n = run.summary.module_time("jit_bench_gemm")
    dot, m = xla.module_time("jit_bench_xla_dot")
    if not n or not m:
        return None
    return (dot / run.counts["xla_passes"]) / (pallas / run.counts["passes"])
