"""Kernels: XLA's own ``ragged_dot`` on the same sorted rows, weights and
group sizes (module ``jit_bench_xla_ragged``, traced after the window)
over the grouped kernel's ops in the window, in device time per pass
(above 1: the grouped kernel is faster)."""

from bench.metrics.gmm_roofline import kernel_seconds


def read(run):
    xla = run.extra.get("xla_ragged")
    if run.summary is None or xla is None:
        return None
    pallas = kernel_seconds(run.summary)
    dot, n = xla.module_time("jit_bench_xla_ragged")
    if not pallas or not n:
        return None
    return (dot / run.counts["xla_passes"]) / (pallas / run.counts["passes"])
