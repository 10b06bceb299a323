"""Compile: seconds of set-up spent compiling, from the program's own
compile counter (``repro.obs`` ``compile.*_s`` histograms: tracing,
lowering, backend compile and persistent-cache retrieval, each stamped
on ``time.perf_counter`` when it ended).  The events nest, so this is
the length of the union of their intervals that end within set-up.
None where the program records no compile events."""

from bench import trace

HISTOGRAMS = ("compile.jaxpr_trace_s", "compile.jaxpr_to_mlir_module_s",
              "compile.backend_compile_s", "compile.cache_retrieval_s")


def read(run):
    try:
        from repro.obs import get_metrics
    except ImportError:
        return None
    found = [h for name, h in get_metrics().histograms.items()
             if name in HISTOGRAMS and hasattr(h, "stamped")]
    if not found:
        return None
    setup_end = run.t_process + run.setup_s
    return sum(e - s for s, e in trace.union(
        [(t - s, t) for h in found for t, s in h.stamped()
         if t <= setup_end]))
