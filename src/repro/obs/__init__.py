"""Observability spine: tracing + metrics for search, registry, serving.

One subsystem (DESIGN.md §12) gives every layer of the stack the same
primitives:

  * :class:`Tracer` — spans / instants / counters streamed as JSONL,
    process-safe (the ``SearchSession`` pool's workers and the parent
    share one file), no-op by default with a gated <2% overhead; while
    a JAX profiler session is active, spans and instants also land in
    the profiler trace as ``jax.profiler.TraceAnnotation`` events;
  * :class:`Metrics` — counters, gauges and streaming histograms with
    p50/p95/p99, always on (aggregates are cheap);
  * :func:`install_compile_listener` — JAX's compile events as
    ``compile.*_s`` histograms in :class:`Metrics`;
  * ``obs.perfetto`` — the JSONL trace rendered as Chrome trace-event
    JSON that https://ui.perfetto.dev opens directly, plus text
    summaries (``python -m repro.obs summarize|to-perfetto``).

Typical wiring (what ``--trace PATH`` does in ``launch/serve.py``,
``python -m repro.network`` and ``benchmarks/run.py``)::

    from repro import obs
    obs.configure("run.trace.jsonl")     # global, inherited by forks
    ... run a sweep / serve a trace ...
    # then: python -m repro.obs to-perfetto run.trace.jsonl
"""

from .trace import Tracer, configure, disable, get_tracer
from .metrics import Histogram, Metrics, get_metrics, percentile
from .compile import install_compile_listener
from .perfetto import (format_summary, load_events, summarize,
                       to_perfetto)

__all__ = [
    "Tracer", "configure", "disable", "get_tracer",
    "Histogram", "Metrics", "get_metrics", "percentile",
    "install_compile_listener",
    "load_events", "to_perfetto", "summarize", "format_summary",
]
