"""Compile events into the metrics registry.

A once-only ``jax.monitoring`` listener observes each of JAX's compile
events into ``obs.Metrics`` as a histogram of seconds, stamped on
``time.perf_counter`` when the event ended:

====================================================  ==============================
JAX event                                             histogram
====================================================  ==============================
``/jax/core/compile/jaxpr_trace_duration``            ``compile.jaxpr_trace_s``
``/jax/core/compile/jaxpr_to_mlir_module_duration``   ``compile.jaxpr_to_mlir_module_s``
``/jax/core/compile/backend_compile_duration``        ``compile.backend_compile_s``
``/jax/compilation_cache/cache_retrieval_time_sec``   ``compile.cache_retrieval_s``
====================================================  ==============================

The events nest: a backend compile holds its persistent-cache retrieval,
and tracing an outer jit holds the tracing of the jits it calls.  A
stamp and a duration give each event's interval, so a reader can take
their union.  The kernels and serving packages install the listener at
import, before their first jit.
"""

from __future__ import annotations

import threading

from .metrics import get_metrics

HISTOGRAMS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.jaxpr_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "compile.jaxpr_to_mlir_module_s",
    "/jax/core/compile/backend_compile_duration": "compile.backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "compile.cache_retrieval_s",
}

_installed = False
_lock = threading.Lock()


def _listener(event: str, duration: float, **kwargs) -> None:
    name = HISTOGRAMS.get(event)
    if name is not None:
        get_metrics().observe(name, duration)


def install_compile_listener() -> None:
    """Register the listener with ``jax.monitoring`` (once a process)."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_listener)
        _installed = True
