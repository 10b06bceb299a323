"""In-process metrics: counters, gauges, streaming histograms.

The :class:`Metrics` registry is the aggregate twin of the event-stream
:class:`~repro.obs.trace.Tracer`: where the tracer answers *when did it
happen*, metrics answer *how often / how slow overall*.  Everything is
cheap enough to leave on unconditionally — a counter bump is one dict
add under a lock-free fast path (the GIL serializes it), a histogram
observation two deque appends.

Histograms are **streaming**: each observation is stamped with the
``time.perf_counter`` at which it was recorded, and an optional
``window`` keeps only the most recent N observations (the rolling TTFT
/ tokens-per-sec percentiles ``ServeStats`` reports); unwindowed
histograms keep everything.  Empty histograms summarize to a
well-formed all-zero report — never raise — which is the contract the
zero-completed-requests serving path relies on.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple


def percentile(values, q: float) -> float:
    """Linear-interpolation percentile (numpy-free; 0.0 when empty)."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return 0.0
    if len(vals) == 1:
        return vals[0]
    pos = q * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    frac = pos - lo
    return vals[lo] * (1.0 - frac) + vals[hi] * frac


class Histogram:
    """Streaming histogram with p50/p95/p99; optionally windowed."""

    def __init__(self, name: str, window: Optional[int] = None):
        self.name = name
        self.window = window
        self._vals: deque = deque(maxlen=window)
        self._stamps: deque = deque(maxlen=window)
        self.count = 0                 # lifetime observations (window-free)
        self.total = 0.0

    def observe(self, value: float) -> None:
        """Record ``value``, stamped now on ``time.perf_counter``."""
        v = float(value)
        self._vals.append(v)
        self._stamps.append(time.perf_counter())
        self.count += 1
        self.total += v

    def stamped(self) -> List[Tuple[float, float]]:
        """The retained observations as ``(stamp, value)`` pairs."""
        return list(zip(self._stamps, self._vals))

    def extend(self, values: Iterable[float]) -> None:
        for v in values:
            self.observe(v)

    def percentile(self, q: float) -> float:
        return percentile(self._vals, q)

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def summary(self) -> Dict[str, float]:
        """All-zero (never raising) when nothing was observed."""
        vals = list(self._vals)
        return {
            "count": self.count,
            "mean": self.mean,
            "min": min(vals) if vals else 0.0,
            "max": max(vals) if vals else 0.0,
            "p50": percentile(vals, 0.50),
            "p95": percentile(vals, 0.95),
            "p99": percentile(vals, 0.99),
        }


class Metrics:
    """Named counters + gauges + histograms with one ``snapshot()``."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.histograms: Dict[str, Histogram] = {}

    def counter(self, name: str, inc: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + inc

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = float(value)

    def histogram(self, name: str,
                  window: Optional[int] = None) -> Histogram:
        h = self.histograms.get(name)
        if h is None:
            with self._lock:
                h = self.histograms.setdefault(name,
                                               Histogram(name, window))
        return h

    def observe(self, name: str, value: float,
                window: Optional[int] = None) -> None:
        self.histogram(name, window).observe(value)

    def snapshot(self) -> Dict:
        return {
            "counters": dict(self.counters),
            "gauges": dict(self.gauges),
            "histograms": {k: h.summary()
                           for k, h in self.histograms.items()},
        }

    def reset(self) -> None:
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            self.histograms.clear()


_metrics = Metrics()


def get_metrics() -> Metrics:
    """The process-global metrics registry."""
    return _metrics
