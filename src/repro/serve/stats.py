"""Serving request/metrics types of the continuous engine.

A :class:`Request` is what a client submits (prompt + decode budget +
arrival time for trace replay); a :class:`RequestMetrics` is what the
scheduler measured for it; a :class:`ServeStats` aggregates one serving run
into the report `launch/serve.py` prints and the benchmark's serving
window reads.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    """One generation request.  ``arrival_s`` is the offset from the start
    of the serving run at which the request becomes visible to the
    scheduler (0 = already queued), enabling Poisson-trace replay.

    ``request_id`` is a caller-side label surfaced in
    :class:`RequestMetrics` (-1 = auto-assign the input position); engine
    outputs are always returned in input order regardless of it.

    ``deadline_s`` is the per-request latency SLO, measured from
    ``arrival_s``: past it the request is evicted (``finish_reason
    "timeout"``, keeping whatever was generated) or never admitted.
    None falls back to ``ServeConfig.deadline_s`` (None = no deadline)."""
    prompt: np.ndarray
    max_new_tokens: int = 32
    arrival_s: float = 0.0
    request_id: int = -1
    deadline_s: Optional[float] = None


@dataclasses.dataclass
class RequestMetrics:
    request_id: int
    prompt_len: int
    new_tokens: int
    queue_wait_s: float        # arrival -> admitted into a slot
    ttft_s: float              # arrival -> first generated token
    decode_s: float            # first generated token -> last
    finish_reason: str         # "eos" | "length" | "timeout" | "shed"

    @property
    def decode_tps(self) -> float:
        """Steady-state decode rate (tokens after the first / decode time).

        0.0 when undefined — a single-token request, or a decode clocked
        at zero duration — so aggregates and JSON reports stay finite."""
        if self.new_tokens <= 1 or self.decode_s <= 0:
            return 0.0
        return (self.new_tokens - 1) / self.decode_s

    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d["decode_tps"] = self.decode_tps
        return d


@dataclasses.dataclass
class ServeStats:
    """Aggregate report for one serving run.

    Well-formed even when *zero* requests completed: every aggregate
    (throughput, percentiles, rolling windows, finish-reason counts) is
    all-zero/empty rather than raising, so a crashed or drained run still
    renders a report."""
    scheduler: str
    requests: List[RequestMetrics]
    wall_s: float
    decode_steps: int = 0      # jit'd decode-step invocations
    prefill_chunks: int = 0    # jit'd prefill/chunk invocations
    engine: str = ""           # engine-class provenance (which scheduler
    #                            implementation produced these numbers)
    # fault/overload accounting (DESIGN.md §15): every submitted request
    # is in ``requests`` exactly once — shed and timed-out ones included,
    # with finish_reason "shed"/"timeout" — so these are cross-checkable
    # against the finish_reasons histogram
    shed: int = 0              # never admitted (load shedding)
    timed_out: int = 0         # evicted past their deadline
    retried: int = 0           # decode ticks retried on transient errors

    @property
    def total_new_tokens(self) -> int:
        return sum(r.new_tokens for r in self.requests)

    @property
    def throughput_tps(self) -> float:
        return self.total_new_tokens / self.wall_s if self.wall_s > 0 else 0.0

    def _quantile(self, vals: List[float], q: float) -> float:
        return float(np.quantile(np.asarray(vals), q)) if vals else 0.0

    def ttft_s(self, q: float = 0.5) -> float:
        # shed/queue-timeout requests never produced a first token: their
        # placeholder ttft of 0.0 would *flatter* the percentile, so TTFT
        # aggregates only requests that actually started generating
        return self._quantile([r.ttft_s for r in self.requests
                               if r.new_tokens >= 1], q)

    def queue_wait_s(self, q: float = 0.5) -> float:
        return self._quantile([r.queue_wait_s for r in self.requests], q)

    def rolling(self, window: int = 64) -> Dict:
        """Windowed TTFT / decode-tok/s percentiles over the most recent
        ``window`` completed requests (all-zero when none completed)."""
        from repro.obs import Histogram
        ttft = Histogram("ttft_s", window=window)
        tps = Histogram("decode_tps", window=window)
        for r in self.requests:
            if r.new_tokens >= 1:      # see ttft_s: never-started requests
                ttft.observe(r.ttft_s)  # have no first token to clock
            tps.observe(r.decode_tps)
        return {"window": window, "ttft_s": ttft.summary(),
                "decode_tps": tps.summary()}

    def to_dict(self) -> Dict:
        return {
            "scheduler": self.scheduler,
            "engine": self.engine,
            "wall_s": self.wall_s,
            "requests": len(self.requests),
            "total_new_tokens": self.total_new_tokens,
            "throughput_tps": self.throughput_tps,
            "decode_steps": self.decode_steps,
            "prefill_chunks": self.prefill_chunks,
            "ttft_s_p50": self.ttft_s(0.5),
            "ttft_s_p95": self.ttft_s(0.95),
            "queue_wait_s_p50": self.queue_wait_s(0.5),
            "queue_wait_s_p95": self.queue_wait_s(0.95),
            "shed": self.shed,
            "timed_out": self.timed_out,
            "retried": self.retried,
            "rolling": self.rolling(),
            "finish_reasons": {
                reason: sum(1 for r in self.requests
                            if r.finish_reason == reason)
                for reason in sorted({r.finish_reason
                                      for r in self.requests})},
            # per-request provenance: rows from different runs stay
            # attributable after a benchmark merges engine reports
            "per_request": [dict(r.to_dict(), scheduler=self.scheduler,
                                 engine=self.engine)
                            for r in self.requests],
        }

    def summary(self) -> str:
        return (f"[{self.scheduler}] {len(self.requests)} requests, "
                f"{self.total_new_tokens} tokens in {self.wall_s:.2f}s "
                f"({self.throughput_tps:.1f} tok/s) | "
                f"ttft p50/p95 {self.ttft_s(0.5) * 1e3:.0f}/"
                f"{self.ttft_s(0.95) * 1e3:.0f} ms | "
                f"queue p95 {self.queue_wait_s(0.95) * 1e3:.0f} ms | "
                f"{self.decode_steps} decode steps, "
                f"{self.prefill_chunks} prefill chunks"
                + (f" | shed {self.shed}, timeout {self.timed_out}, "
                   f"retried {self.retried}"
                   if (self.shed or self.timed_out or self.retried)
                   else ""))


def as_requests(prompts: List[np.ndarray], max_new_tokens: int
                ) -> List[Request]:
    """Wrap plain prompt arrays as already-arrived requests."""
    return [Request(prompt=np.asarray(p, np.int32),
                    max_new_tokens=max_new_tokens, request_id=i)
            for i, p in enumerate(prompts)]
