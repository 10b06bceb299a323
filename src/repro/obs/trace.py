"""Process-safe structured tracing: spans, instants, counters -> JSONL.

One :class:`Tracer` per process tree.  Disabled (the default) every hook
is a single attribute check plus an early return — the <2% overhead
policy DESIGN.md §12 documents and ``benchmarks/search_speed.py`` gates.
Enabled, each event is serialized to one JSON line and appended with a
single ``os.write`` on an ``O_APPEND`` descriptor, which Linux keeps
atomic per call: the ``SearchSession`` process pool, a forked worker and
the parent can all stream into the *same* ``.trace.jsonl`` without
interleaving corruption (every line parses, whoever wrote it).

Fork/spawn safety:

  * **fork** — children inherit the configured tracer.  The descriptor
    is reopened on first emit from a new pid (``_fd_for_pid``), so the
    child never shares the parent's file-object buffering, and every
    event records the *emitting* pid/tid.
  * **spawn** — a fresh interpreter starts with the disabled tracer;
    pass the path through the worker initializer and call
    :func:`configure` there (``core.engine._pool_init`` does).

Event schema (one JSON object per line; ``ts``/``dur`` are microseconds
on the machine-wide monotonic clock, so events from different processes
order correctly):

    {"ev": "span",    "name", "cat", "ts", "dur", "pid", "tid", "args"}
    {"ev": "instant", "name", "cat", "ts",        "pid", "tid", "args"}
    {"ev": "counter", "name",        "ts",        "pid", "tid", "values"}
    {"ev": "meta",    "name": "process_name",     "pid", "args": {...}}

Spans are emitted on *exit* as complete events (Chrome "X" phase), so a
trace is balanced by construction — ``obs.perfetto`` converts it 1:1 to
the Chrome trace-event JSON Perfetto loads.

Profiler sink: while a JAX profiler session is active in the process,
every span is also a ``jax.profiler.TraceAnnotation`` (its ``args`` as
the event's stats) and every instant a zero-length one, JSONL file or
not.  They land on the host plane of the profiler trace, on the device
trace's clock.  jax is never imported here: a process that has not
imported it cannot have a profiler session.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Dict, Optional


def _now_us() -> float:
    """Microseconds on the monotonic clock (comparable across the
    processes of one machine — CLOCK_MONOTONIC is boot-anchored)."""
    return time.monotonic_ns() / 1e3


_annotation = None      # jax.profiler.TraceAnnotation, once jax is loaded


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` if a profiler session is active
    in this process, else None.  Looks jax up only where it is already
    imported, so the check never imports it (and the fork-safe search
    stays jax-free)."""
    global _annotation
    cls = _annotation
    if cls is None:
        profiler = sys.modules.get("jax.profiler")
        if profiler is None:
            return None
        cls = _annotation = profiler.TraceAnnotation
    return cls if cls.is_enabled() else None


class _NullSpan:
    """Shared no-op context manager returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        """Arguments known only inside the span (dropped here)."""


_NULL_SPAN = _NullSpan()


class _Span:
    """A span to the JSONL file (``tracer``), the profiler (``annotation``,
    the TraceAnnotation class), or both."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_annotation",
                 "_live")

    def __init__(self, tracer: Optional["Tracer"], name: str, cat: str,
                 args: Dict, annotation=None):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._annotation = annotation

    def set(self, **args) -> None:
        """Add arguments known only inside the span."""
        self._args.update(args)

    def __enter__(self):
        if self._annotation is not None:
            self._live = self._annotation(self._name)
            self._live.__enter__()
        self._t0 = _now_us()
        return self

    def __exit__(self, *exc):
        t1 = _now_us()
        if self._annotation is not None:
            if self._args:
                self._live.set_metadata(**self._args)
            self._live.__exit__(*exc)
        if self._tracer is not None:
            self._tracer._emit({"ev": "span", "name": self._name,
                                "cat": self._cat, "ts": self._t0,
                                "dur": t1 - self._t0, "args": self._args})
        return False


class Tracer:
    """Structured-event sink.  ``enabled`` is the hot-path gate: callers
    in loops should read it once and skip building kwargs entirely."""

    def __init__(self, path: Optional[str] = None,
                 process_name: Optional[str] = None):
        self.path = path
        self.enabled = path is not None
        self.process_name = process_name
        self._fds: Dict[int, int] = {}      # pid -> O_APPEND descriptor
        self._lock = threading.Lock()
        if self.enabled and process_name:
            self._emit({"ev": "meta", "name": "process_name",
                        "args": {"name": process_name}})

    # -- event API -------------------------------------------------------
    def span(self, name: str, cat: str = "", **args):
        """Context manager; emits one complete span event on exit, and
        annotates the profiler trace while a session is active.  With
        neither sink it is the shared no-op span."""
        ann = _annotation_class()
        if not self.enabled:
            return _NULL_SPAN if ann is None else \
                _Span(None, name, cat, args, ann)
        return _Span(self, name, cat, args, ann)

    def instant(self, name: str, cat: str = "", **args) -> None:
        ann = _annotation_class()
        if ann is not None:
            with ann(name, **args):
                pass
        if not self.enabled:
            return
        self._emit({"ev": "instant", "name": name, "cat": cat,
                    "ts": _now_us(), "args": args})

    def counter(self, name: str, **values) -> None:
        """One sample of a (multi-series) counter track."""
        if not self.enabled:
            return
        self._emit({"ev": "counter", "name": name, "ts": _now_us(),
                    "values": values})

    # -- sink ------------------------------------------------------------
    def _fd_for_pid(self, pid: int) -> int:
        fd = self._fds.get(pid)
        if fd is None:
            with self._lock:
                fd = self._fds.get(pid)
                if fd is None:
                    fd = os.open(self.path,
                                 os.O_WRONLY | os.O_APPEND | os.O_CREAT,
                                 0o644)
                    # forget descriptors inherited from other pids; they
                    # belong to (and will be closed by) their opener
                    self._fds = {pid: fd}
        return fd

    def _emit(self, ev: Dict) -> None:
        pid = os.getpid()
        ev.setdefault("pid", pid)
        ev.setdefault("tid", threading.get_ident() & 0x7FFFFFFF)
        line = json.dumps(ev, separators=(",", ":"),
                          default=str) + "\n"
        # one write() per event: O_APPEND makes concurrent writers from
        # any process/thread land whole lines
        os.write(self._fd_for_pid(pid), line.encode())

    def close(self) -> None:
        with self._lock:
            for fd in self._fds.values():
                try:
                    os.close(fd)
                except OSError:
                    pass
            self._fds = {}
        self.enabled = False


_DISABLED = Tracer(None)
_tracer = _DISABLED


def get_tracer() -> Tracer:
    """The process-global tracer (disabled unless :func:`configure`d)."""
    return _tracer


def configure(path: Optional[str],
              process_name: Optional[str] = None) -> Tracer:
    """Install (or, with ``path=None``, disable) the global tracer.

    Appends to ``path`` — delete the file beforehand for a fresh trace;
    appending is what lets every process of a sweep share one sink.
    """
    global _tracer
    if _tracer is not _DISABLED:
        _tracer.close()
    _tracer = Tracer(path, process_name=process_name) if path else _DISABLED
    return _tracer


def disable() -> None:
    configure(None)
