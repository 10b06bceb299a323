"""From a JAX profiler trace (``.xplane.pb``) to device numbers.

A traced run records exactly its measured window: the profiler starts
before the window's first request and stops after its last result is on
the host.  The reduction reads:

* the device planes (``/device:TPU:<n>``): their ``XLA Ops`` line gives
  busy time as the union of op intervals, and their ``XLA Modules`` line
  the device time of each jitted program, by its module name (``jit_<fn>``
  with the compile hash dropped);
* the host plane's threads: what the host was doing in each idle gap.

The trace of this JAX carries no scope path on device ops, so programs
are found by module name: the program's own jitted functions
(``jit_tick``, ``jit_chunk``) and the wrappers the benchmark names
(``jit_bench_gemm``, ``jit_bench_xla_dot``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

Interval = Tuple[int, int]          # (start_ns, end_ns)


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # Python frames would slow the host
    opts.host_tracer_level = 2        # keep the runtime's dispatch spans
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop(log_dir: str) -> str:
    """Stop tracing; the path of the ``.xplane.pb`` just written."""
    import jax
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime)


def module_name(event_name: str) -> str:
    """``jit_tick(1794...)`` -> ``jit_tick``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Device:
    """One device plane's events, in nanoseconds on the trace's clock."""
    ops: List[Tuple[str, int, int]]           # (op, start, end)
    modules: List[Tuple[str, int, int]]       # (module, start, end)


@dataclasses.dataclass
class Summary:
    busy_s: float                   # union of op time, mean over devices
    modules: Dict[str, Tuple[float, int]]     # name -> (device s, count)
    device_ops: List[Tuple[str, float]]       # top ops by total time
    idle_gaps: List[Tuple[str, float]]        # longest gaps, host label

    def module_time(self, name: str) -> Tuple[float, int]:
        return self.modules.get(name, (0.0, 0))


def read(path: str) -> Tuple[List[Device], List[Tuple[str, int, int]]]:
    """Device planes, and the spans of the host thread that dispatches
    the programs, as (name, start, end)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = [(module_name(e.name), int(e.start_ns),
                        int(e.end_ns)) for e in lines.get("XLA Modules", [])]
            names: Dict[str, str] = {}
            ops = []
            for e in lines.get("XLA Ops", []):
                name = names.get(e.name)
                if name is None:
                    name = names[e.name] = op_name(e.name)
                ops.append((name, int(e.start_ns), int(e.end_ns)))
            devices.append(Device(ops=ops, modules=modules))
        elif plane.name.startswith("/host:CPU"):
            # the thread that dispatches the jitted programs: the Python
            # main thread where one is named so, else the busiest caller
            lines = list(plane.lines)
            named = [ln for ln in lines if ln.name.startswith("python")]
            best = -1
            for line in named or lines:
                events = [(e.name, int(e.start_ns), int(e.end_ns))
                          for e in line.events if e.duration_ns > 0]
                calls = sum(1 for e in events
                            if e[0].startswith("PjitFunction"))
                if calls > best:
                    best, host = calls, events
    return devices, host


def _owner_index(modules: List[Tuple[str, int, int]]):
    """Module of an op by bisection over the (disjoint) module spans."""
    import bisect
    starts = [s for _, s, _ in modules]

    def owner(s: int, e: int) -> str:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and modules[i][1] <= s and e <= modules[i][2] + 1000:
            return modules[i][0]
        return "?"
    return owner


def summarize(devices: List[Device], host: List[Tuple[str, int, int]],
              top: int = 10) -> Summary:
    busy_ns = 0
    modules: Dict[str, List] = {}
    ops: Dict[str, int] = {}
    gaps: List[Tuple[int, int]] = []
    for dev in devices:
        merged = union([(s, e) for _, s, e in dev.ops])
        busy_ns += sum(e - s for s, e in merged)
        gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        for name, s, e in dev.modules:
            acc = modules.setdefault(name, [0, 0])
            acc[0] += e - s
            acc[1] += 1
        owner = _owner_index(sorted(dev.modules, key=lambda m: m[1]))
        for op, s, e in dev.ops:
            key = f"{owner(s, e)}/{op}"
            ops[key] = ops.get(key, 0) + (e - s)
    n = max(1, len(devices))
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return Summary(
        busy_s=busy_ns / n / 1e9,
        modules={k: (v[0] / 1e9, v[1]) for k, v in modules.items()},
        device_ops=[(k, v / 1e9) for k, v in top_ops],
        idle_gaps=[(host_label(host, s, e), (e - s) / 1e9)
                   for s, e in longest])


def host_label(host: List[Tuple[str, int, int]], s: int, e: int) -> str:
    """What the host was doing in the device gap [s, e): the shortest
    host span that covers the gap's middle, else the last one to end
    before it."""
    mid = (s + e) // 2
    covering = [h for h in host if h[1] <= mid < h[2]]
    if covering:
        return min(covering, key=lambda h: h[2] - h[1])[0]
    before = [h for h in host if h[2] <= mid]
    return ("after " + max(before, key=lambda h: h[2])[0]) if before \
        else "host untraced"


def reduce_file(path: str, top: int = 10) -> Summary:
    devices, host = read(path)
    return summarize(devices, host, top=top)
