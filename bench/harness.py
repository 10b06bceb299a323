"""One run of one cell: find its files by name, drive it, print the line.

Nothing here knows a cell.  ``BENCHMARK.json`` names the cell's
configuration and traffic mix; the mix names its driver
(``drivers/<driver>.py``), and each metric of the cell is either an
end-to-end number the driver measured or a per-layer number read by
``metrics/<metric>.py``.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Run:
    """What a driver measures and the readers read."""
    workload: str
    seed: int
    seconds: float
    trace: bool
    config: Dict
    traffic: Dict
    t_process: float                  # perf_counter at process start
    devices: List = dataclasses.field(default_factory=list)
    peak: Dict = dataclasses.field(default_factory=dict)
    setup_s: float = 0.0
    window_s: float = 0.0
    memory_peak_bytes: int = 0
    attempted: int = 0
    failed: int = 0
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    counts: Dict = dataclasses.field(default_factory=dict)
    stats: object = None              # the serving engine's ServeStats
    summary: object = None            # trace.Summary of the window
    extra: Dict[str, object] = dataclasses.field(default_factory=dict)
    checks: Dict[str, Dict] = dataclasses.field(default_factory=dict)
    compiles_in_window: int = 0
    control: Optional[str] = None     # judge the control in the program's place
    _trace_dir: Optional[str] = None
    _t_window: float = 0.0

    # -- the phases a driver marks ------------------------------------- #
    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_process

    def open_window(self) -> None:
        from . import trace
        _count_compiles(self, True)
        if self.trace:
            self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            trace.start(self._trace_dir)
        self._t_window = time.perf_counter()

    def close_window(self) -> None:
        """Call once the window's last result is on the host."""
        from . import device, trace
        self.window_s = time.perf_counter() - self._t_window
        _count_compiles(self, False)
        if self.trace:
            t = time.perf_counter()
            path = trace.stop(self._trace_dir)
            t_stop = time.perf_counter() - t
            self.summary = trace.reduce_file(path)
            print(f"trace: stop {t_stop:.1f} s, reduce "
                  f"{time.perf_counter() - t - t_stop:.1f} s",
                  file=sys.stderr)
            shutil.rmtree(self._trace_dir, ignore_errors=True)
        self.memory_peak_bytes = device.memory_peak_bytes(self.devices)

    def traced(self, name: str, fn) -> None:
        """Run ``fn`` under a trace of its own, kept as ``extra[name]``
        (a comparison that is not part of the window)."""
        from . import trace
        d = tempfile.mkdtemp(prefix="bench-trace-")
        trace.start(d)
        fn()
        self.extra[name] = trace.reduce_file(trace.stop(d))
        shutil.rmtree(d, ignore_errors=True)

    def elapsed(self) -> float:
        """Seconds since the window opened."""
        return time.perf_counter() - self._t_window

    def check(self, name: str, value: float, limit: Optional[float]) -> None:
        """A number compared for ``correct``: it passes at or under its
        limit; a missing limit fails."""
        self.checks[name] = {"value": value, "limit": limit}

    @property
    def correct(self) -> bool:
        return bool(self.checks) and self.failed == 0 and all(
            c["limit"] is not None and math.isfinite(c["value"])
            and c["value"] <= c["limit"] for c in self.checks.values())


_compile_listener = None


def _count_compiles(run: Run, start: bool) -> None:
    """Count backend compiles between open_window and close_window."""
    global _compile_listener
    import jax
    if _compile_listener is None:
        state = {"run": None}

        def listener(event, duration, **kw):
            r = state["run"]
            if r is not None and event.endswith("backend_compile_duration"):
                r.compiles_in_window += 1
        jax.monitoring.register_event_duration_secs_listener(listener)
        _compile_listener = state
    _compile_listener["run"] = run if start else None


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def read_metric(name: str, run: Run) -> Optional[float]:
    """The per-layer reader ``metrics/<name>.py``: ``read(run)``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def parse(argv: List[str]) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def prepare(args: argparse.Namespace, root: Path, t_process: float
            ) -> Run:
    """The cell's run record, from ``BENCHMARK.json`` and its files."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[args.workload]
    run = Run(workload=args.workload, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace),
              config=load_json(BENCH / "configs" / f"{cell['config']}.json"),
              traffic=load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
              t_process=t_process)
    run.counts["chips"] = cell["chips"]
    run.counts["benchmark"] = bench
    return run


def drive(run: Run) -> None:
    driver = importlib.import_module(f"bench.drivers.{run.traffic['driver']}")
    driver.run(run)


def result(run: Run) -> Dict:
    """The result line's object; ``checks`` comes last."""
    bench = run.counts["benchmark"]
    metrics: Dict[str, Dict] = {}
    if run.trace:
        for m in bench["per_layer"]:
            if applies(m, run.workload):
                v = read_metric(m["name"], run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(run.e2e, setup_s=run.setup_s)
        for m in bench["end_to_end"]:
            if applies(m, run.workload) and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    from . import device
    dev = device.describe(run.devices)
    dev["memory_peak_bytes"] = run.memory_peak_bytes
    out: Dict = {"correct": run.correct, "attempted": run.attempted,
                 "failed": run.failed, "metrics": metrics, "device": dev}
    if run.trace and run.summary is not None:
        dev["busy_s"] = run.summary.busy_s
        dev["window_s"] = run.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.summary.device_ops],
            "idle_gaps": [[n, s] for n, s in run.summary.idle_gaps]}
    out["checks"] = run.checks
    return out


def main(argv: List[str], root: Path, t_process: float,
         control: Optional[str] = None) -> int:
    """One run; with ``control`` (the self-tests' way in), the plain
    reference in that lower precision is judged in the program's place."""
    args = parse(argv)
    run = prepare(args, root, t_process)
    run.control = control
    from . import device
    try:
        run.devices = device.require_chips(run.counts["chips"])
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    run.peak = device.peak(run.devices)
    drive(run)
    line = result(run)
    print(f"window {run.window_s:.3f} s, setup {run.setup_s:.3f} s, "
          f"compiles in window {run.compiles_in_window}", file=sys.stderr)
    for name, c in run.checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
