"""Kernels: the lowest roofline share of a GEMM class, in percent.

The tuned kernel's device ops are named
``matmul_{M}x{N}x{K}_{bm}x{bk}x{bn}_{ki|ko}``.  Each (M, N, K) that holds
at least 5% of those ops' device time is a class; its share is its calls
in the window (occurrences a pass times passes) times the least time the
chip could take for one, over its device time.  None where no op carries
such a name.

It reads ``summary.device_ops``, which holds only the ten ops with the
most device time (``bench.trace.reduce_file(top=10)``): both the classes
and the kernels' total that the 5% cut is taken of come from those ten.
A cell whose step issues more named kernel ops than that loses classes
silently; the GEMM cells issue five."""

import re

from bench import counters

NAME = re.compile(r"(?:^|/)matmul_(\d+)x(\d+)x(\d+)_\d+x\d+x\d+_k[io]$")
MIN_SHARE = 0.05


def class_times(summary):
    """Device seconds per (M, N, K) of the named kernel ops."""
    times = {}
    for name, seconds in summary.device_ops:
        m = NAME.search(name)
        if m:
            shape = tuple(int(g) for g in m.groups())
            times[shape] = times.get(shape, 0.0) + seconds
    return times


def class_rooflines(run):
    """Roofline share in percent per (M, N, K) class of the window."""
    if run.summary is None or "passes" not in run.counts:
        return {}
    times = class_times(run.summary)
    total = sum(times.values())
    mix = run.traffic
    rows = mix["batch"] * (mix["prefill_len"] if mix["stage"] == "prefill"
                           else 1)
    counts = dict(counters.gemm_classes(run.config, rows))
    return {shape: 100.0 * counts[shape] * run.counts["passes"]
            * counters.gemm_min_seconds(shape, run.peak) / t
            for shape, t in times.items()
            if t >= MIN_SHARE * total and shape in counts}


def read(run):
    shares = class_rooflines(run)
    return min(shares.values()) if shares else None
