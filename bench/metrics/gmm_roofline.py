"""Kernels: the least time the chip could take for the window's grouped
calls, over the device time of the grouped kernel, in percent.

The least time is summed over the pass's grouped calls and their groups,
on the pass's real group sizes (``bench/moe_counters.py``), times the
passes.  The kernel's device ops are named
``gmm_{R}x{N}x{K}_g{E}_{bm}x{bk}x{bn}``.  Padding of partial tiles lowers
it.  None where no op carries such a name."""

import re

NAME = re.compile(r"(?:^|/)gmm_\d+x\d+x\d+_g\d+_\d+x\d+x\d+$")


def kernel_seconds(summary) -> float:
    """Device seconds of the grouped kernel's named ops."""
    return sum(s for name, s in summary.device_ops if NAME.search(name))


def read(run):
    if run.summary is None or "gmm_min_s" not in run.counts:
        return None
    t = kernel_seconds(run.summary)
    if not t:
        return None
    return 100.0 * run.counts["gmm_min_s"] * run.counts["passes"] / t
