"""Kernels: the least time the chip could take for the window's GEMMs
(the larger of 2MNK over peak FLOP/s and the operands' and result's bytes
over HBM bandwidth, call by call) over the device time of the programs
that ran them, in percent."""


def read(run):
    if run.summary is None or "pass_min_s" not in run.counts:
        return None
    total, count = run.summary.module_time("jit_bench_gemm")
    if not count:
        return None
    return 100.0 * run.counts["pass_min_s"] * run.counts["passes"] / total
