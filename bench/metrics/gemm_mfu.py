"""Device: the whole window's GEMM FLOPs over the chip's bf16 peak times
the window, in percent: the share of the chip the step's GEMMs used."""


def read(run):
    if "pass_flops" not in run.counts or not run.window_s:
        return None
    return 100.0 * run.counts["passes"] * run.counts["pass_flops"] / (
        run.peak["bf16_flops"] * run.window_s)
