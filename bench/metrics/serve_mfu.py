"""Device: useful model FLOPs of the served requests over the chip's
bf16 peak times the serving wall time, in percent."""


def read(run):
    if "useful_flops" not in run.counts:
        return None
    return 100.0 * run.counts["useful_flops"] / (
        run.peak["bf16_flops"] * run.counts["serve_wall_s"])
