"""Tuner: the analytic model's predicted time of the picked blocks over
the device time the tuned kernels took, per pass."""


def read(run):
    if run.summary is None or "pass_model_s" not in run.counts:
        return None
    total, count = run.summary.module_time("jit_bench_gemm")
    if not count:
        return None
    return run.counts["pass_model_s"] * run.counts["passes"] / total
