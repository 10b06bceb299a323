"""Model step: device time of one chunked-prefill call (``jit_chunk``)."""


def read(run):
    if run.summary is None:
        return None
    total, count = run.summary.module_time("jit_chunk")
    return total / count * 1e3 if count else None
