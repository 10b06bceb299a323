"""Model step: device time of one decode tick (module ``jit_tick``)."""


def read(run):
    if run.summary is None:
        return None
    total, count = run.summary.module_time("jit_tick")
    return total / count * 1e3 if count else None
