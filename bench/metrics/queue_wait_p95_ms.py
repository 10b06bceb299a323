"""Serve scheduler: 95th percentile of arrival-to-admission wait."""


def read(run):
    if run.stats is None or not run.stats.requests:
        return None
    from bench.drivers.serve import p95
    return p95([m.queue_wait_s for m in run.stats.requests]) * 1e3
