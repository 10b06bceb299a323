"""Tuner: host seconds the program's block resolutions took, the sum of
its ``tuner.resolve_s`` histogram (``repro.obs``).  None where the
program records no such histogram."""


def read(run):
    try:
        from repro.obs import get_metrics
    except ImportError:
        return None
    h = get_metrics().histograms.get("tuner.resolve_s")
    return h.total if h is not None and h.count else None
