"""Serve scheduler: share of decode-slot rows in the ticks that carried
a request's token (the rest are parked)."""


def read(run):
    steps = run.counts.get("decode_steps")
    if not steps:
        return None
    return 100.0 * run.counts["decode_tokens"] / (
        steps * run.counts["max_batch"])
