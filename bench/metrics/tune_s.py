"""Tuner: host seconds to resolve every shape class of the cell against
a fresh registry (part of set-up)."""


def read(run):
    return run.counts.get("tune_s")
