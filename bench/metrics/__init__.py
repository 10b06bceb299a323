"""Per-layer readers, one file per metric ``BENCHMARK.json`` names.

Each defines ``read(run) -> float | None``: the metric from what the run
recorded (the engine's stats, the driver's counts, the reduced trace), or
None where the run has nothing for it to read.
"""
