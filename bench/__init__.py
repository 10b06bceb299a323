"""Chip benchmark of this repository: one run of one cell per process.

Run from the root of a checkout::

    python bench/run.py --workload smollm-135m.chat --seed 7 --seconds 30 --trace 0

``BENCHMARK.json`` at the root names the cells, the metrics and their
bounds.  Everything that belongs to one configuration, traffic mix or
per-layer metric is a file of its own under this directory, found by the
name ``BENCHMARK.json`` gives it: ``configs/<config>.json``,
``traffic/<mix>.json``, ``metrics/<metric>.py``.  ``drivers/<driver>.py``
runs the window of a mix, and ``reference/`` holds the plain references
that decide ``correct``.
"""
