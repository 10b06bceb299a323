#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
each number compared for ``correct`` beside its limit, which also end
standard error).  Without a TPU it exits 2 and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache: a fixed directory in the checkout,
# so only the first run of a cell there compiles
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
# libtpu would log under a fixed /tmp path otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# the checkout root in place of this directory, whose module names
# (trace, device) would shadow others
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    sys.exit(harness.main(sys.argv[1:], ROOT, T_PROCESS))
