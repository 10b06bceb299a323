"""Drive one cell of a checkout on the CPU, for the self-tests only.

    python cpu_cell.py <checkout> --workload ... --seed ... --seconds ...

It replaces the harness's look for a chip (the CPU stands in, with the
v5e's peaks), keeps the compile cache out of the checkout, and, where
``BENCH_FAULT`` names one, breaks the timed path underneath:

* ``token``:  the decode tick alters the token it produces for slot 0;
* ``state``:  the decode tick returns the cache it was given, unchanged;
* ``answer``: the tuned matmul adds 1 to one row of what it returns.

Where ``BENCH_CONTROL`` names a precision, the plain reference in that
precision is judged in the program's place.
"""

import os
import sys
import tempfile
import time

T0 = time.perf_counter()
root = os.path.abspath(sys.argv[1])
sys.path[0:0] = [root, os.path.join(root, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp()

import jax  # noqa: E402
from pathlib import Path  # noqa: E402

from bench import device, harness  # noqa: E402

device.require_chips = lambda n: jax.devices()[:n]
device.peak = lambda devices: device.PEAKS["TPU v5 lite"]
fault = os.environ.get("BENCH_FAULT", "")
if fault in ("token", "state"):
    from repro.serve import engine as eng
    base_init = eng.EngineBase.__init__

    def init(self, *a, **kw):
        base_init(self, *a, **kw)
        tick = self.decode_tick

        def broken(params, cache, *rest):
            nxt, pos, new_cache = tick(params, cache, *rest)
            if fault == "state":
                return nxt, pos, cache
            return nxt.at[0, 0].add(1), pos, new_cache
        self.decode_tick = broken
    eng.EngineBase.__init__ = init
elif fault == "answer":
    from repro.kernels import ops
    base = ops.matmul_op

    def broken(a, b, config=None, out_dtype=None):
        return base(a, b, config, out_dtype).at[0].add(1)
    ops.matmul_op = broken
elif fault:
    raise SystemExit(f"unknown fault {fault!r}")

sys.exit(harness.main(sys.argv[2:], Path(root), T0,
                      control=os.environ.get("BENCH_CONTROL") or None))
