"""The trace reduction, on a trace recorded on one TPU v5e.

``data/tpu_v5e_probe.xplane.pb`` holds three rounds of: a jitted
``tick`` (a bf16 1024^3 matmul and tanh), a jitted ``gemm`` wrapping the
Pallas matmul, the Pallas matmul called directly, and a ``tick`` whose
result is fetched to the host.  XLA reuses the ``jit_gemm`` executable
for the direct call, so its six runs carry that module name.
"""

from bench import trace
from bench.tests.conftest import HERE

FIXTURE = str(HERE / "data" / "tpu_v5e_probe.xplane.pb")


def test_union():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_names():
    assert trace.module_name("jit_tick(17940585806632707490)") == "jit_tick"
    assert trace.op_name("%fusion.12 = bf16[8] fusion(%a)") == "fusion"


def test_recorded_tpu_trace():
    devices, host = trace.read(FIXTURE)
    assert len(devices) == 1
    s = trace.summarize(devices, host)
    t, n = s.module_time("jit_tick")
    g, m = s.module_time("jit_gemm")
    assert (n, m) == (6, 6)
    assert 0 < t < g                       # the Pallas call is the longer
    ops = sum(e - b for _, b, e in devices[0].ops)
    assert 0 < s.busy_s * 1e9 <= ops
    assert s.device_ops[0][0] == "jit_gemm/_matmul_jit"
    # the longest gaps are the host waiting on a fetched result
    assert s.idle_gaps[0][0] == "np.asarray(jax.Array)"
    assert s.idle_gaps[0][1] > 1e-3


def test_summary_of_no_device_plane():
    s = trace.summarize([], [])
    assert s.busy_s == 0 and s.modules == {}
