"""The dense matmul picks of the shared tuner stay where they are.

The grouped kernel's workload (``resolve_gmm_config``) shares the
tuner's search, cost terms, LRU and registry with the matmul's; these
are the blocks ``resolve_matmul_config`` gives the ten GEMM classes of a
starcoder2-7b step (``bench/counters.gemm_classes`` at M 4096 and 16),
recorded before the grouped workload existed: with no registry, and
against a fresh registry class after class as the GEMM cells resolve
them (each search then seeded from its neighbours).
"""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:       # the benchmark's package, ``bench``
    sys.path.insert(0, str(REPO))

from bench import counters  # noqa: E402
from repro.kernels.autotune import (reset_config_lru,  # noqa: E402
                                    resolve_gmm_config,
                                    resolve_matmul_config)
from repro.registry import RegistryStore  # noqa: E402

STARCODER2 = json.loads((REPO / "bench" / "configs" /
                         "starcoder2-7b.json").read_text())
# (M, N, K) -> (bm, bk, bn, k_innermost)
NO_REGISTRY = {
    (4096, 4608, 4608): (1024, 1536, 1152, True),
    (4096, 512, 4608): (688, 1536, 512, True),
    (4096, 18432, 4608): (1024, 4608, 1024, True),
    (4096, 4608, 18432): (1024, 6144, 768, True),
    (4096, 49152, 4608): (1024, 4608, 1536, True),
    (16, 4608, 4608): (16, 2304, 4608, True),
    (16, 512, 4608): (16, 4608, 512, True),
    (16, 18432, 4608): (16, 4608, 2304, True),
    (16, 4608, 18432): (16, 3072, 4608, True),
    (16, 49152, 4608): (16, 4608, 4096, True),
}
FRESH_REGISTRY = {
    **NO_REGISTRY,
    (4096, 512, 4608): (1024, 1152, 512, True),
    (16, 18432, 4608): (16, 1536, 9216, True),
    (16, 49152, 4608): (16, 4608, 3072, True),
}


def _picks(registry_root=None):
    out = {}
    for rows in (4096, 16):
        store = RegistryStore(str(registry_root / str(rows))) \
            if registry_root else None
        for (M, N, K), _ in counters.gemm_classes(STARCODER2, rows):
            c = resolve_matmul_config(M, N, K, registry=store)
            out[(M, N, K)] = (c.bm, c.bk, c.bn, c.k_innermost)
    return out


@pytest.mark.parametrize("registry", [False, True],
                         ids=["no_registry", "fresh_registry"])
def test_starcoder2_dense_picks_unchanged(tmp_path, registry):
    reset_config_lru()
    # a grouped resolution first: it must move no dense pick
    resolve_gmm_config(4096 * 8, 896, 2304, 64,
                       registry=RegistryStore(str(tmp_path / "4096"))
                       if registry else None)
    picks = _picks(tmp_path if registry else None)
    assert picks == (FRESH_REGISTRY if registry else NO_REGISTRY)
