"""Design registry: persistent tuning cache + transfer-seeded warm start.

The registry turns the search engine into a service (DESIGN.md §9):
tune once, serve the tuned design to every subsequent caller — across
processes and serving replicas — and warm-start nearby workloads from
their cached neighbors.

    store.py        content-addressed on-disk records (atomic, versioned)
    fingerprint.py  workload identity + the nearest-neighbor metric
    transfer.py     record <-> TuneReport, neighbor-genome re-legalization
    service.py      sync lookups, background tuning worker
    __main__.py     operator CLI: python -m repro.registry list|show|...
"""

from .fingerprint import (Fingerprint, gmm_block_fingerprint,
                          matmul_block_fingerprint, nearest,
                          workload_fingerprint)
from .store import (DEFAULT_ROOT_ENV, Record, RegistryStore, SCHEMA_VERSION,
                    default_root)
from .transfer import (record_from_report, report_from_record,
                       seeds_from_neighbors, transfer_seeds)
from .service import TuningService

__all__ = [
    "Fingerprint", "workload_fingerprint", "matmul_block_fingerprint",
    "gmm_block_fingerprint",
    "nearest",
    "Record", "RegistryStore", "SCHEMA_VERSION", "default_root",
    "DEFAULT_ROOT_ENV",
    "record_from_report", "report_from_record", "seeds_from_neighbors",
    "transfer_seeds",
    "TuningService",
]
