"""Workload fingerprinting and the nearest-neighbor metric (DESIGN.md §9).

A *fingerprint* is a content hash of everything the tuned result depends
on: the loop-nest structure (names, bounds, parallel/reduction roles,
array subscripts), dtype, SIMD limits, and the hardware profile the
search was run against.  Two processes that construct the same workload
get the same fingerprint, which is what lets serving replicas share one
on-disk registry.

The *feature vector* is the lossy companion used for transfer: log2 of
the loop bounds, in loop order.  Two fingerprints are *comparable*
(candidates for warm-starting each other) iff everything except the
bounds matches — same loop names/roles, same arrays, same dtype, same
hardware.  The distance between comparable workloads is the L2 norm over
log2-bound deltas, so a 1000x1024x1024 MM sits next to the 1024^3 one
while a CONV layer is never compared to an MM at all.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.hardware import HardwareProfile
from repro.core.workloads import Workload

# Bump when the fingerprint *inputs* change meaning; old records become
# unreachable (never silently reused against a different contract).
FINGERPRINT_VERSION = 1
# Version of the latency model ``kernels.autotune.TpuMatmulModel`` that a
# ``tpu_block`` record was tuned under; bump it when the model changes,
# so picks made under an older model are neither served nor used as
# warm-start seeds.  2: the model charges each grid step's fixed cost.
TPU_BLOCK_MODEL_VERSION = 2


@dataclasses.dataclass(frozen=True)
class Fingerprint:
    """Identity (exact lookups) + comparability key + features (transfer)."""

    digest: str                  # sha256 over the canonical payload
    family: str                  # sha256 over the bounds-free payload
    features: Tuple[float, ...]  # log2 loop bounds, loop order
    workload: str                # human-readable name (diagnostics only)

    def distance(self, other: "Fingerprint") -> Optional[float]:
        """L2 over log2-bound deltas; None if not comparable."""
        if self.family != other.family:
            return None
        return math.sqrt(sum((a - b) ** 2
                             for a, b in zip(self.features, other.features)))


def _canonical(payload: Dict) -> str:
    return json.dumps(payload, sort_keys=True, separators=(",", ":"))


def _digest(payload: Dict) -> str:
    return hashlib.sha256(_canonical(payload).encode()).hexdigest()


def _hw_payload(hw: HardwareProfile) -> Dict:
    # The full profile, not just the name: retuning is required if any
    # constant (DSP budget, BRAM count, bandwidth...) changes.
    return dataclasses.asdict(hw)


def workload_fingerprint(wl: Workload, hw: HardwareProfile,
                         variant: Optional[Dict] = None) -> Fingerprint:
    """Fingerprint of a systolic-array DSE workload against ``hw``.

    ``variant`` captures search-space restrictions that change what a
    cached result *means* (e.g. ``{"divisors_only": True}``): it is
    hashed into the family, so restricted and unrestricted searches
    never serve or seed each other.  ``None`` (the default, full space)
    keeps digests identical to pre-variant records.
    """
    structure = {
        "kind": "systolic",
        "version": FINGERPRINT_VERSION,
        "loops": [{"name": l.name, "parallel": l.parallel}
                  for l in wl.loops],
        # coeffs (subscript strides) are folded in only when non-unit, so
        # pre-stride records keep their digests while a stride-2 conv can
        # never collide with the stride-1 conv of the same loop bounds
        "arrays": [dict({"name": a.name, "dims": [list(d) for d in a.dims],
                         "is_output": a.is_output},
                        **({"coeffs": [list(a.dim_coeffs(i))
                                       for i in range(len(a.dims))]}
                           if a.has_strides else {}))
                   for a in wl.arrays],
        "spatial_candidates": list(wl.spatial_candidates),
        "simd_loop": wl.simd_loop,
        "simd_max": wl.simd_max,
        "dtype": wl.dtype,
        "hw": _hw_payload(hw),
    }
    if variant:
        structure["variant"] = dict(variant)
    family = _digest(structure)
    exact = dict(structure)
    exact["bounds"] = {l.name: l.bound for l in wl.loops}
    return Fingerprint(
        digest=_digest(exact),
        family=family,
        features=tuple(math.log2(l.bound) for l in wl.loops),
        workload=wl.name,
    )


def matmul_block_fingerprint(M: int, N: int, K: int, dtype_bytes: int,
                             hw: HardwareProfile) -> Fingerprint:
    """Fingerprint of a TPU Pallas block-shape tuning problem."""
    structure = {
        "kind": "tpu_block",
        "version": FINGERPRINT_VERSION,
        "model": TPU_BLOCK_MODEL_VERSION,
        "dtype_bytes": dtype_bytes,
        "hw": _hw_payload(hw),
    }
    family = _digest(structure)
    exact = dict(structure)
    exact["dims"] = [M, N, K]
    return Fingerprint(
        digest=_digest(exact),
        family=family,
        features=(math.log2(M), math.log2(N), math.log2(K)),
        workload=f"mm_{M}x{N}x{K}_b{dtype_bytes}",
    )


def gmm_block_fingerprint(R: int, N: int, K: int, E: int, dtype_bytes: int,
                          hw: HardwareProfile) -> Fingerprint:
    """Fingerprint of the grouped kernel's block-shape tuning problem
    (``R`` routed rows over ``E`` weights of (K, N)): a kind of its own,
    so it is never comparable to a matmul's."""
    structure = {
        "kind": "tpu_gmm_block",
        "version": FINGERPRINT_VERSION,
        "model": TPU_BLOCK_MODEL_VERSION,
        "dtype_bytes": dtype_bytes,
        "hw": _hw_payload(hw),
    }
    family = _digest(structure)
    exact = dict(structure)
    exact["dims"] = [R, N, K, E]
    return Fingerprint(
        digest=_digest(exact),
        family=family,
        features=(math.log2(R), math.log2(N), math.log2(K), math.log2(E)),
        workload=f"gmm_{R}x{N}x{K}_g{E}_b{dtype_bytes}",
    )


def nearest(fp: Fingerprint,
            candidates: Sequence[Tuple[Fingerprint, object]],
            k: int = 3,
            max_distance: float = 4.0) -> List[Tuple[float, object]]:
    """The k comparable candidates closest to ``fp`` within ``max_distance``.

    ``candidates`` is (fingerprint, payload) pairs; returns sorted
    (distance, payload).  Exact hits (distance 0) are included — callers
    that want *neighbors only* filter them out.
    """
    scored: List[Tuple[float, object]] = []
    for cand_fp, payload in candidates:
        d = fp.distance(cand_fp)
        if d is not None and d <= max_distance:
            scored.append((d, payload))
    scored.sort(key=lambda t: t[0])
    return scored[:k]
