"""The chip a run is on: peaks, the check that one is there, peak memory.

Peaks are the published numbers of one chip, keyed by the ``device_kind``
JAX reports.  A device that is not in the table is an error, never a
default.
"""

from __future__ import annotations

from typing import Dict, List

PEAKS: Dict[str, Dict] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s.
    "TPU v5 lite": {"name": "TPU v5e", "bf16_flops": 197e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class NoChip(RuntimeError):
    """The run cannot be measured here: no TPU, too few, or unknown."""


def require_chips(n: int) -> List:
    """The first ``n`` TPU devices, or :class:`NoChip`."""
    import jax
    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default devices are "
                     f"{[d.platform for d in devices]}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")
    kind = devices[0].device_kind
    if kind not in PEAKS:
        raise NoChip(f"device kind {kind!r} has no entry in the peak table")
    return devices[:n]


def peak(devices) -> Dict:
    return PEAKS[devices[0].device_kind]


def describe(devices) -> Dict:
    import jax
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 if unreported)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
