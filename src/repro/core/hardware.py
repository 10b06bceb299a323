"""Hardware profiles for the Odyssey design-space exploration engine.

The paper targets a Xilinx Alveo U250 FPGA.  We keep that profile (so the
paper's published ratios are directly comparable) and add the TPU v5e profile
used by the surrounding training framework.  Both are plain dataclasses so the
performance models stay symbolic in the tuning parameters and only bind
hardware constants at evaluation time.

Calibration notes (U250):
  * The paper's Table 3 reports the optimal MM design (T_I1=129, T_J1=130,
    T_I2=3, T_J2=13, SIMD=4) as using 100% of DSPs and the divisor-only
    design (64,128,16,4,SIMD=8) as using 60%.  With dataflow [i,j] those are
    (129/3)x(130/13)=430 PEs x 4 lanes = 1720 lanes and (64/16)x(128/4)=128
    PEs x 8 lanes = 1024 lanes.  At 5 DSPs per fp32 MAC lane this gives
    8600 and 5120 DSPs => 100% / 60% with an 8600-DSP budget, exactly
    matching the paper.  Hence ``dsp_available=8600``, ``dsp_per_lane=5``.
  * BRAM18 count for the U250 is 5376; AutoSA designs run at ~300 MHz.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    """Resource/latency constants consumed by the performance models."""

    name: str
    # --- compute ---
    dsp_available: int          # FPGA: DSP slices.  TPU: see flops_peak.
    dsp_per_lane: int           # DSPs consumed per SIMD MAC lane (fp32: 5).
    mac_pipeline_depth: int     # cycles between dependent accumulations
    freq_hz: float              # design clock
    # --- on-chip memory ---
    bram_available: int         # FPGA: BRAM18 blocks.  TPU: VMEM/bram_bytes.
    bram_bytes: int             # bytes per BRAM18 (18Kb = 2304 B)
    bram_port_bits: int         # native port width of one BRAM18
    # --- off-chip ---
    dram_bus_bytes: int         # bytes/cycle on the shared off-chip bus
    dma_overhead_cycles: int    # fixed per-transfer setup cost
    dma_burst_bytes: int        # transfer granularity (simulator only)
    # --- control/routing fabric (what SIMD vectorization amortizes) ---
    lut_available: int = 0      # usable LUTs (0 = unconstrained)
    lut_per_pe: int = 0         # PE control/routing overhead
    lut_per_lane: int = 0       # per-SIMD-lane datapath LUTs
    # --- TPU-style absolute numbers (used by the roofline/TPU models) ---
    flops_peak: float = 0.0     # peak FLOP/s (bf16 for TPU)
    hbm_bw: float = 0.0         # bytes/s
    ici_bw: float = 0.0         # bytes/s per link
    vmem_bytes: int = 0         # per-core VMEM

    @property
    def peak_lanes(self) -> int:
        return self.dsp_available // self.dsp_per_lane

    @property
    def dram_bw(self) -> float:
        return self.dram_bus_bytes * self.freq_hz


# Xilinx Alveo U250, as used by the paper (see module docstring for the
# calibration of dsp_available/dsp_per_lane against the paper's Table 3).
U250 = HardwareProfile(
    name="u250",
    dsp_available=8600,
    dsp_per_lane=5,
    mac_pipeline_depth=8,       # fp32 accumulate latency on FPGA DSP chains
    freq_hz=300e6,
    bram_available=5376,
    bram_bytes=2304,
    bram_port_bits=36,
    lut_available=1_200_000,    # ~70% of 1728K LUTs usable
    lut_per_pe=800,             # PE control/FIFO/routing overhead
    lut_per_lane=150,           # per-lane datapath glue
    dram_bus_bytes=256,         # 4x DDR4 channels ~ 77 GB/s @300 MHz
    dma_overhead_cycles=120,
    dma_burst_bytes=64,
)

# TPU v5e, per the assignment's hardware constants: 197 TFLOP/s bf16,
# 819 GB/s HBM, ~50 GB/s/link ICI, 128 MiB VMEM, ~940 MHz clock.
TPU_V5E = HardwareProfile(
    name="tpu_v5e",
    dsp_available=0,
    dsp_per_lane=1,
    mac_pipeline_depth=1,
    freq_hz=940e6,
    bram_available=0,
    bram_bytes=1,
    bram_port_bits=0,
    dram_bus_bytes=872,         # 819 GB/s / 940 MHz
    dma_overhead_cycles=500,    # DMA issue latency, ~0.5 us
    dma_burst_bytes=512,
    flops_peak=197e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    vmem_bytes=128 * 1024 * 1024,
)

DTYPE_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}

# ``device_kind`` as JAX reports it -> the profile of that chip
DEVICE_PROFILES = {"TPU v5 lite": TPU_V5E}


def profile_for_device(device_kind: str) -> HardwareProfile:
    """The profile of a JAX device kind; an unknown kind is an error, never
    a default."""
    try:
        return DEVICE_PROFILES[device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware profile for device kind {device_kind!r}; "
            f"known: {sorted(DEVICE_PROFILES)}") from None
