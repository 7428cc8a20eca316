"""Hardware device models for the MaxEVA planner.

Two device families are modelled:

* ``AIEDevice`` — the paper's target (AMD/Xilinx Versal AIE, VC1902 on the
  VCK190 board).  Used verbatim to reproduce the paper's analytical results
  (Tables I-III, Fig. 8) and to validate our implementation of the paper's
  optimization model (eq. 1-9).

* ``TPUDevice`` — the adaptation target (TPU v5e).  The same constraint
  *structure* (compute-rate bound, I/O-bandwidth bound, local-memory bound,
  array-level port/bandwidth bounds) is re-instantiated with the TPU memory
  hierarchy: HBM -> VMEM -> MXU, and ICI links replacing PLIO ports.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class AIEDevice:
    """Versal AIE device model (paper §III, §IV-C)."""

    name: str = "VC1902"
    rows: int = 8
    cols: int = 50
    freq_hz: float = 1.25e9
    # Peak MACs/cycle of one AIE core, per precision (paper §IV-C1).
    peak_macs: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {"int8": 128, "fp32": 8}
    )
    # Stream / PLIO bandwidth in bytes per AIE cycle (paper eq. 2).
    bw_io_bytes_per_cyc: float = 4.0
    # Local data memory: 32KB in 8 x 4KB banks; 1 bank reserved for
    # stack/heap; remaining 28KB double-buffered -> 14KB per buffer set
    # (paper eq. 6).
    mem_bank_bytes: int = 4096
    mem_banks: int = 8
    usable_buffer_bytes: int = 14 * 1024
    # Array-level resources (paper eq. 7-9; VC1902 / VCK190).
    n_cores: int = 400
    plio_in: int = 78
    plio_out: int = 117

    # element sizes: accumulation is always 32-bit (paper §IV-C1).
    @staticmethod
    def sizeof_in(precision: str) -> int:
        return {"int8": 1, "fp32": 4}[precision]

    @staticmethod
    def sizeof_out(precision: str) -> int:
        return 4  # int32 or fp32 accumulators


@dataclasses.dataclass(frozen=True)
class TPUDevice:
    """TPU v5e device model (per-chip), used by the TPU-mode planner and the
    roofline analysis.  Constants fixed by the assignment:
    197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s per ICI link."""

    name: str = "TPUv5e"
    peak_flops: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {
            "bf16": 197e12,
            "fp32": 197e12 / 4,  # fp32 runs through the MXU at 1/4 rate
            "int8": 394e12,
        }
    )
    hbm_bw: float = 819e9           # bytes/s
    hbm_bytes: int = 16 * 2 ** 30   # 16 GiB per chip
    ici_bw_per_link: float = 50e9   # bytes/s, per direction, per link
    ici_links: int = 4              # 2D torus: +/-x, +/-y
    # Scoped VMEM one kernel may use: every pallas_call is compiled with
    # this as its ``vmem_limit_bytes`` (the compiler's v5e default), so
    # the planner's budget and the compiler's limit are the same number.
    vmem_bytes: int = 16 * 2 ** 20
    # Fraction of VMEM the planner lets one kernel's working set claim
    # (compiler scratch, semaphores, pipelining headroom take the rest).
    vmem_budget_frac: float = 0.75
    # MXU native tile granularity: the systolic array is 128x128; a VMEM
    # tile is (8, 128) 32-bit words, so narrower dtypes pack more rows
    # per tile: (16, 128) bf16, (32, 128) int8.
    mxu_dim: int = 128
    sublane: int = 8

    @property
    def vmem_budget(self) -> int:
        return int(self.vmem_bytes * self.vmem_budget_frac)

    def row_tile(self, itemsize: int) -> int:
        """Rows of one native VMEM tile for elements of ``itemsize``."""
        return self.sublane * max(1, 4 // itemsize)

    def ridge_flops_per_byte(self, dtype: str = "bf16") -> float:
        """Arithmetic-intensity ridge point of the HBM roofline."""
        return self.peak_flops[dtype] / self.hbm_bw


DTYPE_BYTES = {
    "bf16": 2,
    "fp32": 4,
    "f32": 4,
    "int8": 1,
    "s8": 1,
    "int32": 4,
    "s32": 4,
}

AIE_VC1902 = AIEDevice()
TPU_V5E = TPUDevice()

# Mesh-level constants for the production deployment (single pod = 16x16
# chips = 256; multi-pod = 2 pods = 512).  Used for roofline math.
CHIPS_PER_POD = 256
PODS = 2
