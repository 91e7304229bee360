"""Hardware constants for the roofline model and the PIM cost model.

Two machines appear in this codebase:

* ``TPU_V5E`` — the *target* hardware for the adapted implementation
  (kernels are authored for TPU and run compiled there; on CPU they run in
  Pallas interpret mode).  Constants are the ones mandated by the assignment:
  197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s per ICI link.
* ``UPMEM`` — the paper's evaluation platform (§V-A, §VI-I).  Used by the
  cycle cost model in :mod:`repro.core.pim_cost` that reproduces the paper's
  speedup tables.  ``L_D``/``L_LOCAL`` are the paper's own profiled constants.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class TpuChip:
    name: str
    peak_flops_bf16: float     # FLOP/s per chip
    peak_flops_int8: float     # FLOP/s per chip
    hbm_bandwidth: float       # bytes/s per chip
    hbm_capacity: float        # bytes per chip
    vmem_capacity: float       # bytes per core
    ici_link_bandwidth: float  # bytes/s per link (one direction)
    ici_links: int             # links per chip (2D torus -> 4)
    mxu_dim: int = 128         # systolic array edge; matmul dims should align


TPU_V5E = TpuChip(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    peak_flops_int8=394e12,
    hbm_bandwidth=819e9,
    hbm_capacity=16 * 1024**3,
    vmem_capacity=128 * 1024**2,
    ici_link_bandwidth=50e9,
    ici_links=4,
)


@dataclasses.dataclass(frozen=True)
class PimDevice:
    """UPMEM-like near-bank DRAM-PIM (paper §II-A, §V-A, §VI-I)."""

    name: str
    n_banks: int               # PIM processing elements (paper: 2048)
    bank_capacity: int         # bytes per DRAM bank (64 MB)
    buffer_capacity: int       # bytes per SRAM local buffer (64 KB)
    lut_budget_frac: float     # fraction of bank/buffer devoted to LUTs (~half, §V-A)
    freq_hz: float             # DPU clock (350 MHz)
    dram_bytes_per_cycle: float  # DRAM bank -> buffer streaming rate (0.5 B/cyc)
    l_d: float                 # s, stream one canonical+reordering LUT entry (§VI-I)
    l_local: float             # s, canonical+reordering lookup + accumulate (12 inst)
    lookup_insts: int          # instructions per canonical+reorder lookup+acc
    op_lookup_insts: int       # instructions per plain packed-LUT lookup+acc
    ltc_lookup_insts: int      # per bit-serial lookup incl. shift-accumulate (LTC)
    mac_insts: int             # instructions per scalar MAC on the in-order core
    reorder_insts_per_elem: int  # unpack+permute+repack cost per packed element (OP+LC)

    @property
    def cycle(self) -> float:
        return 1.0 / self.freq_hz

    @property
    def bank_lut_budget(self) -> int:
        return int(self.bank_capacity * self.lut_budget_frac)

    @property
    def buffer_lut_budget(self) -> int:
        return int(self.buffer_capacity * self.lut_budget_frac)


UPMEM = PimDevice(
    name="upmem",
    n_banks=2048,
    bank_capacity=64 * 1024**2,
    buffer_capacity=64 * 1024,
    lut_budget_frac=0.55,  # "approximately half" (§V-A); 0.55 reproduces
                           # p_local=5/p_dram=8 (W1A3) and p_local=2 (W4A4)
    freq_hz=350e6,
    dram_bytes_per_cycle=0.5,
    l_d=1.36e-9,      # paper §VI-I: 0.5 B/cycle @ 350 MHz, 3-stage pipelined access
    l_local=3.27e-8,  # paper §VI-I: 12 instructions for both lookups + accumulate
    lookup_insts=12,
    op_lookup_insts=8,
    ltc_lookup_insts=10,  # packed lookup + left-shift + accumulate per bit plane
    mac_insts=7,          # ld w, ld a, mul, add, addr/loop overhead (in-order DPU)
    reorder_insts_per_elem=4,
)
