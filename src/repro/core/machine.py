"""Hardware constants: MPU (Table II), a V100-like GPU, and TPU v5e.

MPU numbers are the paper's Table II; GPU numbers follow the V100
whitepaper + common DRAM-energy literature (the paper's own GPU numbers
come from nvprof/nvidia-smi measurements which we cannot re-run, so the
GPU model is calibrated to public V100 figures).  TPU v5e constants are
the roofline constants given in the assignment.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class MPUMachine:
    """One MPU processor (a 3D stack); Table II."""

    processors: int = 8
    dram_dies: int = 4
    cores: int = 16                 # per processor, on the base logic die
    subcores: int = 4               # per core
    nbus: int = 4                   # per core (all on one die: horizontal)
    banks_per_nbu: int = 4
    row_buffers: int = 4            # MASA-style multi-activated (1/2/4)
    simt_width: int = 32
    bank_io_bits: int = 256
    tsv_bits_per_core: int = 64     # 1024 TSVs / 16 cores
    f_core_ghz: float = 1.0
    f_tsv_ghz: float = 2.0
    # DRAM timing (cycles @ 1GHz): tRCD/tCCD/tRTP/tRP/tRAS/tRFC/tREFI
    t_rcd: int = 14
    t_ccd: int = 2
    t_rtp: int = 4
    t_rp: int = 14
    t_ras: int = 33
    row_bytes: int = 2048           # row buffer size per bank
    # energy (J): Table II
    e_rd_wr: float = 0.15e-9        # per 32B bank access
    e_pre_act: float = 0.27e-9
    e_rf: float = 40.0e-12          # register file access
    e_smem: float = 22.2e-12
    e_opc: float = 41.49e-12        # operand collector
    e_lsu_ext: float = 39.67e-12
    e_tsv_bit: float = 4.53e-12
    e_onchip_bit: float = 0.72e-12
    e_offchip_bit: float = 4.50e-12
    e_alu_op: float = 18.0e-12      # per-lane fp op (PTX measurement scale
                                    # of Arafa et al. [8,9], Volta-class)

    @property
    def bank_peak_gbps(self) -> float:
        """Per-bank IO bandwidth: 256b / tCCD cycles."""
        return (self.bank_io_bits / 8) / (self.t_ccd / self.f_core_ghz)

    @property
    def core_bank_gbps(self) -> float:
        return self.bank_peak_gbps * self.nbus * self.banks_per_nbu

    @property
    def tsv_gbps_per_core(self) -> float:
        return (self.tsv_bits_per_core / 8) * self.f_tsv_ghz

    @property
    def offload_near_gbps(self) -> float:
        """Aggregate near-bank stream bandwidth (all cores reading their
        local banks) — what a fused near segment's bytes move at."""
        return self.core_bank_gbps * self.cores * self.processors

    @property
    def offload_far_gbps(self) -> float:
        """Aggregate far-path bandwidth: far-bank execution streams every
        operand through the TSVs, the §IV-B1 bottleneck the offload
        decision weighs fused near traffic against."""
        return self.tsv_gbps_per_core * self.cores * self.processors

    @property
    def total_area_mm2(self) -> float:
        return 926.0


@dataclass(frozen=True)
class GPUMachine:
    """V100-like compute-centric baseline."""

    sms: int = 80
    lanes_per_sm: int = 64
    f_ghz: float = 1.38
    hbm_gbps: float = 900.0
    l2_amplification: float = 1.12   # effective BW boost from L2 residency
    dram_latency_cycles: int = 400   # load-to-use through L2/NoC
    # energy: DRAM ~4nJ/32B access end-to-end (HBM2 ~15pJ/bit incl. PHY),
    # plus on-die movement (L2/NoC/L1) per 32B.
    e_dram_32b: float = 2.0e-9
    e_onchip_move_32b: float = 0.85e-9
    e_rf: float = 40.0e-12
    e_smem: float = 22.2e-12
    e_alu_op: float = 18.0e-12
    total_area_mm2: float = 1199.0   # die + 4 HBM stacks

    @property
    def offload_near_gbps(self) -> float:
        """No near-bank path: fused kernels still stream HBM — the win
        is moving fewer bytes, not a faster wire."""
        return self.hbm_gbps

    @property
    def offload_far_gbps(self) -> float:
        return self.hbm_gbps


@dataclass(frozen=True)
class TPUv5e:
    """Roofline constants (assignment-provided)."""

    # ``jax.Device.device_kind`` of the chips these constants describe
    device_kinds: tuple[str, ...] = ("TPU v5 lite", "TPU v5e")
    peak_bf16_flops: float = 197e12      # per chip
    hbm_gbps: float = 819.0              # GB/s per chip
    ici_link_gbps: float = 50.0          # GB/s per link per direction
    ici_links: int = 4                   # 2D torus, 4 links/chip
    vmem_bytes: int = 128 * 1024 * 1024
    hbm_bytes: int = 16 * 1024 * 1024 * 1024

    @property
    def offload_near_gbps(self) -> float:
        """Fused segments and the far pipeline both stream the same HBM
        on TPU; the cost decision reduces to a pure byte count."""
        return self.hbm_gbps

    @property
    def offload_far_gbps(self) -> float:
        return self.hbm_gbps


MPU = MPUMachine()
GPU = GPUMachine()
V5E = TPUv5e()


# Table III — area of MPU components on the DRAM die (mm^2, incl. the 2x
# DRAM-process overhead), used by benchmarks/table3_area.py.
AREA_TABLE_III = {
    "Shared Memory": (4, 0.84),
    "Register File": (16, 9.71),
    "Memory Controller": (16, 0.63),
    "Operand Collector": (64, 2.43),
    "Vector ALU": (16, 3.74),
    "LSU-extension": (16, 2.43),
    "Multi-row-buffer Support": (64, 0.01),
}
DRAM_DIE_AREA_MM2 = 96.0
