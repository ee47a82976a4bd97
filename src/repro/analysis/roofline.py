"""Roofline terms per (arch x shape x mesh) dry-run cell.

  compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
  memory term     = HLO_bytes / (chips * HBM_bw)
  collective term = collective_bytes / (chips * link_bw)

Constants: TPU v5e — 197 TFLOP/s bf16/chip, 819 GB/s HBM, ~50 GB/s/link.
MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE); the useful-compute
ratio MODEL_FLOPS / HLO_FLOPs flags remat/dispatch waste.

The DVFS planner (the paper's technique) consumes these terms directly:
``repro.core.workloads.roofline_workload`` turns a row of this table into
a WorkloadProfile whose optimal clock and energy saving are computed just
like the paper's per-FFT-length optimum.
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.hardware import TPU_V5E, DeviceSpec


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float                # per-device FLOPs of one step
    hbm_bytes: float                # per-device HBM traffic
    collective_bytes: float         # per-device collective traffic
    model_flops: float              # 6*N(active)*D tokens, global
    device: DeviceSpec = TPU_V5E

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / self.device.peak_flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.device.hbm_bandwidth

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / self.device.link_bandwidth

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step time (perfect overlap = max of terms)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / (global HLO flops) — remat/dispatch waste."""
        total_hlo = self.hlo_flops * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achieved fraction of the dominant roofline: how close the
        OTHER terms come to the bound (1.0 = perfectly balanced use of
        the bottleneck resource)."""
        if self.step_s == 0:
            return 0.0
        return (self.model_flops / self.chips / self.device.peak_flops
                ) / self.step_s

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "compute_ms": round(self.compute_s * 1e3, 3),
            "memory_ms": round(self.memory_s * 1e3, 3),
            "collective_ms": round(self.collective_s * 1e3, 3),
            "bound": self.bound,
            "useful_ratio": round(self.useful_ratio, 3),
            "mfu_roofline": round(self.roofline_fraction, 3),
        }


def model_flops_for(cfg: ArchConfig, shape: ShapeSpec) -> float:
    """6*N*D (6*N_active*D for MoE); D = tokens processed by the step."""
    n = cfg.active_param_count() if cfg.moe is not None else cfg.param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens               # forward only
    tokens = shape.global_batch                # one token per sequence
    return 2.0 * n * tokens

