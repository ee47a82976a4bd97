"""Hardware specifications and DVFS frequency tables.

Paper reference: Table 1 (allowed core clock frequencies) and Table 2 (GPU
card specifications).  We carry the two devices the paper focuses its
discussion on (Tesla V100 and Jetson Nano) for the paper-faithful
calibration, plus the TPU v5e target used by the rest of this framework.

Frequencies are MHz, bandwidths are bytes/s, powers are watts.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Static description of one device model for the DVFS model."""

    name: str
    # --- frequency tables (paper Table 1) -------------------------------
    f_max: float                  # maximal / boost core clock [MHz]
    f_base: float | None          # base core clock [MHz] (None: no base clock)
    f_min: float                  # minimal core clock [MHz]
    f_step: float                 # nominal frequency step [MHz]
    # --- compute/memory capability (paper Table 2) ----------------------
    peak_flops: float             # peak FLOP/s at f_max for the modelled dtype
    hbm_bandwidth: float          # device-memory bandwidth [bytes/s]
    cache_bandwidth: float        # shared/L1-class bandwidth at f_max [bytes/s]
    memory_bytes: float           # device memory size [bytes]
    tdp: float                    # thermal design power [W]
    idle_power: float             # static (idle/P-state floor) power [W]
    # --- DVFS voltage model ---------------------------------------------
    v_max: float = 1.0            # relative voltage at f_max
    v_floor: float = 0.60         # voltage floor (no undervolting below this)
    f_vfloor_frac: float = 0.45   # f/f_max below which voltage stays at floor
    # --- scheduler behaviour ---------------------------------------------
    # Exponent p in t_issue(f) = t_issue(f_max) * (f_max/f)^p.  p > 1 models
    # the paper's Sec. 6 observation that once instruction issue saturates,
    # latency hiding collapses and the slowdown is superlinear in 1/f.
    issue_superlinearity: float = 2.0
    # Effective fraction of peak FLOP/s the device can issue for a
    # shuffle-heavy butterfly kernel (calibrated; cuFFT is far from peak).
    issue_efficiency: float = 0.33
    # Fraction of core switching power still burned while stalled on
    # memory (datacenter parts keep warps resident and hot; mobile SoCs
    # clock-gate aggressively).
    stall_power_frac: float = 0.75
    # How well the memory system and the core pipelines overlap (1.0 =
    # perfect latency hiding, the roofline max; 0.0 = fully serialised).
    # Small devices with few SMs cannot hide HBM latency behind compute,
    # which is why the Nano pays for every clock step (paper Fig. 6).
    exec_overlap: float = 1.0
    # Fraction of the dynamic power envelope drawn by the memory system
    # when saturated (HBM2 stacks are power-hungry; LPDDR4 is not).
    mem_power_frac: float = 0.12
    # Whether the device's power sensor covers the memory rail.  nvidia-smi
    # reports whole-board power; the Nano's tegrastats POM_5V_GPU rail
    # covers the GPU core only (DRAM is on a separate rail), which the
    # paper's Sec. 4 measurement setup inherits.
    power_sensor_includes_mem: bool = True
    # --- interconnect (TPU) ----------------------------------------------
    link_bandwidth: float | None = None   # per-link ICI/NVLink [bytes/s]

    def frequencies(self) -> np.ndarray:
        """The discrete allowed core-clock grid, descending from f_max.

        The paper notes the step alternates between two close values
        (e.g. 7/8 MHz on V100); a fixed nominal step is an accurate model.
        """
        n = int(np.floor((self.f_max - self.f_min) / self.f_step)) + 1
        f = self.f_max - self.f_step * np.arange(n)
        return np.clip(f, self.f_min, None)

    def voltage(self, f: np.ndarray | float) -> np.ndarray:
        """Relative supply voltage V(f)/V(f_max), piecewise linear with floor.

        Models the paper's observation that below a certain frequency the
        P-state (and voltage) stops dropping, which is why power flattens
        at the low end of Fig. 8.
        """
        f = np.asarray(f, dtype=np.float64)
        frac = f / self.f_max
        knee = self.f_vfloor_frac
        slope = (self.v_max - self.v_floor) / (1.0 - knee)
        v = self.v_floor + slope * np.clip(frac - knee, 0.0, None)
        return np.clip(v, self.v_floor, self.v_max)


# ---------------------------------------------------------------------------
# Paper devices (Tables 1 & 2).  peak_flops is the FP32 figure.
# idle_power is estimated from the paper's Fig. 8 low-frequency plateau
# (~55 W on the V100, ~1.3 W on the Nano module rail).
# ---------------------------------------------------------------------------

TESLA_V100 = DeviceSpec(
    name="tesla-v100",
    f_max=1530.0, f_base=1200.0, f_min=135.0, f_step=7.5,
    peak_flops=15.7e12,           # FP32 TFLOP/s at boost
    hbm_bandwidth=900e9,
    cache_bandwidth=14550e9,      # shared-memory bandwidth, Table 2
    memory_bytes=16e9,
    tdp=300.0,
    idle_power=40.0,
    v_floor=0.60, f_vfloor_frac=0.45,
    issue_superlinearity=2.0, issue_efficiency=0.42,
    stall_power_frac=0.75, exec_overlap=1.0,
    mem_power_frac=0.30,                     # HBM2 stacks draw ~60-70 W
)

JETSON_NANO = DeviceSpec(
    name="jetson-nano",
    f_max=921.6, f_base=None, f_min=76.8, f_step=76.8,
    peak_flops=472e9,             # FP32 GFLOP/s
    hbm_bandwidth=25.6e9,
    cache_bandwidth=230e9,
    memory_bytes=4e9,
    tdp=10.0,
    idle_power=0.5,                # GPU rail only (tegrastats view)
    # The Nano has little compute margin over its LPDDR4 bandwidth, so the
    # issue term is near-saturated at f_max -> regime (c) dominates (Fig 6)
    # and every frequency step costs execution time.
    v_floor=0.72, f_vfloor_frac=0.50,
    issue_superlinearity=1.0, issue_efficiency=0.16,
    stall_power_frac=0.30, exec_overlap=0.5,
    mem_power_frac=0.10,                     # LPDDR4 is cheap to drive
)

TITAN_V = DeviceSpec(
    name="titan-v",
    f_max=1912.0, f_base=1220.0, f_min=135.0, f_step=7.5,
    peak_flops=14.9e12,
    hbm_bandwidth=652e9,
    cache_bandwidth=14550e9,
    memory_bytes=12e9,
    tdp=250.0,
    idle_power=36.0,
    v_floor=0.60, f_vfloor_frac=0.45,
    issue_superlinearity=2.0, issue_efficiency=0.42,
    stall_power_frac=0.75, exec_overlap=1.0,
    mem_power_frac=0.30,
)

# Driver cap observed by the paper on the Titan V during compute kernels.
TITAN_V_DRIVER_CAP_MHZ = 1335.0

# ---------------------------------------------------------------------------
# TPU v5e — the deployment target of this framework.
#
# The roofline constants are the assignment's: 197 TFLOP/s bf16 per chip,
# 819 GB/s HBM, ~50 GB/s/link ICI.  The DVFS grid mirrors the *shape* of the
# paper's Table 1 (a dense grid from f_max down to a deep floor); absolute
# MHz values follow public v5e clocks (~1.67 GHz sustained).
# ---------------------------------------------------------------------------

TPU_V5E = DeviceSpec(
    name="tpu-v5e",
    f_max=1670.0, f_base=1411.0, f_min=500.0, f_step=65.0,
    peak_flops=197e12,            # bf16
    hbm_bandwidth=819e9,
    cache_bandwidth=20000e9,      # VMEM-class bandwidth at f_max (scales with f)
    memory_bytes=16e9,
    tdp=220.0,                    # per-chip board power envelope
    idle_power=45.0,
    v_floor=0.62, f_vfloor_frac=0.48,
    issue_superlinearity=1.6, issue_efficiency=0.45,
    stall_power_frac=0.70, exec_overlap=0.92,
    mem_power_frac=0.15,
    link_bandwidth=50e9,
)

DEVICES: dict[str, DeviceSpec] = {
    d.name: d for d in (TESLA_V100, JETSON_NANO, TITAN_V, TPU_V5E)
}


def get_device(name: str) -> DeviceSpec:
    try:
        return DEVICES[name]
    except KeyError as e:
        raise KeyError(f"unknown device {name!r}; have {sorted(DEVICES)}") from e


#: ``device_kind`` strings JAX reports for the TPU chips this repository
#: has a spec for (a v5e reports "TPU v5 lite").
TPU_KINDS: dict[str, DeviceSpec] = {
    "TPU v5 lite": TPU_V5E,
}


def spec_for_device_kind(kind: str) -> DeviceSpec:
    """The :class:`DeviceSpec` of an attached TPU, from its ``device_kind``.

    An unknown kind raises: pricing a different chip with the v5e model
    would give every modelled number the wrong device.
    """
    try:
        return TPU_KINDS[kind]
    except KeyError as e:
        raise ValueError(
            f"no DeviceSpec for TPU device_kind {kind!r}; known kinds: "
            f"{sorted(TPU_KINDS)}") from e
