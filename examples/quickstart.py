"""Quickstart: the paper's result in 60 seconds.

1. Sweep the V100 clock grid for a batched FFT (the paper's experiment).
2. Find the optimal and mean-optimal clocks (Table 3).
3. Apply the same machinery to a TPU-v5e LLM decode step.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (TESLA_V100, TPU_V5E, FFTCase, fft_workload,
                        mean_optimal, roofline_workload, sweep)


def main():
    enable_compile_cache()
    # --- 1. the paper's measurement, analytically -----------------------
    print("=== FFT DVFS sweep on the V100 (paper Secs. 4-5) ===")
    sweeps = []
    for logn in range(10, 21, 2):
        case = FFTCase(n=2**logn)
        res = sweep(fft_workload(case, TESLA_V100), TESLA_V100)
        sweeps.append(res)
        print(f"  N=2^{logn:<3} optimal={res.optimal.f:7.1f} MHz "
              f"({100*res.optimal_frequency_frac:5.1f}% of boost)  "
              f"power cut {100*res.power_reduction:4.1f}%  "
              f"slowdown {100*res.slowdown:5.2f}%  "
              f"I_ef {res.i_ef_boost:.2f}")

    # --- 2. Table 3: one clock for all lengths ---------------------------
    mo = mean_optimal(sweeps, TESLA_V100)
    print(f"\n  mean optimal clock = {mo.f_mean:.0f} MHz "
          f"(paper: 945 MHz); using it loses {mo.loss_pp:.1f} pp of I_ef")

    # --- 3. the same technique on a TPU LLM decode step ------------------
    print("\n=== The technique applied to an LLM decode step (TPU v5e) ===")
    # a memory-bound decode: weights + KV cache reads dominate
    prof = roofline_workload(
        "llm-decode", TPU_V5E,
        hlo_flops=2 * 4e9 * 128,          # 4B params, 128 sequences
        hbm_bytes=4e9 * 2 + 40e9,         # weights bf16 + 40 GB cache read
        issue_efficiency=0.75)
    res = sweep(prof, TPU_V5E, time_budget=0.10)
    print(f"  bound: memory   optimal={res.optimal.f:.0f} MHz "
          f"({100*res.optimal.f/TPU_V5E.f_max:.0f}% of boost)")
    print(f"  predicted power cut {100*res.power_reduction:.0f}% "
          f"at {100*res.slowdown:.1f}% slowdown  (I_ef {res.i_ef_boost:.2f})")


if __name__ == "__main__":
    main()
