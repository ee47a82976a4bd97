"""Work counts and the chip's peaks: the yardstick of every roofline share.

Each count is of the work the algorithm needs, whatever implements it:

* an FFT of length n: 5 n log2 n flops, and one read plus one write of
  complex64 (16 bytes a point);
* dedispersion of C channels into D trials of N samples: D C N adds, and
  a read of the filterbank plus a write of the series, (C + D) N float32.

The least time is the larger of flops over peak FLOP/s and bytes over
peak bandwidth, from ``peaks.json`` keyed by the device's ``device_kind``.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    try:
        return table[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device_kind {device_kind!r}; "
                         f"known: {sorted(table)}") from None


def fft_flops(n: int, transforms: int) -> float:
    return 5.0 * n * math.log2(n) * transforms


def fft_bytes(n: int, transforms: int) -> float:
    return 16.0 * n * transforms


def dedisp_flops(dm_trials: int, nchan: int, ntime: int,
                 filterbanks: int) -> float:
    return float(dm_trials) * nchan * ntime * filterbanks


def dedisp_bytes(dm_trials: int, nchan: int, ntime: int,
                 filterbanks: int) -> float:
    return 4.0 * (nchan + dm_trials) * ntime * filterbanks


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(seconds, bound) where bound is "compute" or "memory"."""
    tc = flops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
