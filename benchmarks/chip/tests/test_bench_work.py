"""Work counts against hand counts, and the peaks table."""
import pytest


def test_fft_counts(work):
    # Length 8, two transforms: 5 * 8 * 3 flops each, 16 bytes a point.
    assert work.fft_flops(8, 2) == 240.0
    assert work.fft_bytes(8, 2) == 256.0


def test_dedisp_counts(work):
    # 2 trials x 3 channels x 4 samples, one filterbank: 24 adds;
    # (3 + 2) rows of 4 float32 moved.
    assert work.dedisp_flops(2, 3, 4, 1) == 24.0
    assert work.dedisp_bytes(2, 3, 4, 1) == 80.0


def test_least_time(work):
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000.0, 50.0, peak) == (10.0, "compute")
    assert work.least_time(100.0, 50.0, peak) == (5.0, "memory")


def test_v5e_peaks(work):
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_kind_raises(work):
    with pytest.raises(ValueError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
