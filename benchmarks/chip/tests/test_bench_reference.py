"""The plain references and the control, at small sizes."""
import numpy as np
import pytest


@pytest.mark.parametrize("n", [8, 1024, 4096, 65536])
def test_fft_high_is_the_dft_at_lower_precision(reference, n):
    rng = np.random.default_rng(n)
    x = (rng.standard_normal((4, n))
         + 1j * rng.standard_normal((4, n))).astype(np.complex64)
    err = reference.rel_l2_rows(reference.fft_high(x),
                                reference.fft_f64(x)).max()
    # Three bfloat16 passes keep about 16 bits of each product.
    assert 1e-6 < err < 2e-5


def test_fft_high_refuses_non_pow2(reference):
    with pytest.raises(ValueError):
        reference.fft_high(np.zeros((1, 12), np.complex64))


def test_dedisperse_is_a_zero_padded_shift_and_sum(reference):
    fb = np.arange(12, dtype=np.float32).reshape(3, 4)
    out = reference.dedisperse(fb, np.array([0, 1, 3]))
    # t=0: 0 + 5 + 11; t=1: 1 + 6; t=2: 2 + 7; t=3: 3.
    np.testing.assert_array_equal(out, [16.0, 7.0, 9.0, 3.0])


def test_delays_match_the_cold_plasma_law(reference):
    freqs = reference.channel_freqs(4, 1300.0, 1500.0)
    step = reference.dm_step(1300.0, 1500.0, 64e-6)
    d = reference.delay_samples(4 * step, freqs, 1500.0, 64e-6)
    assert d[0] == 0 and d[-1] == 4           # 4 samples across the band


def test_taps_are_unit_energy(reference):
    h = reference.template_taps(3.5, 32)
    assert abs(np.sum(np.abs(h) ** 2) - 1.0) < 1e-12


GEO = dict(nchan=32, f_lo=1300.0, f_hi=1500.0, tsamp=64e-6, dm_trials=8,
           dm_spacing=4.0, n_templates=4)


def _pulsar_block(reference, ntime=4096):
    freqs = reference.channel_freqs(32, 1300.0, 1500.0)
    dms = reference.trial_dms(8, 1300.0, 1500.0, 64e-6, 4.0)
    z = reference.template_drifts(4)
    return reference.inject_filterbank(np.random.default_rng(0), 32, ntime,
                                       freqs, 1500.0, 64e-6,
                                       [(dms[3], 700, z[2], 0.3)])


def test_injected_pulsar_stands_out(reference):
    geo = GEO
    fb = _pulsar_block(reference)
    s = reference.candidate_stats(fb, [(3, 2, 700, 0), (3, 2, 500, 0)],
                                  **geo)
    assert s[0] > 100 and s[1] < 25
    low = reference.candidate_stats(fb, [(3, 2, 700, 0)], control=True,
                                    **geo)
    assert abs(low[0] - s[0]) / s[0] < 1e-3


def test_stat_plane_is_the_cells_statistic_at_its_best_level(reference):
    fb = _pulsar_block(reference)
    best, level = reference.stat_plane(fb, 3, n_harmonics=4, **GEO)
    assert best.shape == level.shape == (4, 4096 // 2 + 1)
    cells = [(3, t, b) for t in range(4) for b in (1, 350, 700, 1401, 2048)]
    per_level = reference.candidate_stats(
        fb, [(*c, lev) for c in cells for lev in range(3)], **GEO
    ).reshape(len(cells), 3)
    for (_, t, b), z in zip(cells, per_level):
        assert abs(best[t, b] - z.max()) < 1e-9 * max(1.0, abs(z.max()))
        assert level[t, b] == int(np.argmax(z))
    assert np.unravel_index(np.argmax(best), best.shape) == (2, 700)


def test_related_bins_are_neighbours_and_harmonics(reference):
    near = reference.related_bins(10, 100, 1, 2)
    assert set(np.flatnonzero(near)) == {4, 5, 6, 9, 10, 11, 18, 19, 20,
                                         21, 22}


def test_control_is_one_precision_below(reference):
    assert reference.control_precision("highest") == "high"
    with pytest.raises(ValueError):
        reference.control_precision("default")
