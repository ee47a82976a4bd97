"""Plain references that decide ``correct``, and the lower-precision control.

Nothing here imports the program under test or takes anything it made:
the dispersion delays, the template taps and the transforms are computed
again from their published definitions.

* ``fft_f64``: the float64 numpy FFT every served transform is held to.
* ``fft_high``: the control.  The same DFT computed as a four-step of
  DFT-matrix products at XLA's ``high`` precision (three bfloat16 passes,
  float32 accumulation), emulated with explicit bfloat16 splits so that it
  gives the same numbers on any backend.  It is what a later change that
  lowered the kernels' ``HIGHEST`` matmul precision would serve.
* ``dedisperse``, ``template_taps``, ``candidate_stats``: the pulsar
  search's statistic at given (DM trial, template, bin, harmonic level)
  cells, from the filterbank alone; ``stat_plane``: the same statistic
  over every (template, bin) of one DM trial, at its best level, and
  ``related_bins``: the bins the sift holds to be one source.  Copied in substance from
  ``src/repro/kernels/dedisp/ref.py`` (zero-padded shift-and-sum),
  ``src/repro/search/templates.py`` (acceleration response taps),
  ``src/repro/search/fdas.py`` (matched-filter power over the spectrum's
  noise power) and ``src/repro/kernels/harmonic_sum/ref.py`` (the
  doubling ladder and its normalisation), so that a later change of those
  modules cannot move the yardstick.
"""
from __future__ import annotations

import numpy as np
from ml_dtypes import bfloat16

#: Cold-plasma dispersion constant, s MHz^2 (pc cm^-3)^-1.
K_DM = 4.148808e3
#: Chirp samples of the acceleration response (Riemann sum).
OVERSAMPLE = 4096


def rel_l2_rows(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Relative L2 error of each row (last axis)."""
    got = np.asarray(got, np.complex128)
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(want, axis=-1))


def fft_f64(x: np.ndarray) -> np.ndarray:
    """The reference: float64 FFT along the last axis."""
    return np.fft.fft(np.asarray(x, np.complex128), axis=-1)


# --------------------------------------------------------------------------
# control: DFT-matrix products at ``high`` precision
# --------------------------------------------------------------------------

#: The control's matmul precision for each one a configuration may state:
#: the nearest below it.  Only ``high`` is computed here.
CONTROL_BELOW = {"highest": "high"}


def control_precision(stated: str) -> str:
    """The control's precision under a configuration stating ``stated``."""
    if stated not in CONTROL_BELOW:
        raise ValueError(f"no control is computed for matmul precision "
                         f"{stated!r}; known: {sorted(CONTROL_BELOW)}")
    return CONTROL_BELOW[stated]


def _split(a: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    hi = a.astype(bfloat16).astype(np.float32)
    lo = (a - hi).astype(bfloat16).astype(np.float32)
    return hi, lo


def _dot_high(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """float32 a @ b as three bfloat16 passes (XLA's ``high``)."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    ah, al = _split(a)
    bh, bl = _split(b)
    return (ah @ bh + (ah @ bl + al @ bh)).astype(np.float32)


def _cdot_high(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Complex a @ b from four real ``high`` products."""
    ar, ai = a.real, a.imag
    br, bi = b.real, b.imag
    re = _dot_high(ar, br) - _dot_high(ai, bi)
    im = _dot_high(ar, bi) + _dot_high(ai, br)
    return (re + 1j * im).astype(np.complex64)


def _dft_matrix(n: int) -> np.ndarray:
    k = np.arange(n)
    return np.exp(-2j * np.pi * np.outer(k, k) / n).astype(np.complex64)


def fft_high(x: np.ndarray) -> np.ndarray:
    """The control: a pow2 FFT along the last axis as a four-step of
    DFT-matrix products at ``high`` precision, in complex64."""
    x = np.asarray(x, np.complex64)
    n = x.shape[-1]
    if n & (n - 1):
        raise ValueError(f"fft_high needs a power of two, got {n}")
    lead = x.shape[:-1]
    rows = x.reshape(-1, n)
    n1 = 1 << (n.bit_length() - 1) // 2           # n = n1 * n2, n1 <= n2
    n2 = n // n1
    # x[j1 * n2 + j2]: a length-n1 DFT down the columns, the twiddle
    # W_n^(j2 k1), then a length-n2 DFT along the rows.
    a = rows.reshape(-1, n1, n2).transpose(0, 2, 1)        # (b, j2, j1)
    a = _cdot_high(a.reshape(-1, n1), _dft_matrix(n1)).reshape(-1, n2, n1)
    tw = np.exp(-2j * np.pi * np.outer(np.arange(n2), np.arange(n1)) / n)
    a = (a * tw.astype(np.complex64)).astype(np.complex64)  # (b, j2, k1)
    a = a.transpose(0, 2, 1).reshape(-1, n2)                 # (b*k1, j2)
    a = _cdot_high(a, _dft_matrix(n2)).reshape(-1, n1, n2)  # (b, k1, k2)
    return a.transpose(0, 2, 1).reshape(*lead, n)            # k1 + n1 k2


# --------------------------------------------------------------------------
# pulsar search: geometry, injection and the statistic at given cells
# --------------------------------------------------------------------------

def channel_freqs(nchan: int, f_lo: float, f_hi: float) -> np.ndarray:
    """Channel centres, descending from the top of the band."""
    return np.linspace(f_hi, f_lo, nchan)


def dm_step(f_lo: float, f_hi: float, tsamp: float) -> float:
    """DM giving one sample of differential delay across the band."""
    return tsamp / (K_DM * (f_lo ** -2 - f_hi ** -2))


def delay_samples(dm: float, freqs: np.ndarray, f_hi: float,
                  tsamp: float) -> np.ndarray:
    """Integer-sample dispersion delays relative to the top of the band."""
    sec = K_DM * dm * (freqs ** -2 - f_hi ** -2)
    return np.rint(sec / tsamp).astype(np.int64)


def trial_dms(n_trials: int, f_lo: float, f_hi: float, tsamp: float,
              spacing: float) -> np.ndarray:
    """The DM trial grid: ``spacing`` samples of band delay per trial."""
    return np.arange(n_trials) * spacing * dm_step(f_lo, f_hi, tsamp)


def template_drifts(n_templates: int) -> np.ndarray:
    """Drifts z in bins, evenly over [-zmax, zmax], one bin apart."""
    zmax = max((n_templates - 1) / 2.0, 0.0)
    if n_templates == 1:
        return np.zeros(1)
    return np.linspace(-zmax, zmax, n_templates)


def template_width(n_templates: int) -> int:
    zmax = max((n_templates - 1) / 2.0, 0.0)
    return max(32, 2 * int(np.ceil(zmax)) + 16)


def template_taps(z: float, taps: int) -> np.ndarray:
    """Unit-energy taps correlating a spectrum with the response of a
    tone drifting ``z`` bins over the block (conjugate-reversed)."""
    tau = np.arange(OVERSAMPLE) / OVERSAMPLE
    resp = np.fft.fft(np.exp(1j * np.pi * z * tau * tau)) / OVERSAMPLE
    u = np.arange(taps) - taps // 2
    h = np.conj(resp[u % OVERSAMPLE])[::-1]
    return h / max(np.sqrt(np.sum(np.abs(h) ** 2)), 1e-30)


def inject_filterbank(rng: np.random.Generator, nchan: int, ntime: int,
                      freqs: np.ndarray, f_hi: float, tsamp: float,
                      pulsars) -> np.ndarray:
    """(nchan, ntime) float32 unit noise plus dispersed linear chirps.

    ``pulsars`` holds (dm, k0, z, amp): the same tone in every channel,
    shifted by that channel's integer delay, as
    ``src/repro/data/synthetic.py`` injects it.
    """
    x = rng.standard_normal((nchan, ntime), dtype=np.float32)
    for dm, k0, z, amp in pulsars:
        d = delay_samples(dm, freqs, f_hi, tsamp)
        top = int(d.max())
        # The tone at s = (t - d) / ntime for t - d in [-top, ntime).
        s = np.arange(-top, ntime, dtype=np.float64) / ntime
        tone = (amp * np.cos(2 * np.pi * (k0 * s + 0.5 * z * s * s))
                ).astype(np.float32)
        for c in range(nchan):
            x[c] += tone[top - d[c]:top - d[c] + ntime]
    return x


def dedisperse(fb: np.ndarray, delays: np.ndarray,
               dtype=np.float64) -> np.ndarray:
    """out[t] = sum_c fb[c, t + delay[c]], zero past the block's end."""
    nchan, n = fb.shape
    out = np.zeros(n, dtype)
    for c in range(nchan):
        d = int(delays[c])
        out[:n - d] += fb[c, d:].astype(dtype)
    return out


def candidate_stats(fb: np.ndarray, cells, *, nchan: int, f_lo: float,
                    f_hi: float, tsamp: float, dm_trials: int,
                    dm_spacing: float, n_templates: int,
                    control: bool = False) -> np.ndarray:
    """The detection statistic at each (dm trial, template, bin, level).

    The statistic is z_h = (S_h - h) / sqrt(h), h = 2^level, with
    S_h[k] = sum_{j=1..h} P[j k] over the normalised matched-filter power
    P of the DM trial's mean-subtracted series.  ``control`` computes the
    same in float32 with every product at ``high`` precision.
    """
    ntime = fb.shape[-1]
    freqs = channel_freqs(nchan, f_lo, f_hi)
    dms = trial_dms(dm_trials, f_lo, f_hi, tsamp, dm_spacing)
    drifts = template_drifts(n_templates)
    width = template_width(n_templates)
    offset = width - 1 - width // 2
    dtype = np.float32 if control else np.float64
    spectra: dict[int, tuple[np.ndarray, float]] = {}
    out = []
    for d, t, b, lev in cells:
        d, t, b, lev = int(d), int(t), int(b), int(lev)
        if d not in spectra:
            s = dedisperse(fb, delay_samples(dms[d], freqs, f_hi, tsamp),
                           dtype)
            s = s - s.mean(dtype=dtype)
            spec = (fft_high(s.astype(np.complex64))[:ntime // 2 + 1]
                    if control else np.fft.rfft(s))
            spectra[d] = (spec, float(np.mean(np.abs(spec) ** 2)))
        spec, sigma2 = spectra[d]
        nbins = spec.shape[-1]
        h_taps = template_taps(drifts[t], width)
        h = 1 << lev
        total = 0.0
        for j in range(1, h + 1):
            k = j * b
            if k >= nbins:
                continue
            # Full convolution, trimmed by ``offset``:
            # mf[k] = sum_i spec[k + offset - i] * taps[i].
            idx = k + offset - np.arange(width)
            ok = (idx >= 0) & (idx < nbins)
            seg = spec[np.where(ok, idx, 0)] * ok
            if control:
                mf = _cdot_high(seg[None, :].astype(np.complex64),
                                h_taps[:, None].astype(np.complex64))[0, 0]
            else:
                mf = np.sum(seg * h_taps)
            total += float(np.abs(mf) ** 2) / sigma2
        out.append((total - h) / np.sqrt(h))
    return np.asarray(out, np.float64)


def stat_plane(fb: np.ndarray, d: int, *, nchan: int, f_lo: float,
               f_hi: float, tsamp: float, dm_trials: int, dm_spacing: float,
               n_templates: int, n_harmonics: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """The statistic of ``candidate_stats`` over every (template, bin) of
    DM trial ``d``, in float64: (best z over the levels 0 ..
    log2(n_harmonics), the level that gives it, the earliest on a tie),
    each (templates, ntime // 2 + 1).  The matched filter is the same
    full convolution, taken through a zero-padded FFT."""
    freqs = channel_freqs(nchan, f_lo, f_hi)
    dms = trial_dms(dm_trials, f_lo, f_hi, tsamp, dm_spacing)
    s = dedisperse(fb, delay_samples(dms[d], freqs, f_hi, tsamp))
    spec = np.fft.rfft(s - s.mean())
    nbins = spec.shape[-1]
    sigma2 = float(np.mean(np.abs(spec) ** 2))
    width = template_width(n_templates)
    offset = width - 1 - width // 2
    taps = np.stack([template_taps(z, width)
                     for z in template_drifts(n_templates)])
    size = 1 << (nbins + width - 2).bit_length()     # >= nbins + width - 1
    conv = np.fft.ifft(np.fft.fft(spec, size)[None, :]
                       * np.fft.fft(taps, size, axis=-1), axis=-1)
    power = np.abs(conv[:, offset:offset + nbins]) ** 2 / sigma2
    k = np.arange(nbins)
    acc = np.zeros_like(power)
    best = np.full_like(power, -np.inf)
    level = np.zeros(power.shape, np.int32)
    done = 0
    for lev in range(int(np.log2(n_harmonics)) + 1):
        h = 1 << lev
        for j in range(done + 1, h + 1):
            ok = j * k < nbins
            acc[:, ok] += power[:, j * k[ok]]
        done = h
        z = (acc - h) / np.sqrt(h)
        up = z > best
        best = np.where(up, z, best)
        level = np.where(up, lev, level)
    return best, level


def related_bins(b: int, nbins: int, bin_tol: int,
                 max_harmonic: int) -> np.ndarray:
    """Which bins the sift takes for the same source as bin ``b``: within
    ``m * bin_tol`` of m times it, or it within that of m times them, for
    m = 1 .. max_harmonic (m = 1 is adjacency)."""
    other = np.arange(nbins)
    near = np.zeros(nbins, bool)
    for m in range(1, max_harmonic + 1):
        near |= np.abs(other - m * b) <= m * bin_tol
        near |= np.abs(b - m * other) <= m * bin_tol
    return near
