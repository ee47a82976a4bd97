"""Batched mixed-radix Stockham autosort FFT in pure JAX.

Why Stockham on TPU: the classic Cooley-Tukey in-place FFT needs a
bit-reversal permutation (a gather — expensive and layout-hostile on TPU).
The Stockham autosort formulation replaces every permutation with a
*reshape*: the transform carries a (L, M) factorisation of the length where
the L axis accumulates already-decided output digits in natural order.  All
data movement is therefore affine and XLA lowers each stage to elementwise
ops + reshapes — exactly what the VPU wants, and what the Pallas kernel in
``repro.kernels.fft`` tiles into VMEM.

The decimation-in-frequency radix-r step for one length-M transform
(h = M/r, x_p = x[p*h:(p+1)*h], omega_r = exp(-2*pi*i/r)):

  out[r*t + k] = F_h( (sum_p x_p * omega_r^{p*k}) * w^{k*j} )[t]
  w = exp(-2*pi*i/M)

Keeping X shaped (..., L, M): each stage stacks the new output digit in
front of the L axis (branch k lands at index k*L + l), so after the full
radix schedule L enumerates outputs in natural order.  A radix-4 stage
decides two bits at once — the (4, 2)-schedule halves the stage count of
the radix-2 engine; (8, 4, 2) cuts it to a third.

Twiddles come from :mod:`repro.fft.radix`'s per-length caches and are
embedded as constants at trace time — never recomputed inside a trace.

R2C packs N real points into an N/2 complex FFT plus an O(N) split pass
(~2x FLOP and HBM savings); C2R is the exact inverse (merge + N/2 inverse
FFT + interleave).

Cost: 5 N log2 N real FLOPs at radix 2 — the paper's Eq. (5) convention;
see :func:`repro.fft.radix.mixed_radix_flop_count` for executed counts.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.fft.radix import (DEFAULT_RADICES, dft_matrix, radix_schedule,
                             rfft_split_twiddles, stage_twiddles)


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def _as_complex(x: jax.Array) -> jax.Array:
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    return x


@functools.partial(jax.jit, static_argnames=("inverse", "radices"))
def _stockham_pow2(x: jax.Array, *, inverse: bool = False,
                   radices: tuple[int, ...] = DEFAULT_RADICES) -> jax.Array:
    """Mixed-radix Stockham FFT along the last axis (power-of-two length)."""
    n = x.shape[-1]
    assert _is_pow2(n), n
    if n == 1:
        return x
    batch = x.shape[:-1]
    y = x.reshape(*batch, 1, n)                     # (..., L=1, M=n)
    l, m = 1, n
    schedule = radix_schedule(n, radices)
    tables = stage_twiddles(n, radices, inverse)
    for r, tw in zip(schedule, tables):
        h = m // r
        dft = dft_matrix(r, inverse)
        parts = [y[..., p * h:(p + 1) * h] for p in range(r)]
        outs = []
        for k in range(r):
            acc = parts[0]                          # dft[0, k] == 1
            for p in range(1, r):
                acc = acc + parts[p] * complex(dft[p, k])
            if k:
                acc = acc * jnp.asarray(tw[k - 1]).astype(x.dtype)
            outs.append(acc)
        # Branch k is the LEAST significant undecided digit -> stack the
        # branches *before* L so the combined index is k * L + l.
        y = jnp.stack(outs, axis=-3).reshape(*batch, r * l, h)
        l, m = r * l, h
    out = y.reshape(*batch, n)
    if inverse:
        out = out / n
    return out


# ---------------------------------------------------------------------------
# R2C / C2R building blocks (shared with repro.fft.plan's routed paths)
# ---------------------------------------------------------------------------

def _pack_real(x: jax.Array) -> jax.Array:
    """(..., N) real -> (..., N/2) complex: z[j] = x[2j] + i*x[2j+1]."""
    # Strided slices, not a reshape to a trailing axis of 2: on the TPU
    # that axis is padded to 128 lanes, and at (2, 2^20) the compiler
    # spent two minutes on it.
    return jax.lax.complex(x[..., 0::2], x[..., 1::2])


def _unpack_real(z: jax.Array) -> jax.Array:
    """Inverse of :func:`_pack_real`."""
    m = z.shape[-1]
    return jnp.stack([z.real, z.imag], axis=-1).reshape(*z.shape[:-1], 2 * m)


def _rfft_split(Z: jax.Array, n: int) -> jax.Array:
    """Post-pass of the packed R2C: (..., N/2) -> (..., N/2+1) spectrum."""
    m = n // 2
    Zf = jnp.concatenate([Z, Z[..., :1]], axis=-1)   # wrap Z[m] = Z[0]
    Zr = jnp.conj(Zf[..., ::-1])                     # conj(Z[m-k])
    w = jnp.asarray(rfft_split_twiddles(n)).astype(Z.dtype)
    return 0.5 * (Zf + Zr) - 0.5j * w * (Zf - Zr)


def _irfft_merge(X: jax.Array, n: int) -> jax.Array:
    """Pre-pass of the packed C2R: (..., N/2+1) -> (..., N/2) packed Z."""
    m = n // 2
    Xr = jnp.conj(X[..., ::-1])                      # conj(X[m-k])
    ze = (0.5 * (X + Xr))[..., :m]
    wc = jnp.conj(jnp.asarray(rfft_split_twiddles(n))).astype(X.dtype)
    zo = (0.5 * wc * (X - Xr))[..., :m]
    return ze + 1j * zo


@functools.partial(jax.jit, static_argnames=("radices",))
def _rfft_pow2(x: jax.Array, *,
               radices: tuple[int, ...] = DEFAULT_RADICES) -> jax.Array:
    """R2C FFT along the last axis: (..., N) real -> (..., N/2+1) complex."""
    n = x.shape[-1]
    assert _is_pow2(n) and n >= 2, n
    if not jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.float32)
    z = _pack_real(x)
    return _rfft_split(_stockham_pow2(z, radices=radices), n)


@functools.partial(jax.jit, static_argnames=("radices",))
def _irfft_pow2(X: jax.Array, *,
                radices: tuple[int, ...] = DEFAULT_RADICES) -> jax.Array:
    """C2R inverse: (..., N/2+1) half-spectrum -> (..., N) real (1/N norm)."""
    m = X.shape[-1] - 1
    n = 2 * m
    assert m >= 1 and _is_pow2(n), X.shape
    X = _as_complex(X)
    z = _stockham_pow2(_irfft_merge(X, n), inverse=True, radices=radices)
    return _unpack_real(z)


# ---------------------------------------------------------------------------
# Public pure-JAX reference API
# ---------------------------------------------------------------------------

def _along_axis(fn, x: jax.Array, axis: int) -> jax.Array:
    if axis != -1 and axis != x.ndim - 1:
        return jnp.moveaxis(fn(jnp.moveaxis(x, axis, -1)), -1, axis)
    return fn(x)


def fft(x: jax.Array, axis: int = -1) -> jax.Array:
    """Forward C2C FFT along ``axis``; power-of-two lengths only.

    Non-power-of-two lengths are handled by :mod:`repro.fft.bluestein`
    (wired together in :mod:`repro.fft.plan`).
    """
    return _along_axis(_stockham_pow2, _as_complex(x), axis)


def ifft(x: jax.Array, axis: int = -1) -> jax.Array:
    """Inverse C2C FFT along ``axis`` (normalised by 1/N)."""
    return _along_axis(functools.partial(_stockham_pow2, inverse=True),
                       _as_complex(x), axis)


def rfft(x: jax.Array, axis: int = -1) -> jax.Array:
    """R2C FFT of real input along ``axis``; pow2 lengths, N/2+1 bins out."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.real
    return _along_axis(_rfft_pow2, x, axis)


def irfft(x: jax.Array, axis: int = -1) -> jax.Array:
    """C2R inverse of :func:`rfft` along ``axis`` (1/N normalised)."""
    return _along_axis(_irfft_pow2, _as_complex(x), axis)


def fft_flop_count(n: int, batch: int = 1) -> float:
    """5 N log2 N per transform — the paper's Eq. (5) accounting."""
    return 5.0 * n * math.log2(n) * batch
