"""Bluestein (chirp-z) FFT for arbitrary lengths — paper Sec. 2.1.

cuFFT falls back to Bluestein's algorithm when the length has a prime
factor above 127; we use it for every non-power-of-two length, converting
one length-N DFT into power-of-two FFTs of length M >= 2N-1 plus pointwise
chirp multiplies.  This matches the paper's observation that Bluestein
lengths cost ~3x and use many kernels (their Sec. 4 notes eleven GPU
kernels for N=139^2).

Two cost levers over the naive formulation:

* the chirp AND the filter's spectrum ``fb = FFT(b)`` are precomputed with
  numpy and memoised per (length, direction) — rebuilding them per call
  (or per trace) is pure waste, and caching ``fb`` removes one of the
  three runtime FFTs outright (2 pow2 FFTs per call instead of 3);
* the two remaining pow2 FFTs route through :func:`repro.fft.plan.pow2_fft`
  and therefore execute the fused Pallas kernel (with pure-JAX fallback),
  exactly like every other plan's passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


@functools.lru_cache(maxsize=None)
def _chirp_factors(n: int, inverse: bool
                   ) -> tuple[np.ndarray, np.ndarray]:
    """(chirp, fb): the length-N chirp and the FFT of the chirp filter.

    Computed once per (length, direction) with numpy (complex128) and
    embedded as constants at trace time — the filter FFT never runs on
    device.
    """
    m = _next_pow2(2 * n - 1)
    sign = 1.0 if inverse else -1.0
    k = np.arange(n)
    # exp(sign * i*pi*k^2/n); k^2 mod 2n keeps the argument small & exact.
    chirp = np.exp(sign * 1j * np.pi * ((k * k) % (2 * n)) / n)
    b = np.zeros(m, np.complex128)
    b[:n] = np.conj(chirp)
    b[m - n + 1:] = np.conj(chirp)[1:][::-1]
    return chirp, np.fft.fft(b)


@functools.partial(jax.jit, static_argnames=("inverse", "config"))
def bluestein_fft(x: jax.Array, *, inverse: bool = False,
                  config=None) -> jax.Array:
    """C2C DFT of arbitrary length along the last axis via chirp-z.

    ``config`` (a hashable :class:`repro.tune.KernelConfig`, static) rides
    into the two inner pow2 FFTs so tuned tiles actually execute for
    Bluestein lengths too.
    """
    from repro.fft.plan import pow2_fft          # lazy: avoids import cycle

    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    n = x.shape[-1]
    m = _next_pow2(2 * n - 1)
    chirp_np, fb_np = _chirp_factors(n, inverse)
    chirp = jnp.asarray(chirp_np).astype(x.dtype)
    fb = jnp.asarray(fb_np).astype(x.dtype)

    a = jnp.zeros((*x.shape[:-1], m), dtype=x.dtype).at[..., :n].set(x * chirp)
    fa = pow2_fft(a, config=config)
    conv = pow2_fft(fa * fb, inverse=True, config=config)
    out = conv[..., :n] * chirp
    if inverse:
        out = out / n
    return out
