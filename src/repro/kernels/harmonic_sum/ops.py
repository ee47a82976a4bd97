"""Public wrappers for the harmonic-sum kernels.

Two entry points share one guarded input path:

* :func:`harmonic_sum_kernel` — the demo ladder: (..., N) power spectra
  to the full (..., LEVELS, N) doubling ladder (Sec. 5.3 figure fodder).
* :func:`harmonic_sum_plane` — the production pipeline stage: the same
  ladder built, normalised and max-reduced inside VMEM, returning only
  the (..., N) best detection statistic and its level index — the
  (LEVELS, N) ladder never round-trips through HBM.

Edge cases (tested in tests/test_kernels.py):

* ``n_harmonics=1`` is valid: a single-level ladder — the demo returns
  the input as its one level, the plane returns  z_1 = P - 1  with level
  index 0 everywhere.
* An empty trailing axis (shape (..., 0)) raises ``ValueError``: a
  zero-length spectrum has no bins to sum (and the kernel's grid maths
  would divide by zero).
* Complex input raises ``ValueError`` — power spectra are real by
  construction; silently taking ``.real`` would hide an upstream bug
  (pass ``|X|**2``, not the spectrum itself).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.common import (LANES, batch_tile, round_up, rows_tile,
                                  use_interpret)
from repro.kernels.harmonic_sum.harmonic_sum_kernel import (
    harmonic_sum_pallas, harmonic_sum_plane_pallas)
from repro.obs.ledger import record_launch


def _checked_power(power, n_harmonics: int, fn_name: str) -> jax.Array:
    """Shared shape/dtype guards -> the (..., N) f32 power array.

    ValueErrors, not asserts: asserts vanish under ``python -O`` and
    these guard caller input, not internal invariants.
    """
    if n_harmonics < 1 or n_harmonics & (n_harmonics - 1):
        raise ValueError(
            f"n_harmonics must be a power of two, got {n_harmonics}")
    power = jnp.asarray(power)
    if jnp.issubdtype(power.dtype, jnp.complexfloating):
        raise ValueError(
            f"{fn_name} takes real power (|X|**2), got complex dtype "
            f"{power.dtype} with shape {power.shape}")
    if power.ndim < 1 or power.shape[-1] == 0:
        raise ValueError(
            f"{fn_name} needs a non-empty trailing axis, got shape "
            f"{power.shape}")
    return power.astype(jnp.float32)


def _tiled(power: jax.Array, n_harmonics: int, out_planes: int
           ) -> tuple[jax.Array, int, int, tuple[int, ...]]:
    """Flatten lead dims, pad the batch to a VMEM-sized tile multiple and
    each row to 128-aligned bins plus the kernel's 128 * H zero bins."""
    lead = power.shape[:-1]
    n = power.shape[-1]
    b = 1
    for d in lead:
        b *= d
    width = round_up(n, LANES) + LANES * n_harmonics
    tile, rows = rows_tile(b, batch_tile(width, 4,
                                         buffers=2 * (1 + out_planes)))
    p2 = jnp.pad(power.reshape(b, n), ((0, rows - b), (0, width - n)))
    return p2, b, tile, lead


def harmonic_sum_kernel(power: jax.Array, n_harmonics: int = 32, *,
                        interpret: bool | None = None) -> jax.Array:
    """(..., N) power spectra -> (..., LEVELS, N) harmonic-sum ladder."""
    if interpret is None:
        interpret = use_interpret()
    power = _checked_power(power, n_harmonics, "harmonic_sum_kernel")
    levels = int(math.log2(n_harmonics)) + 1
    p2, b, tile, lead = _tiled(power, n_harmonics, levels)
    n = power.shape[-1]
    out = harmonic_sum_pallas(p2, n_harmonics, tile_b=tile,
                              interpret=interpret)[:b, :, :n]
    record_launch("harmonic-sum", grid=(p2.shape[0] // tile,),
                  tile=(tile, n),
                  bytes_moved=4 * p2.shape[0] * n * (1 + out.shape[-2]),
                  shape=(b, n))
    return out.reshape(*lead, out.shape[-2], power.shape[-1])


def harmonic_sum_plane(power: jax.Array, n_harmonics: int = 8, *,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """(..., N) power plane -> ((..., N) statistic, (..., N) int32 level).

    The statistic is  max_h (S_h - h) / sqrt(h)  over the doubling
    ladder h = 1, 2, ..., n_harmonics, valid for planes normalised to
    per-bin mean 1 under the null (the FDAS power plane); ``level`` is
    log2(h) of the winning ladder rung (earliest wins ties).
    """
    if interpret is None:
        interpret = use_interpret()
    power = _checked_power(power, n_harmonics, "harmonic_sum_plane")
    p2, b, tile, lead = _tiled(power, n_harmonics, 2)
    stat, lev = harmonic_sum_plane_pallas(p2, n_harmonics, tile_b=tile,
                                          interpret=interpret)
    n = power.shape[-1]
    record_launch("harmonic-sum-plane", grid=(p2.shape[0] // tile,),
                  tile=(tile, n), bytes_moved=12 * p2.shape[0] * n,
                  shape=(b, n))
    return (stat[:b, :n].reshape(*lead, n),
            lev[:b, :n].reshape(*lead, n))
