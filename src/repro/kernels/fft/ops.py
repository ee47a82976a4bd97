"""Public wrappers for the FFT Pallas kernels (split re/im planes, tiles)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import (BLOCK_BUDGET_BYTES, LANES, SUBLANES,
                                  batch_tile, round_up, rows_tile,
                                  use_interpret)
from repro.obs.ledger import record_launch
from repro.kernels.fft.fft_kernel import (fft_axis1_pallas,
                                          fft_axis1_twiddle_pallas,
                                          fft_mul_pallas, fft_pallas,
                                          fft_t_pallas,
                                          fft_t_twiddle_pallas, irfft_pallas,
                                          rfft_pallas, rfft_t_pallas,
                                          transpose_pallas)

# One fused pass handles transforms that fit VMEM alongside work buffers.
MAX_KERNEL_N = 2**13


def _check_kernel_length(n: int) -> None:
    if n > MAX_KERNEL_N:
        raise ValueError(
            f"N={n} exceeds the single-pass kernel limit ({MAX_KERNEL_N}); "
            "route long transforms through repro.fft.plan (its four-step "
            "decomposition runs this kernel once per pow2 pass)")


def _flatten(x: jax.Array) -> tuple[jax.Array, tuple[int, ...], int]:
    """Collapse leading dims to one batch axis: (..., n) -> (b, n)."""
    lead = x.shape[:-1]
    b = 1
    for d in lead:
        b *= d
    return x.reshape(b, x.shape[-1]), lead, b


def _tile_and_pad(planes: list[jax.Array], b: int, n: int,
                  elem_bytes: int = 4,
                  tile_b: int | None = None) -> tuple[list[jax.Array], int]:
    """Pick a batch tile and pad only when the batch is not a multiple.

    A tile-multiple batch (the common case after the serving layer's
    coalescer) skips the pad-then-slice HBM round trip entirely.
    ``tile_b`` is an explicit override (the autotuner's tuned choice,
    rounded to the sublane height) — when None the VMEM budget decides.
    """
    tile, padded = rows_tile(b, batch_tile(n, elem_bytes, buffers=8,
                                           override=tile_b))
    if padded > b:
        planes = [jnp.pad(p, ((0, padded - b), (0, 0))) for p in planes]
    return planes, tile


def fft_kernel_c2c(x: jax.Array, *, inverse: bool = False,
                   interpret: bool | None = None,
                   tile_b: int | None = None) -> jax.Array:
    """Batched pow2 C2C FFT (..., N) via the Pallas kernel.

    Accepts complex input, splits to re/im planes for the kernel, and
    recombines.  Longer-than-VMEM transforms should go through
    ``repro.fft.plan`` (four-step built on this kernel per pass).
    ``tile_b`` overrides the heuristic batch tile (autotuner hook).
    """
    if interpret is None:
        interpret = use_interpret()
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    n = x.shape[-1]
    _check_kernel_length(n)
    if n == 1:
        # The length-1 DFT is the identity BOTH ways: the forward sum is
        # the single point and the inverse normalisation is 1/1, so the
        # old ``x / 1`` "inverse" was a silent no-op copy.
        return x
    flat, lead, b = _flatten(x)
    re = flat.real.astype(jnp.float32)
    im = flat.imag.astype(jnp.float32)
    (re, im), tile = _tile_and_pad([re, im], b, n, tile_b=tile_b)
    out_re, out_im = fft_pallas(re, im, tile_b=tile, inverse=inverse,
                                interpret=interpret)
    padded = b + (-b) % tile
    record_launch("fft-c2c", grid=(padded // tile,), tile=(tile, n),
                  bytes_moved=16 * padded * n, shape=(b, n))
    if out_re.shape[0] != b:
        out_re, out_im = out_re[:b], out_im[:b]
    return (out_re + 1j * out_im).reshape(*lead, n)


def fft_kernel_c2c_mul(x: jax.Array, bank, *, inverse: bool = False,
                       interpret: bool | None = None,
                       tile_b: int | None = None) -> jax.Array:
    """Fused pow2 C2C FFT + (T, N) filter-bank multiply epilogue.

    (..., N) in -> (..., T, N) out with out[..., t, :] = FFT(x) * bank[t].
    The bank multiply happens in VMEM on the transformed tile — the
    matched-filter plane of a T-template search costs one forward pass
    (this kernel) plus T inverse passes, with no standalone multiply
    pass.  ``bank`` is a host-side (T, N) complex array (the cached
    filter spectra of ``repro.fft.convolve``).
    """
    if interpret is None:
        interpret = use_interpret()
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    n = x.shape[-1]
    _check_kernel_length(n)
    bank = jnp.asarray(bank)
    if bank.ndim != 2 or bank.shape[-1] != n:
        raise ValueError(
            f"filter bank must be (T, {n}), got {bank.shape}")
    t = bank.shape[0]
    fbr = bank.real.astype(jnp.float32)
    fbi = bank.imag.astype(jnp.float32)
    flat, lead, b = _flatten(x)
    re = flat.real.astype(jnp.float32)
    im = flat.imag.astype(jnp.float32)
    # Rows travel as (1, n) blocks (a (1, n) block pads to 8 sublanes in
    # VMEM), next to a T-row product plane each, double-buffered; the
    # pinned bank takes its share of the budget first.
    per_row = 2 * 4 * (2 * SUBLANES * n + 2 * t * n)
    room = BLOCK_BUDGET_BYTES - 2 * 4 * 2 * t * n
    tile = max(room // per_row, 1)
    if tile_b is not None:          # a tuned tile, never past the budget
        tile = min(tile_b, tile)
    tile = min(tile, b)
    padded = round_up(b, tile)
    if padded > b:
        re, im = (jnp.pad(p, ((0, padded - b), (0, 0))) for p in (re, im))
    out_re, out_im = fft_mul_pallas(re, im, fbr, fbi, tile_b=tile,
                                    inverse=inverse, interpret=interpret)
    record_launch("fft-c2c-mul", grid=(padded // tile,), tile=(tile, n),
                  bytes_moved=8 * n * (padded + t + padded * t),
                  shape=(b, t, n))
    if out_re.shape[0] != b:
        out_re, out_im = out_re[:b], out_im[:b]
    return (out_re + 1j * out_im).reshape(*lead, t, n)


def _lane_tile(r: int, c: int, planes: int = 12,
               override: int | None = None) -> int:
    """Tile along an axis that becomes the lane (minor) dimension of a
    transposed block: a multiple of 128 dividing ``r``, or ``r`` itself
    when it is not a multiple of 128.

    ``planes`` counts the (tile, c) planes the kernel keeps in VMEM
    (double-buffered blocks plus scratch).  An explicit ``override`` (the
    autotuner's tile) is clamped to that budget and snapped down to the
    nearest such divisor.
    """
    if r % LANES or r <= LANES:
        return r
    want = BLOCK_BUDGET_BYTES // (planes * c * 4)
    if override is not None:
        want = min(override, want)
    tile = max(want // LANES, 1) * LANES
    while r % tile:
        tile -= LANES
    return tile


def _flatten3(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    """Collapse leading dims to one batch axis: (..., R, C) -> (b, R, C)."""
    lead = x.shape[:-2]
    b = 1
    for d in lead:
        b *= d
    return x.reshape(b, *x.shape[-2:]), lead


def fft_kernel_c2c_t(x: jax.Array, *, twiddle=None, inverse: bool = False,
                     interpret: bool | None = None,
                     tile_b: int | None = None) -> jax.Array:
    """Fused C2C FFT + transposed write: (..., R, C) -> (..., C, R).

    The hand-off transpose of a 2-D / N-D / four-step transform rides the
    FFT pass: each (tile_r, C) row tile is transformed in VMEM and written
    into its (C, tile_r) column window — one HBM read + one write total.

    ``twiddle`` (optional, an (R, C) complex table) fuses the four-step
    inter-pass multiply as a kernel epilogue, deleting the separate XLA
    multiply pass of the unfused path.
    """
    if interpret is None:
        interpret = use_interpret()
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    r, c = x.shape[-2:]
    _check_kernel_length(c)
    flat, lead = _flatten3(x)
    re = flat.real.astype(jnp.float32)
    im = flat.imag.astype(jnp.float32)
    tile = _lane_tile(r, c, override=tile_b)
    if twiddle is not None:
        tw = jnp.asarray(twiddle)
        ftwr = tw.real.astype(jnp.float32)
        ftwi = tw.imag.astype(jnp.float32)
        out_re, out_im = fft_t_twiddle_pallas(
            re, im, ftwr, ftwi, tile_r=tile, inverse=inverse,
            interpret=interpret)
    else:
        out_re, out_im = fft_t_pallas(re, im, tile_r=tile, inverse=inverse,
                                      interpret=interpret)
    record_launch("fft-c2c-t", grid=(flat.shape[0], r // tile),
                  tile=(tile, c), bytes_moved=16 * flat.shape[0] * r * c,
                  shape=(flat.shape[0], r, c))
    return (out_re + 1j * out_im).reshape(*lead, c, r)


def fft_kernel_c2c_axis1(x: jax.Array, *, twiddle=None,
                         inverse: bool = False,
                         interpret: bool | None = None,
                         tile_b: int | None = None) -> jax.Array:
    """C2C FFT over axis -2, layout preserved: (..., R, C) -> (..., R, C).

    The four-step column pass: transpose-read + FFT + optional twiddle
    epilogue + transpose-write, all in VMEM (one HBM round trip).
    ``twiddle`` is a (C, R) complex table; output ``[..., k, j]`` is
    multiplied by ``twiddle[j, k]``.
    """
    if interpret is None:
        interpret = use_interpret()
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    r, c = x.shape[-2:]
    _check_kernel_length(r)
    flat, lead = _flatten3(x)
    re = flat.real.astype(jnp.float32)
    im = flat.imag.astype(jnp.float32)
    tile = _lane_tile(c, r, override=tile_b)
    if twiddle is not None:
        tw = jnp.asarray(twiddle)
        ftwr = tw.real.astype(jnp.float32)
        ftwi = tw.imag.astype(jnp.float32)
        out_re, out_im = fft_axis1_twiddle_pallas(
            re, im, ftwr, ftwi, tile_c=tile, inverse=inverse,
            interpret=interpret)
    else:
        out_re, out_im = fft_axis1_pallas(re, im, tile_c=tile,
                                          inverse=inverse,
                                          interpret=interpret)
    record_launch("fft-c2c-axis1", grid=(flat.shape[0], c // tile),
                  tile=(r, tile), bytes_moved=16 * flat.shape[0] * r * c,
                  shape=(flat.shape[0], r, c))
    return (out_re + 1j * out_im).reshape(*lead, r, c)


def fft_kernel_r2c_t(x: jax.Array, *, interpret: bool | None = None,
                     tile_b: int | None = None) -> jax.Array:
    """Fused R2C + transposed write: (..., R, C) real -> (..., C/2+1, R)."""
    if interpret is None:
        interpret = use_interpret()
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.real
    r, c = x.shape[-2:]
    _check_kernel_length(max(c // 2, 1))
    if c < 4:
        raise ValueError(f"fused R2C needs C >= 4, got {c}")
    flat, lead = _flatten3(x.astype(jnp.float32))
    tile = _lane_tile(r, c, planes=8, override=tile_b)
    out_re, out_im = rfft_t_pallas(flat, tile_r=tile, interpret=interpret)
    record_launch(
        "fft-r2c-t", grid=(flat.shape[0], r // tile), tile=(tile, c),
        bytes_moved=4 * flat.shape[0] * r * (c + 2 * (c // 2 + 1)),
        shape=(flat.shape[0], r, c))
    return (out_re + 1j * out_im).reshape(*lead, c // 2 + 1, r)


def transpose_kernel(x: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """Tiled last-two-axes transpose: (..., R, C) -> (..., C, R), one pass.

    Complex inputs travel as split re/im planes (TPU Pallas wants real
    dtypes); each plane is transposed tile by tile in VMEM.
    """
    if interpret is None:
        interpret = use_interpret()
    x = jnp.asarray(x)
    r, c = x.shape[-2:]
    flat, lead = _flatten3(x)
    tc = _lane_tile(c, LANES, planes=8)
    tr = _lane_tile(r, tc, planes=8)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        re, im = transpose_pallas(flat.real, flat.imag, tile_r=tr, tile_c=tc,
                                  interpret=interpret)
        record_launch("transpose", grid=(flat.shape[0], r // tr, c // tc),
                      tile=(tr, tc),
                      bytes_moved=2 * flat.shape[0] * r * c * x.dtype.itemsize,
                      shape=(flat.shape[0], r, c))
        return (re + 1j * im).astype(x.dtype).reshape(*lead, c, r)
    (out,) = transpose_pallas(flat, tile_r=tr, tile_c=tc, interpret=interpret)
    record_launch("transpose", grid=(flat.shape[0], r // tr, c // tc),
                  tile=(tr, tc),
                  bytes_moved=2 * flat.shape[0] * r * c * x.dtype.itemsize,
                  shape=(flat.shape[0], r, c))
    return out.reshape(*lead, c, r)


def fft_kernel_r2c(x: jax.Array, *, interpret: bool | None = None,
                   tile_b: int | None = None) -> jax.Array:
    """Batched pow2 R2C FFT: (..., N) real -> (..., N/2+1) complex.

    Packs N reals as N/2 complex points, so it accepts N up to
    2 * MAX_KERNEL_N; the Hermitian split runs fused inside the kernel.
    """
    if interpret is None:
        interpret = use_interpret()
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.real
    n = x.shape[-1]
    _check_kernel_length(max(n // 2, 1))
    if n < 4:
        from repro.fft.stockham import rfft
        return rfft(x)
    flat, lead, b = _flatten(x.astype(jnp.float32))
    (flat,), tile = _tile_and_pad([flat], b, n, tile_b=tile_b)
    out_re, out_im = rfft_pallas(flat, tile_b=tile, interpret=interpret)
    padded = b + (-b) % tile
    record_launch("fft-r2c", grid=(padded // tile,), tile=(tile, n),
                  bytes_moved=4 * padded * (n + 2 * (n // 2 + 1)),
                  shape=(b, n))
    if out_re.shape[0] != b:
        out_re, out_im = out_re[:b], out_im[:b]
    return (out_re + 1j * out_im).reshape(*lead, n // 2 + 1)


def fft_kernel_c2r(x: jax.Array, *, interpret: bool | None = None,
                   tile_b: int | None = None) -> jax.Array:
    """Batched pow2 C2R inverse: (..., N/2+1) half-spectrum -> (..., N) real.

    The exact inverse of :func:`fft_kernel_r2c` (1/N normalised, matching
    ``jnp.fft.irfft``).
    """
    if interpret is None:
        interpret = use_interpret()
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    m = x.shape[-1] - 1
    n = 2 * m
    _check_kernel_length(max(m, 1))
    if n < 4:
        from repro.fft.stockham import irfft
        return irfft(x)
    flat, lead, b = _flatten(x)
    re = flat.real.astype(jnp.float32)
    im = flat.imag.astype(jnp.float32)
    (re, im), tile = _tile_and_pad([re, im], b, n, tile_b=tile_b)
    out = irfft_pallas(re, im, tile_b=tile, interpret=interpret)
    padded = b + (-b) % tile
    record_launch("fft-c2r", grid=(padded // tile,), tile=(tile, n),
                  bytes_moved=4 * padded * (2 * (m + 1) + n),
                  shape=(b, n))
    if out.shape[0] != b:
        out = out[:b]
    return out.reshape(*lead, n)
