"""Pow2 FFT Pallas kernels: DFT-matrix products on the MXU.

TPU adaptation of the paper's single-kernel cuFFT plans (DESIGN.md Sec. 3):
one Pallas program instance keeps a tile of transforms resident in VMEM
and runs the whole transform before writing back, so HBM traffic is one
read + one write of the batch (the paper's t_o -> 0 case, Sec. 5).

Layout and arithmetic:
  * complex data travels as separate (re, im) float32 planes — TPU vector
    memory wants real dtypes;
  * a grid step pins a (tile, N) window; the body walks it in row
    sub-blocks (8 rows, the sublane height), so the unrolled program and
    its temporaries stay the same size whatever the tile is;
  * each sub-block is transformed with dense DFT matrices on the MXU in
    float32 at ``Precision.HIGHEST``:
      - N <= 128: one (rows, N) @ (N, N) product;
      - N > 128: N = N1 * 128 four-step inside VMEM.  The row is viewed as
        (N1, 128) (sample n = n1 * 128 + n2), transformed along n1 by an
        (N1, N1) matrix, multiplied by the twiddle W_N^(n2 k1), transformed
        along n2 by a (128, 128) matrix whose product is written as
        (k2, k1) — which flattens to natural order k = k1 + N1 * k2.
    Every live array keeps 128 lanes (or the row length) in its minor
    dimension: no array is ever shaped with the few-lane minor dimension
    a radix butterfly stage would produce.
  * R2C transforms real rows directly (real first product, the half
    spectrum's 64 rows of the second, the Nyquist bin as an alternating
    sum); C2R is the mirror (a one-sided spectrum weighted 1, 2, ..., 2
    through the inverse four-step, real part only).  Neither needs a lane
    reversal or a stride-2 de-interleave.

VMEM: the ops layer (``repro.kernels.fft.ops``) sizes tiles against
Mosaic's 16 MiB scoped limit.  The transposed-write kernels need 128-row
tiles to keep their output lane-dense; where those blocks cannot fit the
default limit, the kernel raises it for itself
(``repro.kernels.common.vmem_compiler_params``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, SUBLANES, vmem_compiler_params

_HI = jax.lax.Precision.HIGHEST


def _require_pow2(n: int, what: str, minimum: int = 1) -> None:
    """ValueError, not assert: asserts vanish under ``python -O`` and turn
    malformed launches into silent corruption inside the kernel."""
    if n < 1 or n & (n - 1):
        raise ValueError(f"{what} must be a power of two, got {n}")
    if n < minimum:
        raise ValueError(f"{what} must be >= {minimum}, got {n}")


def _require_tiled(size: int, tile: int, axis: str) -> None:
    if tile < 1 or size % tile:
        raise ValueError(
            f"{axis}={size} is not a multiple of its tile ({tile}); the "
            f"ops layer (repro.kernels.fft.ops) pads batches to tile "
            f"multiples — route through it or pass a dividing tile")


# ---------------------------------------------------------------------------
# Host-side DFT tables (float64 maths, float32 planes, memoised per length)
# ---------------------------------------------------------------------------

def _planes(*mats: np.ndarray) -> tuple[np.ndarray, ...]:
    out: list[np.ndarray] = []
    for m in mats:
        out += [np.asarray(m.real, np.float32), np.asarray(m.imag, np.float32)]
    return tuple(out)


def _w(rows, cols, n: int, sign: float) -> np.ndarray:
    return np.exp(sign * 2j * np.pi * np.outer(rows, cols) / n)


@functools.lru_cache(maxsize=None)
def c2c_tables(n: int, inverse: bool = False) -> tuple[np.ndarray, ...]:
    """Matrices of the length-``n`` C2C transform (1/n folded in for the
    inverse): (F) for n <= 128, else (F1, twiddle, F2) as re/im planes."""
    sign = 1.0 if inverse else -1.0
    scale = 1.0 / n if inverse else 1.0
    if n <= LANES:
        k = np.arange(n)
        return _planes(_w(k, k, n, sign) * scale)
    n1 = n // LANES
    k1, k2 = np.arange(n1), np.arange(LANES)
    return _planes(_w(k1, k1, n1, sign) * scale,       # F1[k1, n1]
                   _w(k1, k2, n, sign),                # twiddle[k1, n2]
                   _w(k2, k2, LANES, sign))            # F2[k2, n2]


@functools.lru_cache(maxsize=None)
def r2c_tables(n: int) -> tuple[np.ndarray, ...]:
    """Matrices for bins 0..n/2-1 of a length-``n`` real transform (the
    Nyquist bin is an alternating sum computed in-kernel)."""
    m = n // 2
    if n <= LANES:
        return _planes(_w(np.arange(n), np.arange(m), n, -1.0))
    n1 = n // LANES
    k1, k2 = np.arange(n1), np.arange(LANES)
    return _planes(_w(k1, k1, n1, -1.0),
                   _w(k1, k2, n, -1.0),
                   _w(np.arange(LANES // 2), k2, LANES, -1.0))


@functools.lru_cache(maxsize=None)
def c2r_tables(n: int) -> tuple[np.ndarray, ...]:
    """Matrices of the length-``n`` C2R inverse over bins 0..n/2-1, with
    the one-sided weights (1, 2, ..., 2) and 1/n folded in."""
    m = n // 2
    weight = np.full(m, 2.0 / n)
    weight[0] = 1.0 / n
    if n <= LANES:
        return _planes(weight[:, None] * _w(np.arange(m), np.arange(n), n,
                                            1.0))
    n1 = n // LANES
    k1, k2 = np.arange(n1), np.arange(LANES)
    g1 = _w(k1, np.arange(n1 // 2), n1, 1.0)           # G1[n_a, k1]
    return (_planes(g1)
            + (np.asarray(weight.reshape(n1 // 2, LANES), np.float32),)
            + _planes(_w(k1, k2, n, 1.0), _w(k2, k2, LANES, 1.0)))


# ---------------------------------------------------------------------------
# In-VMEM transforms of one row sub-block
# ---------------------------------------------------------------------------

def _mm(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _bmm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _cprod(f, ar, ai, br, bi):
    """Complex product through a real bilinear map ``f``."""
    return f(ar, br) - f(ai, bi), f(ar, bi) + f(ai, br)


def _cmul(ar, ai, br, bi):
    """Elementwise complex multiply on split planes."""
    return ar * br - ai * bi, ar * bi + ai * br


def _bcast(m, rows: int):
    return jnp.broadcast_to(m, (rows,) + m.shape)


_LEFT = functools.partial(_bmm, "bkn,bnm->bkm")       # M[k, n] @ X_b[n, :]
_RIGHT_T = functools.partial(_bmm, "bkn,bjn->bkj")    # (X_b @ M^T)^T


def _c2c_rows(xr, xi, t):
    """C2C DFT of every row of a (rows, n) re/im pair."""
    s, n = xr.shape
    if n <= LANES:
        return _cprod(_mm, xr, xi, *t)
    f1r, f1i, twr, twi, f2r, f2i = t
    n1 = n // LANES
    xr = xr.reshape(s, n1, LANES)
    xi = xi.reshape(s, n1, LANES)
    ar, ai = _cprod(_LEFT, _bcast(f1r, s), _bcast(f1i, s), xr, xi)
    ar, ai = _cmul(ar, ai, twr, twi)
    yr, yi = _cprod(_RIGHT_T, _bcast(f2r, s), _bcast(f2i, s), ar, ai)
    return yr.reshape(s, n), yi.reshape(s, n)


def _alternating(shape, axis: int):
    """(-1)^index along ``axis`` as float32."""
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return (1 - 2 * (idx & 1)).astype(jnp.float32)


def _r2c_rows(x, t):
    """Bins 0..n/2-1 of the real DFT of each row of (rows, n) ``x``, and
    the (rows, 1) Nyquist bin."""
    s, n = x.shape
    nyq = jnp.sum(x * _alternating(x.shape, 1), axis=1, keepdims=True)
    if n <= LANES:
        fr, fi = t
        return _mm(x, fr), _mm(x, fi), nyq
    f1r, f1i, twr, twi, f2r, f2i = t
    n1 = n // LANES
    x3 = x.reshape(s, n1, LANES)
    ar, ai = _LEFT(_bcast(f1r, s), x3), _LEFT(_bcast(f1i, s), x3)
    ar, ai = _cmul(ar, ai, twr, twi)
    yr, yi = _cprod(_RIGHT_T, _bcast(f2r, s), _bcast(f2i, s), ar, ai)
    return yr.reshape(s, n // 2), yi.reshape(s, n // 2), nyq


def _c2r_rows(zr, zi, nyq, t):
    """Real inverse of (rows, n/2) bins 0..n/2-1 plus the (rows, 1) real
    Nyquist bin (imaginary parts of DC and Nyquist drop, as numpy does)."""
    s, m = zr.shape
    n = 2 * m
    tail = nyq * (_alternating((s, n), 1) * (1.0 / n))
    if n <= LANES:
        gr, gi = t
        return _mm(zr, gr) - _mm(zi, gi) + tail
    g1r, g1i, weight, twr, twi, g2r, g2i = t
    n1 = n // LANES
    zr = zr.reshape(s, n1 // 2, LANES) * weight
    zi = zi.reshape(s, n1 // 2, LANES) * weight
    ar, ai = _cprod(_LEFT, _bcast(g1r, s), _bcast(g1i, s), zr, zi)
    ar, ai = _cmul(ar, ai, twr, twi)
    y = _RIGHT_T(_bcast(g2r, s), ar) - _RIGHT_T(_bcast(g2i, s), ai)
    return y.reshape(s, n) + tail


def _sub_rows(n: int, tile: int) -> int:
    """Rows per in-kernel step: 8 for the four-step (its batched products
    unroll per row), up to 256 for the single direct product."""
    if tile % SUBLANES:
        return tile
    if n <= LANES:
        return math.gcd(tile, 256)
    return SUBLANES


def _for_row_blocks(rows: int, sub: int, fn) -> None:
    """Call ``fn(pl.ds(start, sub))`` for each ``sub``-row block."""
    if rows == sub:
        fn(pl.ds(0, sub))
        return

    def step(i, carry):
        fn(pl.ds(pl.multiple_of(i * sub, sub), sub))
        return carry

    jax.lax.fori_loop(0, rows // sub, step, 0)


def _load(refs) -> tuple:
    return tuple(r[...] for r in refs)


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

def _c2c_body(re_ref, im_ref, *refs, n_tab: int, sub: int):
    tabs, (out_re_ref, out_im_ref) = refs[:n_tab], refs[n_tab:]
    t = _load(tabs)

    def step(rows):
        yr, yi = _c2c_rows(re_ref[rows, :], im_ref[rows, :], t)
        out_re_ref[rows, :] = yr
        out_im_ref[rows, :] = yi

    _for_row_blocks(re_ref.shape[0], sub, step)


def _c2c_mul_body(re_ref, im_ref, *refs, n_tab: int):
    """FFT each (1, n) row of the tile, then multiply by the (T, n) bank.

    The bank multiply is a fused epilogue: the transformed row is still
    resident in VMEM when it is broadcast against every filter, so the
    (T, n) product plane costs one HBM read of the row plus one write of
    the plane — no standalone multiply pass.
    """
    tabs = refs[:n_tab]
    fbr_ref, fbi_ref, out_re_ref, out_im_ref = refs[n_tab:]
    t = _load(tabs)

    def step(i, carry):
        yr, yi = _c2c_rows(re_ref[i], im_ref[i], t)          # (1, n)
        pr, pi = _cmul(yr, yi, fbr_ref[...], fbi_ref[...])   # (T, n)
        out_re_ref[i] = pr
        out_im_ref[i] = pi
        return carry

    jax.lax.fori_loop(0, re_ref.shape[0], step, 0)


def _r2c_body(x_ref, *refs, n_tab: int, sub: int):
    tabs, (out_re_ref, out_im_ref) = refs[:n_tab], refs[n_tab:]
    t = _load(tabs)
    m = x_ref.shape[1] // 2

    def step(rows):
        yr, yi, nyq = _r2c_rows(x_ref[rows, :], t)
        out_re_ref[rows, pl.ds(0, m)] = yr
        out_im_ref[rows, pl.ds(0, m)] = yi
        out_re_ref[rows, pl.ds(m, 1)] = nyq
        out_im_ref[rows, pl.ds(m, 1)] = jnp.zeros_like(nyq)

    _for_row_blocks(x_ref.shape[0], sub, step)


def _c2r_body(xr_ref, xi_ref, *refs, n_tab: int, sub: int):
    tabs, (out_ref,) = refs[:n_tab], refs[n_tab:]
    t = _load(tabs)
    m = xr_ref.shape[1] - 1

    def step(rows):
        out_ref[rows, :] = _c2r_rows(xr_ref[rows, pl.ds(0, m)],
                                     xi_ref[rows, pl.ds(0, m)],
                                     xr_ref[rows, pl.ds(m, 1)], t)

    _for_row_blocks(xr_ref.shape[0], sub, step)


def _fft_t_body(re_ref, im_ref, *refs, n_tab: int, sub: int,
                twiddle: bool):
    """FFT a (1, tile_r, n) tile of rows, write it transposed (1, n, tile_r).

    The row->column hand-off of a 2-D (or four-step) transform costs no
    extra HBM pass: rows are transformed into a VMEM scratch and the
    scratch is written out transposed.  ``twiddle`` fuses the four-step
    inter-pass multiply (the (tile_r, n) window of an (R, n) table).
    """
    tabs = refs[:n_tab]
    rest = refs[n_tab:]
    if twiddle:
        ftwr_ref, ftwi_ref, *rest = rest
    out_re_ref, out_im_ref, scr_r, scr_i = rest
    t = _load(tabs)

    def step(rows):
        yr, yi = _c2c_rows(re_ref[0, rows, :], im_ref[0, rows, :], t)
        if twiddle:
            yr, yi = _cmul(yr, yi, ftwr_ref[rows, :], ftwi_ref[rows, :])
        scr_r[rows, :] = yr
        scr_i[rows, :] = yi

    _for_row_blocks(re_ref.shape[1], sub, step)
    out_re_ref[0] = scr_r[...].T
    out_im_ref[0] = scr_i[...].T


def _fft_axis1_body(re_ref, im_ref, *refs, n_tab: int, sub: int,
                    twiddle: bool):
    """FFT over axis -2 of a (1, R, tile_c) tile, layout preserved.

    Transpose-read into VMEM scratch, transform the rows in place,
    transpose-write: the column pass of a four-step / 2-D plan without an
    HBM transpose.  ``twiddle`` multiplies by the (tile_c, R) window of a
    (C, R) table: element [j, k] scales output bin k of column j.
    """
    tabs = refs[:n_tab]
    rest = refs[n_tab:]
    if twiddle:
        ftwr_ref, ftwi_ref, *rest = rest
    out_re_ref, out_im_ref, scr_r, scr_i = rest
    t = _load(tabs)
    scr_r[...] = re_ref[0].T
    scr_i[...] = im_ref[0].T

    def step(rows):
        yr, yi = _c2c_rows(scr_r[rows, :], scr_i[rows, :], t)
        if twiddle:
            yr, yi = _cmul(yr, yi, ftwr_ref[rows, :], ftwi_ref[rows, :])
        scr_r[rows, :] = yr
        scr_i[rows, :] = yi

    _for_row_blocks(scr_r.shape[0], sub, step)
    out_re_ref[0] = scr_r[...].T
    out_im_ref[0] = scr_i[...].T


def _r2c_t_body(x_ref, *refs, n_tab: int, sub: int):
    """Fused R2C + transposed write: (1, tile_r, n) real -> (1, n/2+1,
    tile_r) re/im, through (tile_r, n/2) scratch planes."""
    tabs = refs[:n_tab]
    out_re_ref, out_im_ref, scr_r, scr_i = refs[n_tab:]
    t = _load(tabs)
    n = x_ref.shape[2]
    m = n // 2

    def step(rows):
        yr, yi, _ = _r2c_rows(x_ref[0, rows, :], t)
        scr_r[rows, :] = yr
        scr_i[rows, :] = yi

    _for_row_blocks(x_ref.shape[1], sub, step)
    out_re_ref[0, pl.ds(0, m), :] = scr_r[...].T
    out_im_ref[0, pl.ds(0, m), :] = scr_i[...].T
    # Nyquist row, already transposed: alternating-sign row times x^T.
    nyq = jax.lax.dot_general(_alternating((1, n), 1), x_ref[0],
                              (((1,), (1,)), ((), ())), precision=_HI,
                              preferred_element_type=jnp.float32)
    out_re_ref[0, pl.ds(m, 1), :] = nyq
    out_im_ref[0, pl.ds(m, 1), :] = jnp.zeros_like(nyq)


def _transpose_body(*refs):
    """Tiled transpose: k (1, tr, tc) input planes -> k (1, tc, tr) planes."""
    k = len(refs) // 2
    for i in range(k):
        refs[k + i][0] = refs[i][0].T


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _table_args(tables):
    """Constant tables as kernel inputs: whole-array blocks whose index
    never changes, so each is copied into VMEM once per call."""
    arrays = [jnp.asarray(t) for t in tables]
    specs = [pl.BlockSpec(t.shape, lambda *_, _nd=t.ndim: (0,) * _nd)
             for t in tables]
    return arrays, specs


@functools.partial(jax.jit,
                   static_argnames=("tile_b", "inverse", "interpret"))
def fft_pallas(re: jax.Array, im: jax.Array, *, tile_b: int = 8,
               inverse: bool = False, interpret: bool = False):
    """Batched pow2 C2C FFT over the last axis; (B, N) re/im in, same out."""
    b, n = re.shape
    _require_pow2(n, "FFT length")
    _require_tiled(b, tile_b, "batch")
    if n == 1:
        return re, im
    tabs, tab_specs = _table_args(c2c_tables(n, inverse))
    spec = pl.BlockSpec((tile_b, n), lambda i: (i, 0))
    fn = pl.pallas_call(
        functools.partial(_c2c_body, n_tab=len(tabs),
                          sub=_sub_rows(n, tile_b)),
        grid=(b // tile_b,),
        in_specs=[spec, spec] + tab_specs,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((b, n), jnp.float32)] * 2,
        name="fft_pallas",
        interpret=interpret,
    )
    return tuple(fn(re, im, *tabs))


@functools.partial(jax.jit,
                   static_argnames=("tile_b", "inverse", "interpret"))
def fft_mul_pallas(re: jax.Array, im: jax.Array, fbr: jax.Array,
                   fbi: jax.Array, *, tile_b: int = 8,
                   inverse: bool = False, interpret: bool = False):
    """Batched pow2 C2C FFT fused with a (T, N) filter-bank multiply.

    (B, N) re/im in, (B, T, N) re/im out: out[b, t] = FFT(x[b]) * f[t].
    Rows travel as (B, 1, N) so the batch tile is a leading block
    dimension (any size, not a sublane multiple); the whole bank stays
    pinned in VMEM across grid steps.
    """
    b, n = re.shape
    t = fbr.shape[0]
    _require_pow2(n, "FFT length")
    _require_tiled(b, tile_b, "batch")
    if fbr.shape != (t, n):
        raise ValueError(
            f"filter-bank planes must be (T, {n}), got {fbr.shape}")
    tabs, tab_specs = _table_args(c2c_tables(n, inverse))
    in_spec = pl.BlockSpec((tile_b, 1, n), lambda i: (i, 0, 0))
    fb_spec = pl.BlockSpec((t, n), lambda i: (0, 0))
    out_spec = pl.BlockSpec((tile_b, t, n), lambda i: (i, 0, 0))
    fn = pl.pallas_call(
        functools.partial(_c2c_mul_body, n_tab=len(tabs)),
        grid=(b // tile_b,),
        in_specs=[in_spec, in_spec] + tab_specs + [fb_spec, fb_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, t, n), jnp.float32)] * 2,
        name="fft_mul_pallas",
        interpret=interpret,
    )
    return tuple(fn(re.reshape(b, 1, n), im.reshape(b, 1, n), *tabs,
                    fbr, fbi))


@functools.partial(jax.jit,
                   static_argnames=("tile_b", "interpret"))
def rfft_pallas(x: jax.Array, *, tile_b: int = 8, interpret: bool = False):
    """Batched pow2 R2C FFT: (B, N) f32 in, (B, N/2+1) re/im out."""
    b, n = x.shape
    _require_pow2(n, "R2C/C2R length", minimum=4)
    _require_tiled(b, tile_b, "batch")
    m = n // 2
    tabs, tab_specs = _table_args(r2c_tables(n))
    fn = pl.pallas_call(
        functools.partial(_r2c_body, n_tab=len(tabs),
                          sub=_sub_rows(n, tile_b)),
        grid=(b // tile_b,),
        in_specs=[pl.BlockSpec((tile_b, n), lambda i: (i, 0))] + tab_specs,
        out_specs=[pl.BlockSpec((tile_b, m + 1), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((b, m + 1), jnp.float32)] * 2,
        name="rfft_pallas",
        interpret=interpret,
    )
    return tuple(fn(x, *tabs))


@functools.partial(jax.jit,
                   static_argnames=("tile_b", "interpret"))
def irfft_pallas(re: jax.Array, im: jax.Array, *, tile_b: int = 8,
                 interpret: bool = False):
    """Batched pow2 C2R inverse: (B, N/2+1) re/im in, (B, N) f32 out."""
    b, m1 = re.shape
    n = 2 * (m1 - 1)
    _require_pow2(n, "R2C/C2R length", minimum=4)
    _require_tiled(b, tile_b, "batch")
    tabs, tab_specs = _table_args(c2r_tables(n))
    in_spec = pl.BlockSpec((tile_b, m1), lambda i: (i, 0))
    fn = pl.pallas_call(
        functools.partial(_c2r_body, n_tab=len(tabs),
                          sub=_sub_rows(n, tile_b)),
        grid=(b // tile_b,),
        in_specs=[in_spec, in_spec] + tab_specs,
        out_specs=pl.BlockSpec((tile_b, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        name="irfft_pallas",
        interpret=interpret,
    )
    return fn(re, im, *tabs)


def _fft_t_call(re, im, ftw, *, tile_r: int, inverse: bool,
                interpret: bool, name: str):
    b, r, c = re.shape
    _require_pow2(c, "row length C")
    _require_tiled(r, tile_r, "rows R")
    tabs, tab_specs = _table_args(c2c_tables(c, inverse))
    in_spec = pl.BlockSpec((1, tile_r, c), lambda i, j: (i, j, 0))
    out_spec = pl.BlockSpec((1, c, tile_r), lambda i, j: (i, 0, j))
    extra, extra_specs = [], []
    planes = 4
    if ftw is not None:
        ftw_spec = pl.BlockSpec((tile_r, c), lambda i, j: (j, 0))
        extra, extra_specs = list(ftw), [ftw_spec, ftw_spec]
        planes = 6
    fn = pl.pallas_call(
        functools.partial(_fft_t_body, n_tab=len(tabs),
                          sub=_sub_rows(c, tile_r),
                          twiddle=ftw is not None),
        grid=(b, r // tile_r),
        in_specs=[in_spec, in_spec] + tab_specs + extra_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, c, r), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((tile_r, c), jnp.float32)] * 2,
        compiler_params=vmem_compiler_params(
            (2 * planes + 4) * tile_r * c * 4),
        name=name,
        interpret=interpret,
    )
    return tuple(fn(re, im, *tabs, *extra))


@functools.partial(jax.jit,
                   static_argnames=("tile_r", "inverse", "interpret"))
def fft_t_pallas(re: jax.Array, im: jax.Array, *, tile_r: int = 8,
                 inverse: bool = False, interpret: bool = False):
    """Fused FFT + transposed write: (B, R, C) re/im in -> (B, C, R) out.

    One grid step FFTs a (tile_r, C) row tile and writes it into the
    (C, tile_r) column window of the output — the hand-off transpose of a
    2-D / four-step transform costs zero extra HBM passes.
    """
    return _fft_t_call(re, im, None, tile_r=tile_r, inverse=inverse,
                       interpret=interpret, name="fft_t_pallas")


@functools.partial(jax.jit,
                   static_argnames=("tile_r", "inverse", "interpret"))
def fft_t_twiddle_pallas(re: jax.Array, im: jax.Array, ftwr: jax.Array,
                         ftwi: jax.Array, *, tile_r: int = 8,
                         inverse: bool = False, interpret: bool = False):
    """:func:`fft_t_pallas` with the four-step inter-pass twiddle fused in.

    ``ftwr``/``ftwi`` is the (R, C) twiddle matrix; each grid step streams
    its (tile_r, C) window and multiplies before the transposed write.
    """
    r, c = re.shape[1:]
    if ftwr.shape != (r, c):
        raise ValueError(
            f"twiddle planes must be ({r}, {c}), got {ftwr.shape}")
    return _fft_t_call(re, im, (ftwr, ftwi), tile_r=tile_r,
                       inverse=inverse, interpret=interpret,
                       name="fft_t_twiddle_pallas")


def _fft_axis1_call(re, im, ftw, *, tile_c: int, inverse: bool,
                    interpret: bool, name: str):
    b, r, c = re.shape
    _require_pow2(r, "column length R")
    _require_tiled(c, tile_c, "columns C")
    tabs, tab_specs = _table_args(c2c_tables(r, inverse))
    spec = pl.BlockSpec((1, r, tile_c), lambda i, j: (i, 0, j))
    extra, extra_specs = [], []
    planes = 4
    if ftw is not None:
        ftw_spec = pl.BlockSpec((tile_c, r), lambda i, j: (j, 0))
        extra, extra_specs = list(ftw), [ftw_spec, ftw_spec]
        planes = 6
    fn = pl.pallas_call(
        functools.partial(_fft_axis1_body, n_tab=len(tabs),
                          sub=_sub_rows(r, tile_c),
                          twiddle=ftw is not None),
        grid=(b, c // tile_c),
        in_specs=[spec, spec] + tab_specs + extra_specs,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((b, r, c), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((tile_c, r), jnp.float32)] * 2,
        compiler_params=vmem_compiler_params(
            (2 * planes + 4) * tile_c * r * 4),
        name=name,
        interpret=interpret,
    )
    return tuple(fn(re, im, *tabs, *extra))


@functools.partial(jax.jit,
                   static_argnames=("tile_c", "inverse", "interpret"))
def fft_axis1_pallas(re: jax.Array, im: jax.Array, *, tile_c: int = 8,
                     inverse: bool = False, interpret: bool = False):
    """FFT over axis -2: (B, R, C) re/im in, (B, R, C) out, layout kept.

    Each grid step pins an (R, tile_c) column tile, transposes it in VMEM,
    transforms the rows and writes it back untransposed — the column pass
    of a 2-D / four-step transform in one HBM round trip.
    """
    return _fft_axis1_call(re, im, None, tile_c=tile_c, inverse=inverse,
                           interpret=interpret, name="fft_axis1_pallas")


@functools.partial(jax.jit,
                   static_argnames=("tile_c", "inverse", "interpret"))
def fft_axis1_twiddle_pallas(re: jax.Array, im: jax.Array, ftwr: jax.Array,
                             ftwi: jax.Array, *, tile_c: int = 8,
                             inverse: bool = False, interpret: bool = False):
    """:func:`fft_axis1_pallas` with a fused (C, R) twiddle epilogue:
    output element [.., k, j] is multiplied by ``ftw[j, k]`` in-kernel."""
    r, c = re.shape[1:]
    if ftwr.shape != (c, r):
        raise ValueError(
            f"twiddle planes must be ({c}, {r}), got {ftwr.shape}")
    return _fft_axis1_call(re, im, (ftwr, ftwi), tile_c=tile_c,
                           inverse=inverse, interpret=interpret,
                           name="fft_axis1_twiddle_pallas")


@functools.partial(jax.jit,
                   static_argnames=("tile_r", "interpret"))
def rfft_t_pallas(x: jax.Array, *, tile_r: int = 8, interpret: bool = False):
    """Fused R2C + transposed write: (B, R, C) f32 -> (B, C/2+1, R) re/im."""
    b, r, c = x.shape
    _require_pow2(c, "R2C row length C", minimum=4)
    _require_tiled(r, tile_r, "rows R")
    m = c // 2
    tabs, tab_specs = _table_args(r2c_tables(c))
    out_spec = pl.BlockSpec((1, m + 1, tile_r), lambda i, j: (i, 0, j))
    fn = pl.pallas_call(
        functools.partial(_r2c_t_body, n_tab=len(tabs),
                          sub=_sub_rows(c, tile_r)),
        grid=(b, r // tile_r),
        in_specs=[pl.BlockSpec((1, tile_r, c), lambda i, j: (i, j, 0))]
        + tab_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, m + 1, r), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((tile_r, m), jnp.float32)] * 2,
        compiler_params=vmem_compiler_params(8 * tile_r * c * 4),
        name="rfft_t_pallas",
        interpret=interpret,
    )
    return tuple(fn(x, *tabs))


@functools.partial(jax.jit,
                   static_argnames=("tile_r", "tile_c", "interpret"))
def transpose_pallas(*planes: jax.Array, tile_r: int = 128,
                     tile_c: int = 128, interpret: bool = False):
    """Tiled last-two-axes transpose: k (B, R, C) planes -> k (B, C, R).

    Reads row-major (tile_r, tile_c) windows, writes them column-major —
    one HBM read + one write instead of an XLA transpose pair around a
    separate kernel.  Used for the plan graph's explicit transpose nodes
    (non-pow2 axes whose FFT pass cannot fuse the hand-off).
    """
    b, r, c = planes[0].shape
    _require_tiled(r, tile_r, "rows R")
    _require_tiled(c, tile_c, "columns C")
    grid = (b, r // tile_r, c // tile_c)
    in_spec = pl.BlockSpec((1, tile_r, tile_c), lambda i, j, k: (i, j, k))
    out_spec = pl.BlockSpec((1, tile_c, tile_r), lambda i, j, k: (i, k, j))
    out_shape = [jax.ShapeDtypeStruct((b, c, r), p.dtype) for p in planes]
    fn = pl.pallas_call(
        _transpose_body,
        grid=grid,
        in_specs=[in_spec] * len(planes),
        out_specs=[out_spec] * len(planes),
        out_shape=out_shape,
        compiler_params=vmem_compiler_params(
            4 * len(planes) * tile_r * tile_c * planes[0].dtype.itemsize),
        name="transpose_pallas",
        interpret=interpret,
    )
    return tuple(fn(*planes))
