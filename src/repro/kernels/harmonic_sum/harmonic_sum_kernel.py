"""Harmonic-sum Pallas kernel — gather-free decimate-and-add on the MXU.

GPU pulsar pipelines implement S_h[k] = sum_j P[j*k] with texture/global
gathers; TPU has no efficient gather, and its compiler lowers no
stride-j lane slice either, so we ADAPT the algorithm (DESIGN.md: rethink
for the TPU memory hierarchy):

  P[j*k] for the 128 bins k of output block c  ==  W_c,j @ S_j

where W_c,j is the (rows, 128 j) window of P starting at bin 128 j c (an
aligned lane slice) and S_j is the constant (128 j, 128) 0/1 matrix with
S_j[j t, t] = 1.  The product runs on the MXU at HIGHEST precision,
which reproduces each selected float32 value exactly.  The ops layer pads
each row with 128 * H zero bins past the 128-aligned length, so a window
that runs off the spectrum reads zeros: exactly the zero-padded
convention P[i] = 0 for i >= N.

Grid: 1-D over batch-row tiles; the whole (padded) spectrum row stays in
VMEM because harmonic j of block c reads bins up to 128 j (c + 1).  The
body walks the 128-bin output blocks in a loop, so the program size does
not grow with N.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANES

_HI = jax.lax.Precision.HIGHEST
# Selection matrices up to this many bytes are built once per grid step;
# larger ladders (H > 8) rebuild each one where it is used.
_HOIST_BYTES = 4 * 2**20


def _selection(j: int) -> jax.Array:
    rows = jax.lax.broadcasted_iota(jnp.int32, (LANES * j, LANES), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (LANES * j, LANES), 1)
    return (rows == j * cols).astype(jnp.float32)


def _ladder(p_ref, n_harmonics: int, n_out: int, emit) -> None:
    """Walk the 128-bin output blocks; call ``emit(off, level, h, acc)``
    with the running harmonic sum S_h of block ``off`` at each level."""
    levels = int(math.log2(n_harmonics)) + 1
    js = range(2, n_harmonics + 1)
    hoist = 4 * LANES * LANES * sum(js) <= _HOIST_BYTES
    sels = {j: _selection(j) for j in js} if hoist else {}

    def block(c, carry):
        off = pl.multiple_of(c * LANES, LANES)
        acc = p_ref[:, pl.ds(off, LANES)]
        emit(off, 0, 1, acc)
        h = 1
        for lev in range(1, levels):
            h *= 2
            for j in range(h // 2 + 1, h + 1):
                start = pl.multiple_of(jnp.minimum(c * (LANES * j), n_out),
                                       LANES)
                win = p_ref[:, pl.ds(start, LANES * j)]
                sel = sels[j] if hoist else _selection(j)
                acc = acc + jnp.dot(win, sel, precision=_HI,
                                    preferred_element_type=jnp.float32)
            emit(off, lev, h, acc)
        return carry

    jax.lax.fori_loop(0, n_out // LANES, block, 0)


def _hsum_body(p_ref, out_ref, *, n_harmonics: int, n_out: int):
    def emit(off, lev, h, acc):
        out_ref[:, lev, pl.ds(off, LANES)] = acc

    _ladder(p_ref, n_harmonics, n_out, emit)


def _hsum_plane_body(p_ref, stat_ref, lev_ref, *, n_harmonics: int,
                     n_out: int):
    """Fused ladder + normalisation + best-level reduction.

    The production pipeline path: builds the same doubling ladder as
    ``_hsum_body`` but never writes it — each level is normalised in
    VMEM to the detection statistic  z_h = (S_h - h) / sqrt(h)  (the
    FDAS power plane is ~chi^2(2)/2 under the null, per-bin mean 1) and
    max-reduced on the spot.  Only the winning statistic and its level
    index leave VMEM.
    """
    def emit(off, lev, h, acc):
        z = (acc - h) * (1.0 / math.sqrt(h))
        cols = pl.ds(off, LANES)
        if lev == 0:
            stat_ref[:, cols] = z
            lev_ref[:, cols] = jnp.zeros(z.shape, jnp.int32)
            return
        best = stat_ref[:, cols]
        better = z > best
        stat_ref[:, cols] = jnp.where(better, z, best)
        lev_ref[:, cols] = jnp.where(better, lev, lev_ref[:, cols])

    _ladder(p_ref, n_harmonics, n_out, emit)


def _check(b: int, tile_b: int, width: int, n_harmonics: int) -> int:
    if tile_b < 1 or b % tile_b:
        raise ValueError(
            f"batch={b} is not a multiple of its tile ({tile_b}); the ops "
            f"layer (repro.kernels.harmonic_sum.ops) pads batches to tile "
            f"multiples — route through it or pass a dividing tile")
    n_out = width - LANES * n_harmonics
    if n_out < LANES or n_out % LANES:
        raise ValueError(
            f"spectrum rows must be a 128-multiple of bins plus "
            f"{LANES * n_harmonics} zero bins, got width {width}; the ops "
            f"layer pads them")
    return n_out


@functools.partial(jax.jit,
                   static_argnames=("n_harmonics", "tile_b", "interpret"))
def harmonic_sum_plane_pallas(power: jax.Array, n_harmonics: int, *,
                              tile_b: int = 8, interpret: bool = False):
    """(b, n_out + 128 H) zero-padded power -> ((b, n_out) best statistic,
    (b, n_out) int32 level)."""
    b, width = power.shape
    n_out = _check(b, tile_b, width, n_harmonics)
    out_spec = pl.BlockSpec((tile_b, n_out), lambda i: (i, 0))
    fn = pl.pallas_call(
        functools.partial(_hsum_plane_body, n_harmonics=n_harmonics,
                          n_out=n_out),
        grid=(b // tile_b,),
        in_specs=[pl.BlockSpec((tile_b, width), lambda i: (i, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, n_out), jnp.float32),
                   jax.ShapeDtypeStruct((b, n_out), jnp.int32)],
        interpret=interpret,
    )
    return tuple(fn(power))


@functools.partial(jax.jit,
                   static_argnames=("n_harmonics", "tile_b", "interpret"))
def harmonic_sum_pallas(power: jax.Array, n_harmonics: int, *,
                        tile_b: int = 8, interpret: bool = False):
    """(b, n_out + 128 H) zero-padded power -> (b, LEVELS, n_out) ladder."""
    b, width = power.shape
    n_out = _check(b, tile_b, width, n_harmonics)
    levels = int(math.log2(n_harmonics)) + 1
    fn = pl.pallas_call(
        functools.partial(_hsum_body, n_harmonics=n_harmonics, n_out=n_out),
        grid=(b // tile_b,),
        in_specs=[pl.BlockSpec((tile_b, width), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_b, levels, n_out), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, levels, n_out), power.dtype),
        interpret=interpret,
    )
    return fn(power)
