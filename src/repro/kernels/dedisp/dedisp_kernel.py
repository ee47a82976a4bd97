"""Dedispersion Pallas kernel — gather-free shift-and-sum on full vregs.

GPU dedispersion engines walk a (DM, channel) delay table with global
gathers; TPU has no efficient gather, so we ADAPT the algorithm the same
way the harmonic-sum kernel does (DESIGN.md: rethink for the TPU memory
hierarchy): every delay is a whole number of samples, so

  x[c, t + d]  over t = 0..N-1-d  ==  the affine ``lax.slice`` x[c, d:]

zero-padded back to length N.

Layout.  Each channel's time axis is laid out as rows of 128 lanes, a
``(rows, 128)`` array, so one (8, 128) vreg holds 1024 consecutive samples
of one channel (a ``(1, N)`` row would fill one sublane of eight).  The
wrapper zero-pads the time axis past N by the largest delay's row and
its successor (rounded up to 8 rows): a sample at or past N reads 0,
the zero-padded convention, and no read leaves the slab.

Shift.  A delay splits as ``s = 128 q + r``.  Output rows ``[t0, t0 +
T)`` read input rows ``[t0 + q, t0 + q + T)`` (A) and their successors
(B) at a dynamic sublane offset; lane ``l`` of the shifted tile is lane
``l + r`` of A where that is < 128, else lane ``l + r - 128`` of B.  So
one per-lane select (A where ``lane >= r``, else B) and one lane
rotation (``pltpu.roll`` by ``128 - r``) build the tile.

Accumulation.  The (D, C) delay table rides in SMEM as scalar-prefetch
data (flattened, so SMEM pads no minor dimension).  The kernel loops over
batch rows, trials and time tiles of T rows; for each it loads the
trial's tile from the resident output block once, adds the slab's
channels c0..c0+TILE_C-1 in order with the tile held in registers (T x
128 float32: T/8 vregs), and writes it back once.  Every loop is a
``fori_loop`` (the channel loop unrolls only a slab of 8), so the program
stays the same size for any table (an unrolled D x C table of unaligned
slices took the compiler minutes at 16 x 64).

Grid: (batch tiles, channel tiles).  The channel axis is a reduction:
each step reads one (TILE_B, TILE_C, rows, 128) slab of the filterbank
once and adds its D * TILE_C shifted rows into the (TILE_B, D, rows, 128)
output block, which stays resident in VMEM across the channel steps (its
block index does not change with the channel), so the filterbank is read
from HBM exactly once and the channel count is bounded by nothing in
VMEM.  The sum runs over channels 0..C-1 in order, in float32, as the
untiled kernel did.  Each trial keeps its whole time axis resident (a
time-tiled grid would need halo reads of max-delay samples per tile, the
overhead-access t_o term the paper's Sec. 5 discussion prices), so N is
what VMEM bounds: 2 * (D + TILE_C) * N * 4 bytes of blocks per
filterbank row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (LANES, SUBLANES, round_up,
                                  vmem_compiler_params)

#: Largest time tile, in rows of 128 lanes: 128 rows are 16 vregs of
#: accumulator, well inside the 64 a core has.
MAX_TILE_ROWS = 128


def row_layout(n: int, max_delay: int) -> tuple[int, int, int]:
    """(output rows, input rows, time-tile rows) for N samples.

    The output keeps N samples in whole (8, 128) tiles; the input adds
    the rows the largest delay reads past them (its row and the
    successor the lane select takes from).  The time tile is the largest
    power of two up to ``MAX_TILE_ROWS`` dividing the output rows.
    """
    rows_out = round_up(pl.cdiv(n, LANES), SUBLANES)
    rows_in = round_up(rows_out + max_delay // LANES + 1, SUBLANES)
    rows_t = MAX_TILE_ROWS
    while rows_out % rows_t:
        rows_t //= 2
    return rows_out, rows_in, rows_t


def _dedisp_body(delays_ref, fb_ref, out_ref, *, nchan: int, rows_t: int):
    tb, tc, _, lanes = fb_ref.shape
    ndm, rows_out = out_ref.shape[1:3]
    c0 = pl.program_id(1) * tc                 # first channel of the slab
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows_t, lanes), 1)

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def batch_row(i, carry):
        def trial(d, carry):
            def time_tile(k, carry):
                t0 = pl.multiple_of(k * rows_t, rows_t)

                def channel(c, acc):
                    s = delays_ref[d * nchan + c0 + c]
                    q, r = s >> 7, s & (lanes - 1)     # s = 128 q + r
                    a = fb_ref[i, c, pl.ds(t0 + q, rows_t), :]
                    b = fb_ref[i, c, pl.ds(t0 + q + 1, rows_t), :]
                    # Lane l of the shifted tile: a[l + r], or b[l + r -
                    # 128] past the row's end; select, then rotate by r.
                    z = jnp.where(lane >= r, a, b)
                    return acc + pltpu.roll(z, (lanes - r) & (lanes - 1), 1)

                tile = out_ref[i, d, pl.ds(t0, rows_t), :]
                out_ref[i, d, pl.ds(t0, rows_t), :] = jax.lax.fori_loop(
                    0, tc, channel, tile, unroll=tc <= SUBLANES)
                return carry

            return jax.lax.fori_loop(0, rows_out // rows_t, time_tile,
                                     carry)

        return jax.lax.fori_loop(0, ndm, trial, carry)

    jax.lax.fori_loop(0, tb, batch_row, 0)


@functools.partial(jax.jit,
                   static_argnames=("delays", "tile_b", "tile_c", "interpret"))
def dedisperse_pallas(fb: jax.Array,
                      delays: tuple[tuple[int, ...], ...], *,
                      tile_b: int = 1, tile_c: int | None = None,
                      interpret: bool = False):
    """(b, C, N) filterbanks + static (D, C) delay table -> (b, D, N).

    ``tile_c`` channels are summed per grid step (None: all of them); it
    divides C and is a multiple of 8 or C itself.  The filterbank is
    zero-padded and viewed as rows of 128 lanes here (``row_layout``),
    and the output's rows past N are sliced off.
    """
    b, nchan, n = fb.shape
    tile_c = nchan if tile_c is None else tile_c
    # A ValueError, not an assert: asserts vanish under ``python -O`` and
    # a non-dividing tile would silently corrupt the grid partition.
    if tile_b < 1 or b % tile_b:
        raise ValueError(
            f"batch={b} is not a multiple of its tile ({tile_b}); the ops "
            f"layer (repro.kernels.dedisp.ops) pads batches to tile "
            f"multiples — route through it or pass a dividing tile")
    if tile_c < 1 or nchan % tile_c or (tile_c != nchan and tile_c % 8):
        raise ValueError(
            f"channel tile {tile_c} must divide nchan={nchan} and be a "
            f"multiple of 8 (or nchan itself)")
    ndm = len(delays)
    for trial, row in enumerate(delays):
        if len(row) != nchan:
            raise ValueError(
                f"delay row {trial} has {len(row)} channels; filterbank "
                f"has {nchan} (shape {fb.shape})")
        for d in row:
            if not 0 <= d < n:
                raise ValueError(
                    f"delay {d} of trial {trial} outside [0, ntime={n}); "
                    f"clip the DM grid to the block length")
    rows_out, rows_in, rows_t = row_layout(n, max(map(max, delays)))
    x = jnp.pad(fb, ((0, 0), (0, 0), (0, rows_in * LANES - n)))
    x = x.reshape(b, nchan, rows_in, LANES)
    fn = pl.pallas_call(
        functools.partial(_dedisp_body, nchan=nchan, rows_t=rows_t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // tile_b, nchan // tile_c),
            in_specs=[pl.BlockSpec((tile_b, tile_c, rows_in, LANES),
                                   lambda i, j, _: (i, j, 0, 0))],
            out_specs=pl.BlockSpec((tile_b, ndm, rows_out, LANES),
                                   lambda i, j, _: (i, 0, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((b, ndm, rows_out, LANES),
                                       jnp.float32),
        compiler_params=vmem_compiler_params(
            2 * (tile_c * rows_in + ndm * rows_out) * tile_b * LANES * 4),
        name="dedisperse_pallas",
        interpret=interpret,
    )
    out = fn(jnp.asarray(np.asarray(delays, np.int32).reshape(-1)), x)
    return out.reshape(b, ndm, rows_out * LANES)[..., :n]
