"""Dedispersion Pallas kernel — gather-free shift-and-sum.

GPU dedispersion engines walk a (DM, channel) delay table with global
gathers; TPU has no efficient gather, so we ADAPT the algorithm the same
way the harmonic-sum kernel does (DESIGN.md: rethink for the TPU memory
hierarchy): every delay is a whole number of samples, so

  x[c, t + d]  over t = 0..N-1-d  ==  the affine ``lax.slice`` x[c, d:]

zero-padded back to length N.  On the chip that is a lane rotation
(``pltpu.roll``) by N - d with the wrapped tail masked to zero.  The
(D, C) delay table rides in SMEM as scalar-prefetch data (flattened, so
SMEM pads no minor dimension) and the kernel loops over trials and
channels, so the program stays the same size for any table (an unrolled
D x C table of unaligned slices took the compiler minutes at 16 x 64).

Grid: (batch tiles, channel tiles).  The channel axis is a reduction:
each step reads one (TILE_B, TILE_C, N) slab of the filterbank once and
adds its D * TILE_C shifted rows into the (TILE_B, D, N) output block,
which stays resident in VMEM across the channel steps (its block index
does not change with the channel), so the filterbank is read from HBM
exactly once and the channel count is bounded by nothing in VMEM.  The
sum still runs over channels 0..C-1 in order, as the untiled kernel did.
Each trial keeps its whole time axis resident (a time-tiled variant would
need halo reads of max-delay samples per tile, the overhead-access t_o
term the paper's Sec. 5 discussion prices), so N is what VMEM bounds:
2 * (D + TILE_C) * N * 4 bytes of blocks per filterbank row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import vmem_compiler_params


def _dedisp_body(delays_ref, fb_ref, out_ref, *, nchan: int):
    tb, tc, n = fb_ref.shape
    c0 = pl.program_id(1) * tc                 # first channel of the slab
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, n), 1)

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def trial(d, carry):
        def channel(c, acc):
            x = fb_ref[:, pl.ds(c, 1), :].reshape(tb, n)
            s = delays_ref[d * nchan + c0 + c]
            # y[t] = x[t + s] for t < n - s, else 0 (zero-padded shift).
            y = pltpu.roll(x, jnp.where(s == 0, 0, n - s), 1)
            return acc + jnp.where(lane < n - s, y, 0.0)

        acc = jax.lax.fori_loop(0, tc, channel,
                                out_ref[:, pl.ds(d, 1), :].reshape(tb, n))
        out_ref[:, pl.ds(d, 1), :] = acc.reshape(tb, 1, n)
        return carry

    jax.lax.fori_loop(0, out_ref.shape[1], trial, 0)


@functools.partial(jax.jit,
                   static_argnames=("delays", "tile_b", "tile_c", "interpret"))
def dedisperse_pallas(fb: jax.Array,
                      delays: tuple[tuple[int, ...], ...], *,
                      tile_b: int = 1, tile_c: int | None = None,
                      interpret: bool = False):
    """(b, C, N) filterbanks + static (D, C) delay table -> (b, D, N).

    ``tile_c`` channels are summed per grid step (None: all of them); it
    divides C and is a multiple of 8 or C itself.
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
    fn = pl.pallas_call(
        functools.partial(_dedisp_body, nchan=nchan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // tile_b, nchan // tile_c),
            in_specs=[pl.BlockSpec((tile_b, tile_c, n),
                                   lambda i, j, _: (i, j, 0))],
            out_specs=pl.BlockSpec((tile_b, ndm, n),
                                   lambda i, j, _: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((b, ndm, n), jnp.float32),
        compiler_params=vmem_compiler_params(
            2 * (tile_c + ndm) * tile_b * n * 4),
        interpret=interpret,
    )
    return fn(jnp.asarray(np.asarray(delays, np.int32).reshape(-1)), fb)
