"""Fused |X|^2 + mean/variance Pallas kernel.

The pipeline's power-spectrum and normalisation stages each re-read the
spectrum from HBM on the GPU implementation; fusing them halves the HBM
traffic of the non-FFT pipeline (a beyond-paper optimisation recorded in
EXPERIMENTS.md Sec. Perf).  One pass: read (re, im), emit power, and reduce
sum / sum-of-squares for the row statistics.

Grid: 1-D over batch tiles; (TILE_B, N) resident in VMEM.  The row
mean and variance come out as (TILE_B, 1) columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _spectrum_body(re_ref, im_ref, p_ref, mean_ref, var_ref):
    re = re_ref[...].astype(jnp.float32)
    im = im_ref[...].astype(jnp.float32)
    n = re.shape[-1]
    p = (re * re + im * im) / n
    p_ref[...] = p
    mean = jnp.mean(p, axis=-1, keepdims=True)
    mean_ref[...] = mean
    var_ref[...] = jnp.mean(p * p, axis=-1, keepdims=True) - mean * mean


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def power_spectrum_stats_pallas(re: jax.Array, im: jax.Array, *,
                                tile_b: int = 8, interpret: bool = False):
    b, n = re.shape
    # A ValueError, not an assert: asserts vanish under ``python -O`` and
    # a non-dividing tile would silently corrupt the grid partition.
    if tile_b < 1 or b % tile_b:
        raise ValueError(
            f"batch={b} is not a multiple of its tile ({tile_b}); the ops "
            f"layer (repro.kernels.spectrum.ops) pads batches to tile "
            f"multiples — route through it or pass a dividing tile")
    row = pl.BlockSpec((tile_b, n), lambda i: (i, 0))
    # Row statistics leave as (tile_b, 1) columns: a rank-1 block of
    # tile_b would have to be a multiple of 128 on the chip.
    vec = pl.BlockSpec((tile_b, 1), lambda i: (i, 0))
    fn = pl.pallas_call(
        _spectrum_body,
        grid=(b // tile_b,),
        in_specs=[row, row],
        out_specs=[row, vec, vec],
        out_shape=[
            jax.ShapeDtypeStruct((b, n), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ],
        name="power_spectrum_stats_pallas",
        interpret=interpret,
    )
    return fn(re, im)
