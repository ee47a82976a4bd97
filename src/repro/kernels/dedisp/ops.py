"""Public wrapper for the dedispersion kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import LANES, SUBLANES, batch_tile, use_interpret
from repro.kernels.dedisp.dedisp_kernel import dedisperse_pallas, row_layout
from repro.obs.ledger import record_launch


def _as_static_delays(delays) -> tuple[tuple[int, ...], ...]:
    """Normalise a (D, C) delay table to the hashable tuple-of-tuples the
    jitted kernel takes as a static argument."""
    arr = np.asarray(delays)
    if arr.ndim != 2:
        raise ValueError(
            f"delays must be a (n_dm, nchan) table, got shape {arr.shape}")
    if not np.issubdtype(arr.dtype, np.integer):
        raise ValueError(
            f"delays must be integer samples, got dtype {arr.dtype}; round "
            f"with FilterbankSpec.delay_samples / DispersionPlan")
    return tuple(tuple(int(d) for d in row) for row in arr)


def dedisperse_kernel(fb: jax.Array, delays, *,
                      interpret: bool | None = None) -> jax.Array:
    """(..., C, N) filterbanks -> (..., D, N) dedispersed time series.

    ``delays`` is a (D, C) integer-sample table (rows = DM trials); it is
    host-side and static (checked against the block length here, then
    handed to the kernel as scalar-prefetch data).
    """
    if interpret is None:
        interpret = use_interpret()
    static = (_as_static_delays(delays)
              if not isinstance(delays, tuple) else delays)
    # A ValueError, not an assert: asserts vanish under ``python -O`` and
    # these guard caller input, not internal invariants.
    if getattr(fb, "ndim", 0) < 2:
        raise ValueError(
            f"dedisperse_kernel needs (..., nchan, ntime) input, got shape "
            f"{getattr(fb, 'shape', None)}")
    if jnp.issubdtype(jnp.asarray(fb).dtype, jnp.complexfloating):
        raise ValueError(
            f"filterbank data must be real, got dtype {fb.dtype}")
    fb = jnp.asarray(fb, jnp.float32)
    *lead, nchan, n = fb.shape
    if nchan == 0 or n == 0:
        raise ValueError(
            f"dedisperse_kernel needs non-empty channel/time axes, got "
            f"shape {fb.shape}")
    if static and len(static[0]) != nchan:
        raise ValueError(
            f"delay table covers {len(static[0])} channels; filterbank has "
            f"{nchan} (shape {fb.shape})")
    if not static:
        raise ValueError("delay table has no DM trials")
    b = 1
    for d in lead:
        b *= d
    x = fb.reshape(b, nchan, n)
    # VMEM holds a (tile, tile_c, rows_in, 128) channel slab plus the
    # (tile, D, rows_out, 128) output, both double-buffered; the batch
    # tile is a leading block dimension (any size).  One sublane group of
    # channels per step keeps the slab small whatever the channel count.
    ndm = len(static)
    rows_out, rows_in, rows_t = row_layout(n, max(map(max, static)))
    tile_c = SUBLANES if nchan % SUBLANES == 0 else nchan
    tile = min(batch_tile(rows_in * LANES, 4, buffers=2 * (tile_c + ndm),
                          align=1), b)
    pad = (-b) % tile
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
    out = dedisperse_pallas(x, static, tile_b=tile, tile_c=tile_c,
                            interpret=interpret)[:b]
    padded = b + pad
    # tile: the block (batch, channels, padded samples) and the time
    # tile's rows of 128 lanes, so the ledger shows which layout ran.
    record_launch("dedisperse", grid=(padded // tile, nchan // tile_c),
                  tile=(tile, tile_c, rows_in * LANES, rows_t),
                  bytes_moved=4 * padded * LANES * (nchan * rows_in
                                                    + ndm * rows_out),
                  shape=(b, nchan, n))
    return out.reshape(*lead, ndm, n)
