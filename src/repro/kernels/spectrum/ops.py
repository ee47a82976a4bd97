"""Public wrapper for the fused spectrum kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import batch_tile, rows_tile, use_interpret
from repro.kernels.spectrum.spectrum_kernel import power_spectrum_stats_pallas
from repro.obs.ledger import record_launch


def power_spectrum_stats_kernel(x: jax.Array, *,
                                interpret: bool | None = None):
    """Complex spectra (..., N) -> (power (..., N), mean (...,), std (...,))."""
    if interpret is None:
        interpret = use_interpret()
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    lead, n = x.shape[:-1], x.shape[-1]
    if n == 0:
        raise ValueError("power_spectrum_stats_kernel needs a non-empty "
                         f"trailing axis, got shape {x.shape}")
    b = 1
    for d in lead:
        b *= d
    re = x.real.reshape(b, n).astype(jnp.float32)
    im = x.imag.reshape(b, n).astype(jnp.float32)
    tile, rows = rows_tile(b, batch_tile(n, 4, buffers=6))
    if rows > b:
        re = jnp.pad(re, ((0, rows - b), (0, 0)))
        im = jnp.pad(im, ((0, rows - b), (0, 0)))
    p, mean, var = power_spectrum_stats_pallas(re, im, tile_b=tile,
                                               interpret=interpret)
    record_launch("power-spectrum-stats", grid=(re.shape[0] // tile,),
                  tile=(tile, n),
                  bytes_moved=4 * re.shape[0] * (3 * n + 2),
                  shape=(b, n))
    std = jnp.sqrt(jnp.maximum(var, 0.0))
    return (p[:b].reshape(*lead, n), mean[:b, 0].reshape(lead),
            std[:b, 0].reshape(lead))
