"""Distributed FFTs over a device mesh (shard_map + collectives).

Two parallel regimes, matching how the paper's workload scales out:

Pallas kernels run inside ``shard_map`` bodies, whose outputs carry no
varying-axis annotation, so every ``shard_map`` here passes
``check_vma=False``.

* **Batch parallel** (:func:`batch_parallel_fft`) — the paper's own setting:
  many independent transforms, sharded over the ``data`` axis.  No
  communication at all; this is why the paper can say "FFTs which fit into
  GPU memory can be easily distributed amongst the GPUs" (Sec. 2.3).

* **Pencil / four-step** (:func:`pencil_fft`) — one transform too long for
  a device (the SKA long_500k class): view N = n1 * n2, shard n1 across the
  ``model`` axis, and turn the four-step algorithm's transpose into
  ``jax.lax.all_to_all``.  This is the TPU-native analogue of cuFFT's
  multi-kernel long plans, and the piece whose collective term shows up in
  the roofline analysis.

The output of :func:`pencil_fft` is in *transposed* layout — element
``[k1, k2]`` of the local (n1_local, n2) block holds bin ``k2 * n1 + k1``
(FFTW's MPI transposed-output convention).  Use :func:`untranspose_ref`
on gathered results when validating.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pad_rows(x: jax.Array, rows: int) -> jax.Array:
    """Zero-pad the leading (batch) dimension up to ``rows``."""
    pad = rows - x.shape[0]
    if pad <= 0:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], axis=0)


def batch_parallel_fft(x: jax.Array, mesh: Mesh, *, axis: str = "data",
                       fft_fn=None, kind: str = "c2c") -> jax.Array:
    """Batched FFT with the batch dimension sharded over ``axis``.

    Batches that do not divide the axis size are zero-padded to the next
    multiple, transformed, and sliced back — the serving layer coalesces
    requests into arbitrary batch sizes, so divisibility cannot be assumed.
    A divided batch comes back sharded over ``axis``; a padded one comes
    back replicated, because its row count cannot be split evenly over
    the devices (an all-gather of the batch).  Meshes with ``Auto`` and
    with ``Explicit`` axes take the same path.

    ``kind="r2c"`` routes real-input batches through the R2C plan (half
    the FLOPs and HBM traffic per shard) instead of silently casting to
    complex; N-D payloads (rank > 2) route through the plan graph
    (:mod:`repro.fft.plan_nd`), so sharded 2-D transforms get the fused
    transpose-write passes too.
    """
    if fft_fn is None:
        if x.ndim > 2:
            from repro.fft.plan_nd import plan_nd
            fft_fn = plan_nd(tuple(x.shape[1:]), kind)
        else:
            from repro.fft.plan import plan_for_length
            fft_fn = plan_for_length(x.shape[-1], kind)
    d = mesh.shape[axis]
    b = x.shape[0]
    spec = P(axis, *([None] * (x.ndim - 1)))
    x = jax.device_put(pad_rows(x, b + (-b) % d), NamedSharding(mesh, spec))
    fn = shard_map(lambda v: fft_fn(v), mesh=mesh, in_specs=(spec,),
                   out_specs=spec, check_vma=False)
    out = fn(x)
    if out.shape[0] == b:
        return out
    # Replicate, then cut: XLA picks the same replicated result for an
    # Auto mesh, and on Explicit axes a sharded array cannot be sliced to
    # a row count the axis does not divide.
    return jax.device_put(out, NamedSharding(mesh, P()))[:b]


@functools.partial(jax.jit, static_argnames=("n1", "n2", "axis", "mesh"))
def _pencil_body(x, *, n1, n2, axis, mesh):
    from repro.fft.plan import pow2_fft

    def local(v):                           # v: (batch, n1/D, n2)
        d = jax.lax.psum(1, axis)
        p = jax.lax.axis_index(axis)
        # ---- transpose 1: gather full n1, scatter n2 -------------------
        v = jax.lax.all_to_all(v, axis, split_axis=2, concat_axis=1,
                               tiled=True)      # (batch, n1, n2/D)
        # ---- FFT over n1 (plan-graph routed: Pallas when available) ----
        v = jnp.swapaxes(v, -1, -2)             # (batch, n2/D, n1)
        v = pow2_fft(v)
        # ---- twiddle: exp(-2*pi*i*j*k/n), j = global n2 index ----------
        n = n1 * n2
        j_local = jnp.arange(n2 // d) + p * (n2 // d)
        k = jnp.arange(n1)
        tw = jnp.exp(-2j * jnp.pi * (j_local[:, None] * k[None, :]) / n)
        v = v * tw.astype(v.dtype)
        v = jnp.swapaxes(v, -1, -2)             # (batch, n1, n2/D)
        # ---- transpose 2: back to n1-sharded ---------------------------
        v = jax.lax.all_to_all(v, axis, split_axis=1, concat_axis=2,
                               tiled=True)      # (batch, n1/D, n2)
        # ---- FFT over n2 ------------------------------------------------
        v = pow2_fft(v)                         # rows are contiguous
        return v

    spec = P(None, axis, None)
    return shard_map(local, mesh=mesh, in_specs=(spec,), out_specs=spec,
                     check_vma=False)(x)


@functools.partial(jax.jit, static_argnames=("n1", "n2p", "axis", "mesh"))
def _pencil_split_body(z, *, n1, n2p, axis, mesh):
    """Distributed Hermitian split of a packed-pencil result.

    ``z``: the transposed-layout C2C pencil transform of the *packed*
    real signal — (batch, n1/D, n2p) sharded P(None, axis, None), where
    element [k1, k2] holds Z[k2*n1 + k1] of the length M = n1*n2p packed
    transform.  The split needs Z[(M-k) mod M]: a global index reversal,
    realised as local flips plus a shard-reversing ``ppermute`` and a
    one-row global roll — O(local block) interconnect, no gather.
    """
    d = mesh.shape[axis]
    m = n1 * n2p

    def local(zt):                              # zt: (batch, L, n2p)
        p = jax.lax.axis_index(axis)
        l = zt.shape[-2]
        rows = p * l + jnp.arange(l)            # global k1 of each row
        # ---- G[k1] = Z row (n1 - k1) mod n1: reverse + roll by one -----
        rev = jax.lax.ppermute(zt[:, ::-1, :], axis,
                               perm=[(q, d - 1 - q) for q in range(d)])
        last = jax.lax.ppermute(rev[:, -1:, :], axis,
                                perm=[(q, (q + 1) % d) for q in range(d)])
        g = jnp.concatenate([last, rev[:, :-1, :]], axis=-2)
        # ---- k2 mirror: flip, with an extra roll on the k1 == 0 row ----
        flip = g[..., ::-1]
        rolled = jnp.roll(flip, 1, axis=-1)
        g = jnp.where((rows == 0)[None, :, None], rolled, flip)
        zm = jnp.conj(g)                        # Z[(M - k) mod M]*
        # ---- split: X[k] = (Z+Zm)/2 - i/2 * w^k * (Z-Zm) ---------------
        k = (jnp.arange(n2p)[None, :] * n1 + rows[:, None])   # (L, n2p)
        w = jnp.exp(-1j * jnp.pi * k / m)       # w_N^k, N = 2M
        x = 0.5 * (zt + zm) - 0.5j * w.astype(zt.dtype) * (zt - zm)
        # ---- Nyquist bin X[M] = Re(Z[0]) - Im(Z[0]), shard 0 row 0 -----
        z0 = zt[:, :1, :1]
        nyq = (z0.real - z0.imag).astype(zt.dtype)
        col = jnp.where((rows == 0)[None, :, None],
                        jnp.broadcast_to(nyq, (zt.shape[0], l, 1)), 0.0)
        return jnp.concatenate([x, col], axis=-1)

    spec = P(None, axis, None)
    return shard_map(local, mesh=mesh, in_specs=(spec,), out_specs=spec,
                     check_vma=False)(z)


def pencil_fft(x: jax.Array, mesh: Mesh, *, n1: int, n2: int,
               axis: str = "model", kind: str = "c2c") -> jax.Array:
    """Four-step FFT of length n1*n2 with n1 sharded over ``axis``.

    ``x``: (batch, n1, n2), sharded P(None, axis, None).

    ``kind="c2c"`` (default) returns the transform in transposed layout
    (see module docstring).  ``kind="r2c"`` takes REAL input and runs the
    packed real algorithm end to end distributed: adjacent reals pack
    into a length-M = n1*n2/2 complex pencil (HALF the FFT FLOPs, HBM
    traffic and all_to_all payload of the complex path), then the
    Hermitian split runs sharded — the spectral mirror Z[(M-k) mod M] is
    one shard-reversing ppermute plus a one-row roll, not a gather.  The
    result is (batch, n1/D-sharded n1, n2/2+1): element [k1, k2] holds
    half-spectrum bin X[k2*n1 + k1] for k2 < n2/2 (packed transposed
    layout), and the final column holds the Nyquist bin X[M] in row
    k1 = 0 (zeros elsewhere).  :func:`assemble_rfft_pencil` reorders a
    gathered result into ``jnp.fft.rfft`` natural order for validation.
    ``n2/2`` must divide evenly over the mesh axis.
    """
    assert x.shape[-2:] == (n1, n2), (x.shape, n1, n2)
    if kind == "r2c":
        d = mesh.shape[axis]
        if n2 % 2:
            raise ValueError(
                f"pencil r2c packs adjacent reals: n2 must be even, got {n2}")
        if (n2 // 2) % d:
            raise ValueError(
                f"pencil r2c needs n2/2 ({n2 // 2}) divisible by the "
                f"{d}-device mesh axis {axis!r}")
        batch = x.shape[:-2]
        v = jnp.real(x).astype(jnp.float32)
        v = v.reshape(*batch, n1, n2 // 2, 2)
        z = jax.lax.complex(v[..., 0], v[..., 1])     # packed rows
        z = _pencil_body(z, n1=n1, n2=n2 // 2, axis=axis, mesh=mesh)
        return _pencil_split_body(z, n1=n1, n2p=n2 // 2, axis=axis,
                                  mesh=mesh)
    if kind != "c2c":
        raise ValueError(f"unknown pencil transform kind {kind!r}")
    return _pencil_body(x, n1=n1, n2=n2, axis=axis, mesh=mesh)


def untranspose_ref(y: jax.Array, n1: int, n2: int) -> jax.Array:
    """Reorder a gathered transposed-layout result into natural order."""
    batch = y.shape[:-2]
    # y[k1, k2] holds bin k2*n1+k1  ->  natural[k] with k = k2*n1+k1
    return jnp.swapaxes(y, -1, -2).reshape(*batch, n1 * n2)


def assemble_rfft_pencil(y, n1: int, n2: int):
    """Reconstruct ``jnp.fft.rfft`` natural order from a gathered r2c
    pencil result (validation helper, host-side numpy).

    ``y``: (..., n1, n2/2+1) from ``pencil_fft(..., kind="r2c")`` —
    element [k1, k2] is half-spectrum bin X[k2*n1 + k1] for k2 < n2/2;
    the final column carries the Nyquist bin X[n1*n2/2] in row 0.
    """
    import numpy as np
    y = np.asarray(y)
    m = n1 * n2 // 2
    k = np.arange(m)
    k2, k1 = np.divmod(k, n1)
    body = y[..., k1, k2]
    nyq = y[..., 0:1, n2 // 2]
    return np.concatenate([body, nyq], axis=-1)


def pencil_collective_bytes(batch: int, n1: int, n2: int,
                            n_devices: int, elem_bytes: int = 8,
                            kind: str = "c2c") -> float:
    """Analytic all_to_all traffic per device for the DVFS/roofline model.

    C2C: two all_to_alls; each moves the device's local block (minus the
    diagonal chunk that stays put): (D-1)/D of batch*n1*n2/D elements.
    R2C: the same two all_to_alls on the HALF-length packed transform,
    plus the Hermitian-split mirror ppermute (one half-size local block)
    — ~70% of the c2c traffic on top of half the FLOPs and HBM passes.
    """
    local = batch * n1 * n2 / n_devices * elem_bytes
    if kind == "r2c":
        packed = local / 2.0
        return (2.0 * packed + packed) * (n_devices - 1) / n_devices
    return 2.0 * local * (n_devices - 1) / n_devices
