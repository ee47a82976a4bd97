"""FFT planning — pick the algorithm and kernel route per length.

The paper leans on cuFFT's dispatch (Cooley-Tukey for smooth lengths,
Bluestein otherwise, multi-kernel plans for long transforms).  Our planner
mirrors it:

  pow2, fits one kernel   -> single fused pass (Pallas kernel: DFT-matrix
                             products on the MXU)
  pow2, long              -> four-step decomposition (two kernel passes
                             + cached twiddle)
  non-pow2                -> Bluestein (pow2 FFTs, cached chirp/filter)

plus real-valued plans (``kind="r2c"``/``"c2r"``): N real points packed
into an N/2 complex transform with a fused Hermitian split/merge — ~2x
FLOP and HBM savings for real telescope voltages.

**Routing**: every plan's power-of-two passes execute the fused Pallas
kernels (``repro.kernels.fft``) via :func:`pow2_fft`.  A kernel that
fails to lower or run raises; nothing falls back behind the caller's
back.  The pure-JAX Stockham engine runs only where it is asked for:
``REPRO_FFT_DISABLE_PALLAS=1`` or :func:`pallas_disabled`, which the
serving layer's bottom degradation rung uses and names in its receipts.
Tests monkeypatch the module-level ``_kernel_*`` hooks to count kernel
invocations.

**Tuning**: plan construction consults the active
:class:`repro.tune.TuningContext` (exactly once per (device, shape, kind)
— the context memoises) for a tuned :class:`repro.tune.KernelConfig`
overriding the batch-tile / four-step-split heuristics;
``REPRO_FFT_DISABLE_TUNING=1`` or the absence of a context restores the
heuristic plans bit-for-bit (they are the same memoised objects).

``plan.passes`` feeds the DVFS workload model (HBM traffic = 2 bytes moved
per pass), keeping the analytic model and the implementation consistent.
All twiddle/chirp constants are memoised per length (here, in
``repro.fft.radix`` and ``repro.fft.bluestein``), so planning and repeated
pipeline builds never re-materialise them; the serving layer's
``PlanSweepCache`` builds on the same memoisation.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.fft.bluestein import bluestein_fft
from repro.fft.stockham import (_as_complex, _irfft_merge, _pack_real,
                                _rfft_split, _stockham_pow2, _unpack_real)
from repro.kernels.common import LANES
from repro.tune.config import KernelConfig
from repro.tune.context import plan_config as _tuned_plan_config

# Longest transform a single fused pass keeps resident (complex64 in VMEM;
# 2^13 c64 = 64 KiB per transform — matches the paper's single-kernel range).
MAX_SINGLE_PASS = 2**13

# ---------------------------------------------------------------------------
# Pallas kernel routing (monkeypatchable hooks + env kill-switch)
# ---------------------------------------------------------------------------

from repro.kernels.fft.ops import (MAX_KERNEL_N, fft_kernel_c2c,
                                   fft_kernel_c2c_axis1, fft_kernel_c2c_mul,
                                   fft_kernel_c2c_t, fft_kernel_c2r,
                                   fft_kernel_r2c, fft_kernel_r2c_t,
                                   transpose_kernel)

_kernel_fft: Callable = fft_kernel_c2c
_kernel_rfft: Callable = fft_kernel_r2c
_kernel_irfft: Callable = fft_kernel_c2r
_kernel_fft_t: Callable = fft_kernel_c2c_t
_kernel_fft_axis1: Callable = fft_kernel_c2c_axis1
_kernel_rfft_t: Callable = fft_kernel_r2c_t
_kernel_transpose: Callable = transpose_kernel
_kernel_fft_mul: Callable = fft_kernel_c2c_mul


def _pallas_enabled() -> bool:
    return os.environ.get("REPRO_FFT_DISABLE_PALLAS", "") not in ("1", "true")


@contextlib.contextmanager
def pallas_disabled():
    """Force the pure-JAX engine inside the block (tracing included).

    The serving layer's bottom degradation rung traces its fallback
    executables under this, so they capture the ``REPRO_FFT_DISABLE_PALLAS``
    path permanently regardless of the ambient environment.
    """
    prev = os.environ.get("REPRO_FFT_DISABLE_PALLAS")
    os.environ["REPRO_FFT_DISABLE_PALLAS"] = "1"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("REPRO_FFT_DISABLE_PALLAS", None)
        else:
            os.environ["REPRO_FFT_DISABLE_PALLAS"] = prev


def _kernel_overrides(config: KernelConfig | None) -> dict:
    """Kwargs a tuned config contributes to a kernel entry-point call.

    None (heuristic) contributes nothing, so the disabled/untuned path
    issues byte-identical kernel calls to the pre-tuner code.
    """
    if config is None:
        return {}
    return {"tile_b": config.tile_b} if config.tile_b else {}


def _resolve_split(n: int, config: KernelConfig | None) -> tuple[int, int]:
    """The four-step (n1, n2) cut: the tuned one when valid, else balanced."""
    if config is not None and config.split:
        n1, n2 = config.split
        if n1 * n2 == n and _is_pow2(n1) and _is_pow2(n2):
            return n1, n2
    return _four_step_split(n)


def pow2_fft(x: jax.Array, *, inverse: bool = False,
             config: KernelConfig | None = None) -> jax.Array:
    """C2C FFT of a pow2 length, routed through the Pallas kernel.

    Single-kernel lengths run the fused DFT-matrix kernel (pure-JAX
    Stockham with Pallas disabled); longer lengths recurse through the four-step
    decomposition so *every* pow2 pass of every plan lands on the kernel.
    ``config`` (a tuned :class:`repro.tune.KernelConfig`) overrides the
    batch tile / four-step split heuristics.
    """
    n = x.shape[-1]
    if n > MAX_SINGLE_PASS:
        if inverse:
            return jnp.conj(pow2_fft(jnp.conj(x), config=config)) / n
        n1, n2 = _resolve_split(n, config)
        return four_step_fft(x, n1, n2, config=config)
    if n <= MAX_KERNEL_N and _pallas_enabled():
        return _kernel_fft(x, inverse=inverse, **_kernel_overrides(config))
    return _stockham_pow2(x, inverse=inverse)


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def fft_mul(x: jax.Array, bank,
            config: KernelConfig | None = None) -> jax.Array:
    """Forward pow2 C2C FFT fused with a (T, N) filter-bank multiply.

    (..., N) in -> (..., T, N) out: out[..., t, :] = FFT(x) * bank[t].
    The overlap-save convolution engine's forward pass: the bank multiply
    rides the FFT kernel as an in-VMEM epilogue (``fft_kernel_c2c_mul``),
    so a T-template matched-filter plane costs forward + T inverse passes
    with zero standalone multiply passes.  With Pallas disabled (the
    pure-JAX rung) it pays the routed FFT plus ONE XLA broadcast multiply —
    numerically identical, one extra HBM round trip of the plane.
    """
    x = _as_complex(x)
    n = x.shape[-1]
    if _is_pow2(n) and 1 < n <= MAX_KERNEL_N and _pallas_enabled():
        return _kernel_fft_mul(x, bank, **_kernel_overrides(config))
    y = pow2_fft(x, config=config)
    return y[..., None, :] * jnp.asarray(bank).astype(y.dtype)


# ---------------------------------------------------------------------------
# Fused-epilogue pass primitives (the plan graph's node executors)
# ---------------------------------------------------------------------------

def fft_transposed(x: jax.Array, *, twiddle=None, inverse: bool = False,
                   config: KernelConfig | None = None) -> jax.Array:
    """C2C FFT along the last axis with the last two axes swapped on write.

    One fused kernel pass: (..., R, C) -> (..., C, R).  ``twiddle`` (an
    (R, C) complex table) rides along as a kernel epilogue — the four-step
    inter-pass multiply costs zero extra HBM passes.  With Pallas
    disabled it runs routed-FFT + XLA multiply + XLA transpose
    (numerically identical, just more memory passes).
    """
    x = _as_complex(x)
    n = x.shape[-1]
    if _is_pow2(n) and 1 < n <= MAX_KERNEL_N and _pallas_enabled():
        return _kernel_fft_t(x, twiddle=twiddle, inverse=inverse,
                             **_kernel_overrides(config))
    y = _routed_1d(x, n, inverse, config)
    if twiddle is not None:
        y = y * jnp.asarray(twiddle).astype(y.dtype)
    return jnp.swapaxes(y, -1, -2)


def _routed_1d(x: jax.Array, n: int, inverse: bool,
               config: KernelConfig | None = None) -> jax.Array:
    """Last-axis C2C of any length, honouring ``inverse`` (conj trick for
    the non-pow2 plans, which only run forward)."""
    if _is_pow2(n):
        return pow2_fft(x, inverse=inverse, config=config)
    plan = plan_for_length(n)
    if inverse:
        return jnp.conj(plan(jnp.conj(x))) / n
    return plan(x)


def fft_column(x: jax.Array, *, twiddle=None, inverse: bool = False,
               config: KernelConfig | None = None) -> jax.Array:
    """C2C FFT over axis -2, layout preserved: (..., R, C) -> (..., R, C).

    One fused kernel pass (transpose-read + FFT + optional twiddle
    epilogue + transpose-write, all in VMEM) — the column pass of the
    four-step algorithm.  ``twiddle`` is a (C, R) table multiplying output
    ``[..., k, j]`` by ``twiddle[j, k]``.  With Pallas disabled it
    runs XLA transpose + routed FFT + multiply.
    """
    x = _as_complex(x)
    r = x.shape[-2]
    if _is_pow2(r) and 1 < r <= MAX_KERNEL_N and _pallas_enabled():
        return _kernel_fft_axis1(x, twiddle=twiddle, inverse=inverse,
                                 **_kernel_overrides(config))
    y = _routed_1d(jnp.swapaxes(x, -1, -2), r, inverse, config)
    if twiddle is not None:
        y = y * jnp.asarray(twiddle).astype(y.dtype)
    return jnp.swapaxes(y, -1, -2)


def rfft_transposed(x: jax.Array,
                    config: KernelConfig | None = None) -> jax.Array:
    """R2C FFT along the last axis, transposed write: (..., R, C) real ->
    (..., C/2+1, R) — one fused pass (pack + half-length FFT + Hermitian
    split + transpose all in VMEM)."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.real
    n = x.shape[-1]
    if (_is_pow2(n) and 4 <= n and n // 2 <= MAX_KERNEL_N
            and _pallas_enabled()):
        return _kernel_rfft_t(x, **_kernel_overrides(config))
    return jnp.swapaxes(plan_with_config(n, "r2c", config)(x), -1, -2)


def tiled_transpose(x: jax.Array) -> jax.Array:
    """Swap the last two axes in one tiled kernel pass (read row tiles,
    write column tiles); an XLA transpose with Pallas disabled."""
    if _pallas_enabled():
        return _kernel_transpose(x)
    return jnp.swapaxes(x, -1, -2)


def _four_step_split(n: int) -> tuple[int, int]:
    n1 = 1 << (int(math.log2(n)) // 2)
    return n1, n // n1


def _dft_products(n: int) -> int:
    """DFT-matrix products one fused kernel pass spends on a length-``n``
    transform: a single (n, n) product up to 128 points, else the two of
    its in-VMEM N1 x 128 four-step (``repro.kernels.fft.fft_kernel``)."""
    return 1 if n <= LANES else 2


@dataclasses.dataclass(frozen=True)
class FFTPlan:
    n: int
    algorithm: str              # "single-pass" | "four-step" | "bluestein"
    passes: int                 # HBM read+write passes (DVFS model input)
    fn: Callable[[jax.Array], jax.Array]
    kind: str = "c2c"           # "c2c" | "r2c" | "c2r"
    stages: int = 0             # kernel DFT-matrix products per transform

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.fn(x)


@functools.lru_cache(maxsize=None)
def _four_step_twiddle(n1: int, n2: int) -> np.ndarray:
    """The (n2, n1) inter-pass twiddle matrix, materialised once per shape.

    complex128 so the x64 path keeps full precision; consumers cast to the
    working dtype at trace time.
    """
    j = np.arange(n2)[:, None]
    k = np.arange(n1)[None, :]
    return np.exp(-2j * np.pi * (j * k) / (n1 * n2))


def four_step_fft(x: jax.Array, n1: int, n2: int,
                  config: KernelConfig | None = None) -> jax.Array:
    """Long FFT as (n1 x n2) decomposition — Bailey's four-step algorithm,
    run as TWO fused kernel passes.

    View x as v[j1, j2] (row-major).  With outputs indexed k = k2*n1 + k1:

      pass 1: FFT the columns (length n1, axis -2, transpose-read in
              VMEM) -> V[k1, j2]; multiply the inter-pass twiddle
              exp(-2*pi*i*j2*k1/n) as a kernel epilogue; write back in
              the same layout -> T[k1, j2]
      pass 2: FFT the rows of T (length n2) -> Y[k1, k2]; write
              transposed -> out[k2, k1], which flattens to natural order.

    The unfused formulation costs kernel + XLA-twiddle + three XLA
    transposes (five HBM round trips of the batch); the fused pair costs
    exactly two.  Both passes route through the Pallas kernels
    (:func:`fft_column`, :func:`fft_transposed`); with Pallas disabled
    they run routed :func:`pow2_fft` + XLA ops.  The
    distributed version (repro.fft.distributed) turns the transpose into
    an all_to_all across the mesh — cuFFT's multi-kernel plan, TPU-style.
    """
    n = n1 * n2
    assert x.shape[-1] == n
    batch = x.shape[:-1]
    v = x.reshape(*batch, n1, n2)
    tw = _four_step_twiddle(n1, n2)              # (n2, n1): w^{j2*k1}
    v = fft_column(v, twiddle=tw, config=config)  # (..., n1, n2): T[k1, j2]
    v = fft_transposed(v, config=config)         # (..., n2, n1), natural
    return v.reshape(*batch, n)


# ---------------------------------------------------------------------------
# Plan construction
# ---------------------------------------------------------------------------

def _c2c_fn(x: jax.Array,
            config: KernelConfig | None = None) -> jax.Array:
    return pow2_fft(_as_complex(x), config=config)


def _r2c_fn(x: jax.Array, n: int,
            config: KernelConfig | None = None) -> jax.Array:
    """Routed R2C: fused kernel when the packed length fits, else pack ->
    routed pow2 C2C -> split (so long real transforms still hit the kernel
    once per four-step pass)."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.real
    m = n // 2
    if 4 <= n and m <= MAX_KERNEL_N and _pallas_enabled():
        return _kernel_rfft(x, **_kernel_overrides(config))
    if m < 1:
        return _as_complex(x)
    return _rfft_split(
        pow2_fft(_pack_real(x.astype(jnp.float32)), config=config), n)


def _c2r_fn(x: jax.Array, n: int,
            config: KernelConfig | None = None) -> jax.Array:
    """Routed C2R inverse of :func:`_r2c_fn` (1/N normalised)."""
    x = _as_complex(x)
    m = n // 2
    if 4 <= n and m <= MAX_KERNEL_N and _pallas_enabled():
        return _kernel_irfft(x, **_kernel_overrides(config))
    return _unpack_real(
        pow2_fft(_irfft_merge(x, n), inverse=True, config=config))


def plan_for_length(n: int, kind: str = "c2c") -> FFTPlan:
    """Build (or return the memoised) plan for length ``n``.

    ``kind`` selects the transform: ``"c2c"`` (default), ``"r2c"`` (real
    input, N/2+1 bins out) or ``"c2r"`` (the inverse).  Plans are immutable
    and shape-keyed, so planning runs once per (length, kind, config) per
    process — the serving layer's plan cache builds on this, and repeated
    pipeline construction never re-derives the decomposition or twiddles.

    The active :class:`repro.tune.TuningContext` (if any) supplies the
    tuned kernel config; it memoises its own lookups, so the tuning cache
    is consulted exactly once per (device, shape, kind) no matter how
    often plans rebuild.  ``REPRO_FFT_DISABLE_TUNING=1`` (or no context)
    resolves to ``None`` — the pre-tuner heuristic plan object itself.
    """
    return _plan_for_length(int(n), kind, _tuned_plan_config((n,), kind))


def plan_with_config(n: int, kind: str = "c2c",
                     config: KernelConfig | None = None) -> FFTPlan:
    """Build the plan for an *explicit* config, bypassing the active
    tuning context (the autotuner's measurement loop, plan_nd threading).
    A heuristic-equivalent config collapses onto the heuristic plan."""
    if config is not None and config.is_heuristic:
        config = None
    return _plan_for_length(int(n), kind, config)


@functools.lru_cache(maxsize=None)
def _plan_for_length(n: int, kind: str,
                     config: KernelConfig | None) -> FFTPlan:
    if kind not in ("c2c", "r2c", "c2r"):
        raise ValueError(f"unknown transform kind {kind!r}")
    if kind != "c2c":
        return _real_plan(n, kind, config)
    if _is_pow2(n):
        if n <= MAX_SINGLE_PASS:
            return FFTPlan(n, "single-pass", 1,
                           functools.partial(_c2c_fn, config=config),
                           stages=_dft_products(n))
        n1, n2 = _resolve_split(n, config)
        return FFTPlan(
            n, "four-step", 2,
            lambda x, n1=n1, n2=n2, c=config: four_step_fft(
                _as_complex(x), n1, n2, config=c),
            stages=_dft_products(n1) + _dft_products(n2),
        )
    # Bluestein: the filter-spectrum FFT is precomputed and cached per
    # length (repro.fft.bluestein), so only 2 pow2 FFTs of length
    # m >= 2n-1 run per call, plus pointwise chirp passes.  The config
    # rides into those inner FFTs (the heuristic path keeps the bare
    # bluestein_fft object so disabled tuning stays bit-for-bit).
    m = 1 << (2 * n - 2).bit_length()
    inner = _plan_for_length(m, "c2c", config)
    fn = (bluestein_fft if config is None
          else functools.partial(bluestein_fft, config=config))
    return FFTPlan(n, "bluestein", 2 * inner.passes + 1, fn,
                   stages=inner.stages)


def _real_plan(n: int, kind: str, config: KernelConfig | None) -> FFTPlan:
    if not _is_pow2(n):
        if kind == "c2r":
            raise ValueError(
                f"c2r plans need a power-of-two length, got {n}")
        # r2c fallback: full C2C plan + slice to the half spectrum.
        inner = _plan_for_length(n, "c2c", config)
        return FFTPlan(
            n, inner.algorithm, inner.passes,
            lambda x: inner.fn(_as_complex(x))[..., :n // 2 + 1],
            kind="r2c", stages=inner.stages)
    m = max(n // 2, 1)
    inner = _plan_for_length(m, "c2c", config) if m > 1 else None
    passes = inner.passes if inner else 1
    alg = inner.algorithm if inner else "single-pass"
    # Up to 2 * MAX_KERNEL_N points the real kernels transform the n
    # reals directly; longer rows pack into the m-point complex plan.
    stages = (_dft_products(n) if 4 <= n and m <= MAX_KERNEL_N
              else inner.stages if inner else 0)
    fn = (functools.partial(_r2c_fn, n=n, config=config) if kind == "r2c"
          else functools.partial(_c2r_fn, n=n, config=config))
    return FFTPlan(n, alg, passes, fn, kind=kind, stages=stages)
