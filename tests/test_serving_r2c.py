"""The served R2C route at four-step lengths: results against float64
numpy, and Eq. 6 at real element bytes coalescing whole requests into
one bucketed batch."""
import numpy as np
import pytest

import jax

from repro.core.hardware import TPU_V5E
from repro.core.workloads import COMPLEX_BYTES
from repro.fft.plan import plan_for_length
from repro.kernels.fft.ops import MAX_KERNEL_N
from repro.obs import Tracer
from repro.serving import FFTService


def _real(rows, n, seed):
    return np.random.default_rng(seed).standard_normal((rows, n),
                                                       dtype=np.float32)


def _rel_l2(got, want):
    got = np.asarray(got, np.complex128)
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(want, axis=-1))


@pytest.mark.parametrize("rows,n", [(8, 2**15), (2, 2**16)])
def test_served_r2c_at_four_step_lengths_matches_numpy(rows, n):
    """Past twice the kernel length the real row is packed into n/2
    complex points, run through the four-step and split into the
    n/2 + 1-bin half spectrum, unnormalised, as ``np.fft.rfft`` gives it.

    Tolerance: float32 rounding of the DFT-matrix passes and the split
    leaves a relative L2 error of a few float32 epsilons (1.2e-7) times
    the passes: about 3e-7 here.  1e-6 leaves three times that, and fails
    a matmul pass at bfloat16 (~4e-3) or one wrong bin (~sqrt(2 / n),
    at least 5e-3)."""
    assert n // 2 > MAX_KERNEL_N
    assert plan_for_length(n, "r2c").algorithm == "four-step"
    svc = FFTService(TPU_V5E, devices=jax.devices()[:1])
    payloads = [_real(rows, n, seed) for seed in (1, 2)]
    reqs = [svc.submit(p, transform="r2c") for p in payloads]
    svc.drain()
    for req, p in zip(reqs, payloads):
        got = np.asarray(svc.receipt(req).result)
        assert got.shape == (rows, n // 2 + 1)
        want = np.fft.rfft(p.astype(np.float64), axis=-1)
        assert _rel_l2(got, want).max() < 1e-6


@pytest.mark.parametrize("transform,batches", [("r2c", 1), ("c2c", 2)])
def test_eq6_at_real_bytes_coalesces_four_requests_into_one_batch(
        transform, batches):
    """The cell's drain, scaled down: four 64-row requests under a budget
    that Eq. 6 turns into 476 real transforms, a bucket of 256, serve as
    one 256-row batch; at complex element bytes the same budget holds
    238 transforms, a bucket of 128, and the drain splits in two."""
    n = 256
    budget = 476 * n * COMPLEX_BYTES["fp32"] // 2
    tracer = Tracer()
    svc = FFTService(TPU_V5E, devices=jax.devices()[:1], batch_bytes=budget,
                     tracer=tracer)
    for seed in range(4):
        x = _real(64, n, seed)
        svc.submit(x if transform == "r2c" else x.astype(np.complex64),
                   transform=transform)
    svc.drain()
    spans = [s for s in tracer.spans if s.name == "batch"]
    assert len(spans) == batches
    assert [s.attrs["rows"] for s in spans] == [256 // batches] * batches
    assert all(s.attrs["transform"] == transform for s in spans)
    pads = [s.attrs["pad_rows"] for s in tracer.spans if s.name == "pad"]
    assert pads == [0] * batches
    assert svc.report().n_batches == batches
