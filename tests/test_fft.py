"""FFT substrate correctness: Stockham/Bluestein/four-step vs jnp.fft."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:          # optional test dep: skip property tests
    from _hyp import given, settings, st

from repro.fft import (bluestein_fft, fft, fft2, ifft, irfft,
                       plan_for_length, rfft, rfft2)
from repro.fft import plan as plan_mod
from repro.fft.plan import four_step_fft
from repro.fft.pipeline import (PipelineShape, candidate_snr, harmonic_sum,
                                power_spectrum, pulsar_pipeline,
                                spectrum_stats, stage_profiles)
from repro.fft.radix import radix_schedule, stage_count
from repro.fft.stockham import _stockham_pow2

KEY = jax.random.PRNGKey(0)


def rand_complex(shape, key=KEY, dtype=jnp.complex64):
    kr, ki = jax.random.split(key)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(dtype)


@pytest.mark.parametrize("n", [2, 4, 8, 32, 128, 1024, 4096])
@pytest.mark.parametrize("batch", [(), (3,), (2, 5)])
def test_stockham_matches_reference(n, batch):
    x = rand_complex((*batch, n))
    np.testing.assert_allclose(fft(x), jnp.fft.fft(x), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", [8, 256, 2048])
def test_ifft_inverts(n):
    x = rand_complex((4, n))
    np.testing.assert_allclose(ifft(fft(x)), x, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", [3, 12, 100, 139, 139 * 139 // 139, 2187, 2401])
def test_bluestein_matches_reference(n):
    x = rand_complex((2, n))
    np.testing.assert_allclose(bluestein_fft(x), jnp.fft.fft(x),
                               rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("n1,n2", [(4, 8), (32, 32), (64, 128)])
def test_four_step_matches_reference(n1, n2):
    x = rand_complex((2, n1 * n2))
    np.testing.assert_allclose(four_step_fft(x, n1, n2), jnp.fft.fft(x),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("n", [64, 8192, 2**15, 139, 100])
def test_planner_dispatch_and_correctness(n):
    plan = plan_for_length(n)
    expected = {True: "single-pass" if n <= 2**13 else "four-step",
                False: "bluestein"}[(n & (n - 1)) == 0]
    assert plan.algorithm == expected
    assert plan.passes >= 1
    x = rand_complex((2, n))
    np.testing.assert_allclose(plan(x), jnp.fft.fft(x), rtol=3e-3, atol=3e-3)


def test_fft2_matches_reference():
    x = rand_complex((3, 16, 32))
    np.testing.assert_allclose(fft2(x), jnp.fft.fft2(x), rtol=3e-4, atol=3e-4)


def test_fft_axis_argument():
    x = rand_complex((8, 5))
    np.testing.assert_allclose(fft(x, axis=0), jnp.fft.fft(x, axis=0),
                               rtol=2e-4, atol=2e-4)


def test_float64_precision_path():
    with jax.enable_x64(True):
        x = rand_complex((2, 512), dtype=jnp.complex128)
        np.testing.assert_allclose(fft(x), jnp.fft.fft(x), rtol=1e-10)


# ---------------------------------------------------------------------------
# Mixed-radix engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("radices", [(2,), (4, 2), (8, 4, 2)])
@pytest.mark.parametrize("n", [2, 8, 64, 1024, 4096])
def test_mixed_radix_parity(n, radices):
    """Every radix schedule computes the same transform as jnp.fft."""
    x = rand_complex((3, n))
    got = _stockham_pow2(x, radices=radices)
    np.testing.assert_allclose(got, jnp.fft.fft(x), rtol=3e-4, atol=3e-4)
    gi = _stockham_pow2(x, inverse=True, radices=radices)
    np.testing.assert_allclose(gi, jnp.fft.ifft(x), rtol=3e-4, atol=3e-4)


def test_radix_schedule_structure():
    assert radix_schedule(4096) == (4,) * 6
    # The residual radix-2 stage runs first, at full butterfly width.
    assert radix_schedule(2048) == (2,) + (4,) * 5
    assert stage_count(4096, (2,)) == 12
    assert stage_count(4096, (4, 2)) == 6
    assert stage_count(4096, (8, 4, 2)) == 4
    with pytest.raises(ValueError):
        radix_schedule(12, (4,))          # 3 is not expressible in radix 4


def test_mixed_radix_halves_stage_count():
    """The tentpole claim: >= 1.3x fewer stages than radix-2 at N=2^12."""
    assert stage_count(2**12, (2,)) / stage_count(2**12, (4, 2)) >= 1.3


# ---------------------------------------------------------------------------
# R2C / C2R real transforms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 4, 8, 64, 1024, 4096])
@pytest.mark.parametrize("batch", [(), (3,), (2, 5)])
def test_rfft_matches_reference(n, batch):
    x = jax.random.normal(KEY, (*batch, n))
    np.testing.assert_allclose(rfft(x), jnp.fft.rfft(x),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("n", [4, 256, 2048])
def test_irfft_inverts_rfft(n):
    x = jax.random.normal(KEY, (4, n))
    np.testing.assert_allclose(irfft(rfft(x)), x, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(irfft(jnp.fft.rfft(x)),
                               jnp.fft.irfft(jnp.fft.rfft(x)),
                               rtol=2e-4, atol=2e-4)


def test_rfft_float64_precision_path():
    with jax.enable_x64(True):
        x = jax.random.normal(KEY, (2, 512), dtype=jnp.float64)
        np.testing.assert_allclose(rfft(x), jnp.fft.rfft(x), rtol=1e-10)
        np.testing.assert_allclose(irfft(rfft(x)), x, rtol=1e-10)


def test_rfft_axis_argument():
    x = jax.random.normal(KEY, (16, 5))
    np.testing.assert_allclose(rfft(x, axis=0), jnp.fft.rfft(x, axis=0),
                               rtol=2e-4, atol=2e-4)


def test_rfft2_matches_reference():
    x = jax.random.normal(KEY, (3, 16, 32))
    np.testing.assert_allclose(rfft2(x), jnp.fft.rfft2(x),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("n", [64, 4096, 2**15, 100])
def test_plan_r2c_all_algorithms(n):
    """R2C plans: kernel route, four-step route, and non-pow2 fallback."""
    x = jax.random.normal(KEY, (2, n))
    plan = plan_for_length(n, "r2c")
    assert plan.kind == "r2c"
    np.testing.assert_allclose(plan(x), jnp.fft.rfft(x),
                               rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("n", [64, 4096, 2**15])
def test_plan_c2r_roundtrip(n):
    x = jax.random.normal(KEY, (2, n))
    X = plan_for_length(n, "r2c")(x)
    back = plan_for_length(n, "c2r")(X)
    np.testing.assert_allclose(back, x, rtol=3e-3, atol=3e-3)


def test_plan_c2r_rejects_non_pow2():
    with pytest.raises(ValueError):
        plan_for_length(60, "c2r")
    with pytest.raises(ValueError):
        plan_for_length(64, "hartley")


# ---------------------------------------------------------------------------
# Kernel routing: every plan's pow2 passes execute the Pallas kernel
# ---------------------------------------------------------------------------

class _CountingKernel:
    def __init__(self, inner):
        self.inner = inner
        self.calls = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self.inner(*args, **kwargs)


@pytest.mark.parametrize("n,algorithm", [
    (2**9, "single-pass"),    # single fused pass
    (45, "bluestein"),        # two kernel passes at m=128
])
def test_plans_route_through_pallas_kernel(monkeypatch, n, algorithm):
    """Acceptance: each algorithm path demonstrably runs the kernel.

    Jitted paths (bluestein) execute the router at trace time, so each
    case uses a batch shape unique to this test to force a fresh trace.
    """
    counter = _CountingKernel(plan_mod.fft_kernel_c2c)
    monkeypatch.setattr(plan_mod, "_kernel_fft", counter)
    plan = plan_for_length(n)
    assert plan.algorithm == algorithm
    x = rand_complex((7, n))
    np.testing.assert_allclose(plan(x), jnp.fft.fft(x), rtol=3e-3, atol=3e-3)
    assert counter.calls >= (2 if algorithm != "single-pass" else 1)


def test_four_step_plan_runs_two_fused_kernel_passes(monkeypatch):
    """Acceptance: the long-N plan is exactly TWO fused kernel passes —
    column FFT + twiddle epilogue, then row FFT + transposed write.  No
    plain kernel launches, no separate twiddle / transpose ops."""
    col = _CountingKernel(plan_mod.fft_kernel_c2c_axis1)
    row = _CountingKernel(plan_mod.fft_kernel_c2c_t)
    plain = _CountingKernel(plan_mod.fft_kernel_c2c)
    monkeypatch.setattr(plan_mod, "_kernel_fft_axis1", col)
    monkeypatch.setattr(plan_mod, "_kernel_fft_t", row)
    monkeypatch.setattr(plan_mod, "_kernel_fft", plain)
    n = 2**14
    plan = plan_for_length(n)
    assert plan.algorithm == "four-step"
    assert plan.passes == 2
    x = rand_complex((3, n))
    np.testing.assert_allclose(plan(x), jnp.fft.fft(x), rtol=3e-3, atol=3e-3)
    assert col.calls == 1 and row.calls == 1
    assert plain.calls == 0          # no hidden unfused passes


def test_r2c_plan_routes_through_pallas_kernel(monkeypatch):
    counter = _CountingKernel(plan_mod.fft_kernel_r2c)
    monkeypatch.setattr(plan_mod, "_kernel_rfft", counter)
    x = jax.random.normal(KEY, (7, 2**9))
    plan = plan_for_length(2**9, "r2c")
    np.testing.assert_allclose(plan(x), jnp.fft.rfft(x), rtol=3e-3, atol=3e-3)
    assert counter.calls == 1


@pytest.mark.parametrize("n", [2**9, 2**14, 45])
def test_plans_fall_back_without_pallas(monkeypatch, n):
    """With Pallas disabled (the serving ladder's pure-JAX rung) every
    plan stays correct and reaches no kernel hook."""
    for hook in ("_kernel_fft", "_kernel_rfft", "_kernel_irfft",
                 "_kernel_fft_t", "_kernel_fft_axis1", "_kernel_rfft_t",
                 "_kernel_transpose"):
        monkeypatch.setattr(plan_mod, hook, _no_kernel)
    x = rand_complex((5, n))
    with plan_mod.pallas_disabled():
        np.testing.assert_allclose(plan_for_length(n)(x), jnp.fft.fft(x),
                                   rtol=3e-3, atol=3e-3)


def _no_kernel(*args, **kwargs):
    raise AssertionError("a Pallas kernel ran with Pallas disabled")


def test_pallas_disable_env_skips_kernel(monkeypatch):
    monkeypatch.setenv("REPRO_FFT_DISABLE_PALLAS", "1")
    counter = _CountingKernel(plan_mod.fft_kernel_c2c)
    monkeypatch.setattr(plan_mod, "_kernel_fft", counter)
    x = rand_complex((6, 2**9))
    np.testing.assert_allclose(plan_mod.pow2_fft(x), jnp.fft.fft(x),
                               rtol=3e-4, atol=3e-4)
    assert counter.calls == 0


def test_broken_kernel_falls_back_gracefully(monkeypatch):
    """A failing kernel raises to the caller: no silent retreat to the
    pure-JAX engine, which runs only when asked for (the serving
    ladder's bottom rung, via ``pallas_disabled``)."""
    def boom(*args, **kwargs):
        raise RuntimeError("no Pallas backend")
    monkeypatch.setattr(plan_mod, "_kernel_fft", boom)
    x = rand_complex((4, 2**9))
    with pytest.raises(RuntimeError, match="no Pallas backend"):
        plan_mod.pow2_fft(x)
    with plan_mod.pallas_disabled():
        np.testing.assert_allclose(plan_mod.pow2_fft(x), jnp.fft.fft(x),
                                   rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# Twiddle / chirp caching
# ---------------------------------------------------------------------------

def test_four_step_twiddle_cached_across_calls():
    """The (n2, n1) twiddle matrix materialises once per shape."""
    x = rand_complex((2, 16 * 32), key=jax.random.PRNGKey(9))
    before = plan_mod._four_step_twiddle.cache_info().misses
    four_step_fft(x, 16, 32)
    four_step_fft(x, 16, 32)
    info = plan_mod._four_step_twiddle.cache_info()
    assert info.misses - before <= 1
    assert info.hits >= 1


def test_bluestein_chirp_cached_across_traces():
    """Chirp + filter-spectrum factors build once per (length, direction)."""
    from repro.fft.bluestein import _chirp_factors
    before = _chirp_factors.cache_info().misses
    bluestein_fft(rand_complex((1, 77)))
    bluestein_fft(rand_complex((2, 77)))      # second trace, same length
    info = _chirp_factors.cache_info()
    assert info.misses - before <= 1
    assert info.hits >= 1


def test_bluestein_runs_two_pow2_ffts_per_call(monkeypatch):
    """The cached filter spectrum removes one of the three naive FFTs."""
    counter = _CountingKernel(plan_mod.fft_kernel_c2c)
    monkeypatch.setattr(plan_mod, "_kernel_fft", counter)
    bluestein_fft(rand_complex((3, 51)))      # fresh shape -> fresh trace
    assert counter.calls == 2
    plan = plan_for_length(51)
    assert plan.algorithm == "bluestein"
    assert plan.passes == 2 * plan_for_length(128).passes + 1


# ---------------------------------------------------------------------------
# Property-based invariants (hypothesis)
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=20)
@given(logn=st.integers(3, 10), seed=st.integers(0, 2**31 - 1))
def test_property_parseval(logn, seed):
    """sum |x|^2 == sum |X|^2 / N (energy conservation)."""
    n = 2**logn
    x = rand_complex((n,), key=jax.random.PRNGKey(seed))
    X = fft(x)
    np.testing.assert_allclose(jnp.sum(jnp.abs(x) ** 2),
                               jnp.sum(jnp.abs(X) ** 2) / n, rtol=1e-3)


@settings(deadline=None, max_examples=20)
@given(logn=st.integers(2, 9), seed=st.integers(0, 2**31 - 1),
       a=st.floats(-3, 3), b=st.floats(-3, 3))
def test_property_linearity(logn, seed, a, b):
    n = 2**logn
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x, y = rand_complex((n,), k1), rand_complex((n,), k2)
    np.testing.assert_allclose(fft(a * x + b * y), a * fft(x) + b * fft(y),
                               rtol=2e-3, atol=2e-3)


@settings(deadline=None, max_examples=10)
@given(logn=st.integers(3, 8), shift=st.integers(1, 7))
def test_property_time_shift(logn, shift):
    """Circular time shift <-> linear phase in frequency."""
    n = 2**logn
    x = rand_complex((n,))
    X = fft(x)
    Xs = fft(jnp.roll(x, -shift))
    phase = jnp.exp(2j * jnp.pi * shift * jnp.arange(n) / n)
    np.testing.assert_allclose(Xs, X * phase, rtol=2e-3, atol=2e-3)


@settings(deadline=None, max_examples=15)
@given(logn=st.integers(2, 10), seed=st.integers(0, 2**31 - 1))
def test_property_rfft_is_half_spectrum(logn, seed):
    """rfft(x) == fft(x)[:n/2+1] for real x (Hermitian symmetry), and
    irfft inverts it — across lengths and seeds."""
    n = 2**logn
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    X = rfft(x)
    np.testing.assert_allclose(X, fft(x)[: n // 2 + 1], rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(irfft(X), x, rtol=2e-3, atol=2e-3)


@settings(deadline=None, max_examples=10)
@given(logn=st.integers(3, 9), seed=st.integers(0, 2**31 - 1))
def test_property_mixed_radix_schedules_agree(logn, seed):
    """All radix schedules are numerically interchangeable."""
    n = 2**logn
    x = rand_complex((n,), key=jax.random.PRNGKey(seed))
    base = _stockham_pow2(x, radices=(2,))
    for radices in ((4, 2), (8, 4, 2)):
        np.testing.assert_allclose(_stockham_pow2(x, radices=radices), base,
                                   rtol=2e-3, atol=2e-3)


@settings(deadline=None, max_examples=10)
@given(logn=st.integers(4, 10), seed=st.integers(0, 2**31 - 1))
def test_property_impulse_is_flat(logn, seed):
    """FFT of a delta is a flat spectrum (magnitude 1 everywhere)."""
    n = 2**logn
    pos = seed % n
    x = jnp.zeros(n, jnp.complex64).at[pos].set(1.0)
    np.testing.assert_allclose(jnp.abs(fft(x)), jnp.ones(n), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# Pulsar pipeline
# ---------------------------------------------------------------------------

def test_power_spectrum_and_stats():
    x = rand_complex((3, 256))
    X = fft(x)
    p = power_spectrum(X)
    assert p.shape == (3, 256)
    assert bool(jnp.all(p >= 0))
    mean, std = spectrum_stats(p)
    assert mean.shape == (3, 1) and std.shape == (3, 1)


def test_harmonic_sum_levels():
    p = jnp.ones((2, 128))
    hs = harmonic_sum(p, 8)
    assert hs.shape == (2, 4, 128)        # h = 1, 2, 4, 8
    # On a flat spectrum (away from the clipped tail) S_h = h.
    np.testing.assert_allclose(hs[:, 0, 1:16], 1.0)
    np.testing.assert_allclose(hs[:, 3, 1:16], 8.0)


def test_pipeline_finds_injected_pulsar():
    """A periodic signal must produce a high-S/N candidate at its bin."""
    n = 4096
    t = jnp.arange(n, dtype=jnp.float32)
    f0 = 128 / n                               # bin 128 fundamental
    key = jax.random.PRNGKey(1)
    noise = jax.random.normal(key, (1, n))
    # A pulse train has power in the fundamental AND its harmonics.
    signal = (jnp.sin(2 * jnp.pi * f0 * t) > 0.95).astype(jnp.float32)
    x = noise + 4.0 * signal[None, :]
    snr = pulsar_pipeline(x, n_harmonics=8)
    assert snr.shape == (1, 4, n)
    assert float(snr[0, :, 128].max()) > 8.0   # strong detection
    # and harmonic summing must help for a pulse train:
    assert float(snr[0, 1:, 128].max()) >= float(snr[0, 0, 128]) - 1.0


def test_pipeline_real_input_r2c_path():
    """The R2C pipeline finds the same pulsar in half the spectrum."""
    n = 4096
    t = jnp.arange(n, dtype=jnp.float32)
    f0 = 128 / n
    noise = jax.random.normal(jax.random.PRNGKey(1), (1, n))
    signal = (jnp.sin(2 * jnp.pi * f0 * t) > 0.95).astype(jnp.float32)
    x = noise + 4.0 * signal[None, :]
    snr = pulsar_pipeline(x, n_harmonics=8, real_input=True)
    assert snr.shape == (1, 4, n // 2 + 1)     # half-spectrum bins
    assert float(snr[0, :, 128].max()) > 8.0   # same detection, half the work


def test_stage_profiles_real_input_cheaper():
    """R2C accounting: the real-input pipeline moves less and flops less."""
    from repro.core.hardware import TESLA_V100
    c2c = stage_profiles(PipelineShape(batch=32, n=2**20), TESLA_V100)
    r2c = stage_profiles(PipelineShape(batch=32, n=2**20, real_input=True),
                         TESLA_V100)
    assert r2c[0].flops < 0.7 * c2c[0].flops
    assert r2c[0].t_mem < 0.7 * c2c[0].t_mem
    # downstream stages shrink with the half-spectrum too
    assert sum(p.t_mem for p in r2c[1:]) < 0.7 * sum(p.t_mem for p in c2c[1:])


def test_stage_profiles_fft_dominant_share():
    """Sec. 5.3: with 2 harmonics the FFT is ~60% of pipeline time."""
    from repro.core.hardware import TESLA_V100
    from repro.fft.pipeline import fft_time_share
    share = fft_time_share(PipelineShape(batch=32, n=2**20, n_harmonics=2),
                           TESLA_V100)
    assert 0.35 <= share <= 0.85
