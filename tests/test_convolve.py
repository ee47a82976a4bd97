"""Overlap-save FFT convolution engine: parity, edge cases, routing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:          # optional test dep: skip property tests
    from _hyp import given, settings, st

from repro.fft import convolve as conv_mod
from repro.fft import plan as plan_mod
from repro.fft.convolve import (ConvPlan, cached_filter_spectra, conv_plan,
                                overlap_save_conv, select_nfft)

KEY = jax.random.PRNGKey(0)


def rand_complex(shape, key=KEY):
    kr, ki = jax.random.split(key)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


def oracle(x, filters):
    """Direct per-filter full convolution (numpy)."""
    x = np.atleast_2d(np.asarray(x))
    filters = np.atleast_2d(np.asarray(filters))
    return np.stack([[np.convolve(row, f) for f in filters] for row in x])


def assert_close(got, want, rtol=1e-4):
    got, want = np.asarray(got), np.asarray(want)
    rel = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    assert rel < rtol, rel


# ---------------------------------------------------------------------------
# Parity vs the direct oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,taps,t,nfft", [
    (1000, 33, 3, None),       # non-pow2 signal -> padded pow2 segments
    (512, 16, 1, None),        # single filter
    (513, 17, 4, 64),          # explicit segment length
    (64, 8, 2, None),          # signal shorter than the chosen segment
    (100, 129, 2, None),       # filter longer than the signal
])
def test_overlap_save_matches_convolve(n, taps, t, nfft):
    x = rand_complex((2, n))
    h = np.asarray(rand_complex((t, taps), key=jax.random.PRNGKey(7)))
    got = overlap_save_conv(x, h, nfft=nfft)
    assert got.shape == (2, t, n + taps - 1)
    assert_close(got, oracle(x, h))


def test_overlap_save_batch_of_one_and_1d_input():
    x1 = rand_complex((1, 300))
    h = np.asarray(rand_complex((2, 21), key=jax.random.PRNGKey(3)))
    assert_close(overlap_save_conv(x1, h), oracle(x1, h))
    # a bare (n,) row keeps its rank: (T, out) without a batch axis
    x0 = rand_complex((300,), key=jax.random.PRNGKey(4))
    got = overlap_save_conv(x0, h)
    assert got.shape == (2, 320)
    assert_close(got, oracle(x0, h)[0])


def test_real_input_promoted_to_complex():
    x = jax.random.normal(KEY, (2, 200))
    h = np.asarray(rand_complex((2, 15), key=jax.random.PRNGKey(5)))
    assert_close(overlap_save_conv(x, h), oracle(x, h))


def test_filter_longer_than_segment_raises():
    with pytest.raises(ValueError, match="longer than the segment"):
        overlap_save_conv(jnp.zeros(100), np.ones((1, 65)), nfft=64)
    with pytest.raises(ValueError, match="power of two"):
        overlap_save_conv(jnp.zeros(100), np.ones((1, 5)), nfft=48)


def test_auto_selection_handles_long_filters():
    """A filter far longer than the default segment guess just bumps the
    auto-selected segment — no caller-side sizing needed."""
    taps = 700
    x = rand_complex((1, 256))
    h = np.asarray(rand_complex((1, taps), key=jax.random.PRNGKey(9)))
    plan = conv_plan(256, taps, 1)
    assert plan.nfft >= taps
    assert_close(overlap_save_conv(x, h), oracle(x, h))


@settings(deadline=None, max_examples=15)
@given(n=st.integers(16, 600), logtaps=st.integers(2, 6),
       t=st.integers(1, 3), seed=st.integers(0, 2**31 - 1))
def test_property_overlap_save_parity(n, logtaps, t, seed):
    taps = 2**logtaps + 1
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    x = rand_complex((2, n), key=k1)
    h = np.asarray(rand_complex((t, taps), key=k2))
    assert_close(overlap_save_conv(x, h), oracle(x, h), rtol=3e-4)


# ---------------------------------------------------------------------------
# Plan accounting + segment selection
# ---------------------------------------------------------------------------

def test_conv_plan_pass_accounting():
    plan = conv_plan(4096, 32, templates=8)
    assert isinstance(plan, ConvPlan)
    assert plan.forward_passes == 1          # fused bank multiply epilogue
    assert plan.inverse_passes == 8          # one inverse pass per template
    assert plan.step == plan.nfft - plan.taps + 1
    assert plan.n_segments * plan.step >= plan.out_len
    # long signal, short filter: overlap-save beats the direct method
    assert plan.traffic_ratio > 1.0


def test_conv_plan_memoised_and_validated():
    assert conv_plan(1024, 17, 4) is conv_plan(1024, 17, 4)
    with pytest.raises(ValueError):
        conv_plan(1024, 17, 0)
    with pytest.raises(ValueError):
        conv_plan(1024, 65, 1, nfft=64)


def test_select_nfft_bounds():
    for taps, n in [(17, 4096), (65, 1000), (5, 64)]:
        nfft = select_nfft(taps, n, templates=4)
        assert nfft >= taps and nfft & (nfft - 1) == 0
        # never longer than one segment covering the whole padded signal
        assert nfft <= 1 << max(n + taps - 2, 1).bit_length()


def test_filter_spectra_cached_per_key():
    h = np.asarray(rand_complex((3, 9), key=jax.random.PRNGKey(11)))
    before = conv_mod._SPECTRA_BUILDS
    a = cached_filter_spectra(("test-bank", 1), h, 64)
    mid = conv_mod._SPECTRA_BUILDS
    b = cached_filter_spectra(("test-bank", 1), h, 64)
    after = conv_mod._SPECTRA_BUILDS
    assert mid == before + 1 and after == mid    # second call: pure hit
    assert a is b
    # a different segment length is a different artefact
    cached_filter_spectra(("test-bank", 1), h, 128)
    assert conv_mod._SPECTRA_BUILDS == after + 1


# ---------------------------------------------------------------------------
# Kernel routing: fused multiply epilogue, no standalone multiply pass
# ---------------------------------------------------------------------------

class _CountingKernel:
    def __init__(self, inner):
        self.inner = inner
        self.calls = 0
        self.inverse_calls = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        if kwargs.get("inverse"):
            self.inverse_calls += 1
        return self.inner(*args, **kwargs)


def test_conv_routes_fused_mul_plus_one_inverse(monkeypatch):
    """The forward segment FFT carries the bank multiply as a kernel
    epilogue and the T product planes share ONE batched inverse launch —
    no plain forward FFT, no separate multiply, no transpose kernels."""
    mul = _CountingKernel(plan_mod.fft_kernel_c2c_mul)
    fft = _CountingKernel(plan_mod.fft_kernel_c2c)
    tr = _CountingKernel(plan_mod.transpose_kernel)
    monkeypatch.setattr(plan_mod, "_kernel_fft_mul", mul)
    monkeypatch.setattr(plan_mod, "_kernel_fft", fft)
    monkeypatch.setattr(plan_mod, "_kernel_transpose", tr)
    x = rand_complex((3, 777), key=jax.random.PRNGKey(21))
    h = np.asarray(rand_complex((5, 33), key=jax.random.PRNGKey(22)))
    got = overlap_save_conv(x, h)
    assert_close(got, oracle(x, h))
    assert mul.calls == 1                       # fused forward + epilogue
    assert fft.calls == 1 and fft.inverse_calls == 1   # one inverse launch
    assert tr.calls == 0


def test_conv_falls_back_without_pallas(monkeypatch):
    for hook in ("_kernel_fft", "_kernel_rfft", "_kernel_irfft",
                 "_kernel_fft_mul", "_kernel_fft_t", "_kernel_fft_axis1",
                 "_kernel_rfft_t", "_kernel_transpose"):
        monkeypatch.setattr(plan_mod, hook, _no_kernel)
    x = rand_complex((2, 333), key=jax.random.PRNGKey(23))
    h = np.asarray(rand_complex((3, 17), key=jax.random.PRNGKey(24)))
    with plan_mod.pallas_disabled():
        assert_close(overlap_save_conv(x, h), oracle(x, h))


def test_fft_mul_kernel_parity():
    from repro.kernels.fft.ops import fft_kernel_c2c_mul
    x = rand_complex((4, 128), key=jax.random.PRNGKey(31))
    bank = np.asarray(rand_complex((3, 128), key=jax.random.PRNGKey(32)))
    got = np.asarray(fft_kernel_c2c_mul(x, bank))
    want = np.fft.fft(np.asarray(x), axis=-1)[:, None, :] * bank[None]
    assert_close(got, want)


def test_fft_mul_kernel_rejects_bad_bank():
    from repro.kernels.fft.ops import fft_kernel_c2c_mul
    with pytest.raises(ValueError, match="filter bank"):
        fft_kernel_c2c_mul(jnp.zeros((2, 64), jnp.complex64),
                           jnp.zeros((3, 32), jnp.complex64))


def test_conv_plan_unfused_beyond_kernel_limit():
    """Segments past the single-pass kernel limit cannot fuse the bank
    multiply; the plan must charge the fallback (FFT passes + ONE
    standalone multiply pass) instead of the fused-epilogue counts."""
    plan = conv_plan(2**15, 6000, templates=2)       # forces nfft > 2^13
    assert plan.nfft > 8192 and not plan.fused
    assert plan.forward_passes > 1                   # + multiply pass
    assert plan.inverse_passes > plan.templates      # four-step inverses
    fused = conv_plan(2**15, 33, templates=2)
    assert fused.fused and fused.forward_passes == 1


def _no_kernel(*args, **kwargs):
    raise AssertionError("a Pallas kernel ran with Pallas disabled")
