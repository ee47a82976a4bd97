"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles.

Each kernel is swept over shapes and dtypes per the deliverable contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fft.ops import (MAX_KERNEL_N, fft_kernel_c2c,
                                   fft_kernel_c2r, fft_kernel_r2c)
from repro.kernels.fft.ref import fft_ref, irfft_ref, rfft_ref
from repro.kernels.harmonic_sum.ops import (harmonic_sum_kernel,
                                            harmonic_sum_plane)
from repro.kernels.harmonic_sum.ref import (harmonic_sum_plane_ref,
                                            harmonic_sum_ref)
from repro.kernels.spectrum.ops import power_spectrum_stats_kernel
from repro.kernels.spectrum.ref import power_spectrum_stats_ref

KEY = jax.random.PRNGKey(42)


def rand_c(shape, key=KEY):
    kr, ki = jax.random.split(key)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


class TestFFTKernel:
    @pytest.mark.parametrize("n", [8, 64, 512, 2048, 8192])
    @pytest.mark.parametrize("batch", [1, 4, 13])
    def test_matches_oracle(self, n, batch):
        x = rand_c((batch, n))
        got = fft_kernel_c2c(x, interpret=True)
        re, im = fft_ref(x.real, x.imag)
        want = re + 1j * im
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)

    def test_inverse(self):
        x = rand_c((4, 256))
        y = fft_kernel_c2c(fft_kernel_c2c(x, interpret=True),
                           inverse=True, interpret=True)
        np.testing.assert_allclose(y, x, rtol=3e-4, atol=3e-4)

    def test_multidim_batch(self):
        x = rand_c((2, 3, 128))
        got = fft_kernel_c2c(x, interpret=True)
        np.testing.assert_allclose(got, jnp.fft.fft(x), rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
    def test_real_input_promoted(self, dtype):
        x = jax.random.normal(KEY, (4, 64)).astype(dtype)
        got = fft_kernel_c2c(x, interpret=True)
        np.testing.assert_allclose(got, jnp.fft.fft(x.astype(jnp.complex64)),
                                   rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("n", [128, 1024, MAX_KERNEL_N])
    def test_radix_schedules_match_oracle(self, n):
        """Each factorisation the kernel runs: one (n, n) DFT product at
        n <= 128, else the in-VMEM N1 x 128 four-step (N1 = 8 and 64)."""
        x = rand_c((4, n))
        got = fft_kernel_c2c(x, interpret=True)
        np.testing.assert_allclose(got, jnp.fft.fft(x), rtol=3e-4, atol=3e-4)

    def test_too_long_raises_with_plan_pointer(self):
        x = rand_c((1, 2 * MAX_KERNEL_N))
        with pytest.raises(ValueError, match="repro.fft.plan"):
            fft_kernel_c2c(x, interpret=True)

    def test_n1_forward_inverse_identity(self):
        """Length-1 DFT is the identity BOTH ways (the old inverse branch
        was a silent ``x / 1`` no-op copy standing in for the real path)."""
        x = rand_c((3, 1))
        fwd = fft_kernel_c2c(x, interpret=True)
        inv = fft_kernel_c2c(x, inverse=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(fwd), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(inv), np.asarray(x))
        # parity with the jnp oracle at n=1 (fft == ifft == identity)
        np.testing.assert_allclose(fwd, jnp.fft.fft(x), rtol=1e-6)
        np.testing.assert_allclose(inv, jnp.fft.ifft(x), rtol=1e-6)

    def test_explicit_tile_b_override(self):
        """The autotuner hook: an explicit tile replaces the heuristic and
        stays numerically identical."""
        x = rand_c((12, 256))
        got = fft_kernel_c2c(x, interpret=True, tile_b=4)
        np.testing.assert_allclose(got, jnp.fft.fft(x), rtol=3e-4, atol=3e-4)

    def test_tile_override_clamped_to_vmem_budget(self):
        """A tuned tile past the VMEM block budget (a stale cache entry)
        is cut to the budget tile instead of reaching the compiler."""
        from repro.kernels.common import BLOCK_BUDGET_BYTES, batch_tile
        budget = batch_tile(MAX_KERNEL_N, 4, buffers=8)
        assert budget * MAX_KERNEL_N * 4 * 8 <= BLOCK_BUDGET_BYTES
        assert batch_tile(MAX_KERNEL_N, 4, buffers=8, override=64) == budget
        assert batch_tile(MAX_KERNEL_N, 4, buffers=8, override=3) == 8

    def test_tile_multiple_batch_skips_padding(self, monkeypatch):
        """A tile-multiple batch must not pay the pad-then-slice trip."""
        import repro.kernels.fft.ops as ops
        called = []
        real_pad = jnp.pad
        monkeypatch.setattr(ops.jnp, "pad",
                            lambda *a, **k: called.append(1) or real_pad(*a, **k))
        x = rand_c((8, 256))          # 8 <= tile -> tile=8, pad=0
        got = fft_kernel_c2c(x, interpret=True)
        np.testing.assert_allclose(got, jnp.fft.fft(x), rtol=3e-4, atol=3e-4)
        assert not called


class TestRealFFTKernels:
    @pytest.mark.parametrize("n", [8, 64, 512, 2048, 8192, 2 * MAX_KERNEL_N])
    @pytest.mark.parametrize("batch", [1, 4, 13])
    def test_r2c_matches_oracle(self, n, batch):
        """R2C accepts up to 2*MAX_KERNEL_N (it packs to N/2 complex)."""
        x = jax.random.normal(KEY, (batch, n), jnp.float32)
        got = fft_kernel_r2c(x, interpret=True)
        re, im = rfft_ref(x)
        np.testing.assert_allclose(got, re + 1j * im, rtol=3e-4, atol=2e-3)

    @pytest.mark.parametrize("n", [8, 256, 4096])
    def test_c2r_matches_oracle(self, n):
        x = rand_c((3, n // 2 + 1))
        # a valid half-spectrum: endpoints real (Hermitian consistency)
        x = x.at[:, 0].set(x[:, 0].real).at[:, -1].set(x[:, -1].real)
        got = fft_kernel_c2r(x, interpret=True)
        np.testing.assert_allclose(got, irfft_ref(x.real, x.imag),
                                   rtol=3e-4, atol=2e-3)

    @pytest.mark.parametrize("n", [64, 1024])
    def test_r2c_c2r_roundtrip(self, n):
        x = jax.random.normal(KEY, (5, n), jnp.float32)
        back = fft_kernel_c2r(fft_kernel_r2c(x, interpret=True),
                              interpret=True)
        np.testing.assert_allclose(back, x, rtol=3e-4, atol=2e-3)

    def test_small_n_falls_back(self):
        x = jax.random.normal(KEY, (4, 2), jnp.float32)
        np.testing.assert_allclose(fft_kernel_r2c(x, interpret=True),
                                   jnp.fft.rfft(x), rtol=3e-4, atol=3e-4)

    def test_r2c_too_long_raises(self):
        x = jax.random.normal(KEY, (1, 4 * MAX_KERNEL_N), jnp.float32)
        with pytest.raises(ValueError, match="repro.fft.plan"):
            fft_kernel_r2c(x, interpret=True)


class TestHarmonicSumKernel:
    @pytest.mark.parametrize("n", [64, 256, 1024, 4096])
    @pytest.mark.parametrize("h", [2, 8, 32])
    def test_matches_oracle(self, n, h):
        p = jax.random.uniform(KEY, (5, n), dtype=jnp.float32)
        got = harmonic_sum_kernel(p, h, interpret=True)
        want = harmonic_sum_ref(p, h)
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_flat_spectrum_values(self):
        """On P == 1, level h sums h in-range copies: S_h[k] = #valid j."""
        n, h = 128, 4
        p = jnp.ones((1, n))
        got = harmonic_sum_kernel(p, h, interpret=True)
        # k=1: all j*k < n for j<=4 -> S = 1, 2, 4 at levels 0..2
        np.testing.assert_allclose(got[0, :, 1], [1.0, 2.0, 4.0])
        # k = n-1: only j=1 in range
        np.testing.assert_allclose(got[0, :, n - 1], [1.0, 1.0, 1.0])

    def test_large_batch_tiling(self):
        p = jax.random.uniform(KEY, (37, 256), dtype=jnp.float32)
        got = harmonic_sum_kernel(p, 8, interpret=True)
        np.testing.assert_allclose(got, harmonic_sum_ref(p, 8), rtol=1e-5,
                                   atol=1e-5)

    def test_single_harmonic_is_identity_ladder(self):
        """n_harmonics=1: one ladder level that IS the input spectrum."""
        p = jax.random.uniform(KEY, (3, 64), dtype=jnp.float32)
        got = harmonic_sum_kernel(p, 1, interpret=True)
        assert got.shape == (3, 1, 64)
        np.testing.assert_allclose(got[:, 0], p, rtol=1e-6)


class TestHarmonicSumPlane:
    """The fused production variant: ladder + normalise + max-reduce in
    VMEM, only the (..., N) statistic and int32 level leave the kernel."""

    @pytest.mark.parametrize("n", [64, 1024])
    @pytest.mark.parametrize("h", [1, 4, 32])
    def test_matches_oracle(self, n, h):
        p = jax.random.uniform(KEY, (5, n), dtype=jnp.float32) * 3.0
        stat, lev = harmonic_sum_plane(p, h, interpret=True)
        stat_r, lev_r = harmonic_sum_plane_ref(p, h)
        assert stat.shape == lev.shape == (5, n)
        assert lev.dtype == jnp.int32
        np.testing.assert_allclose(stat, stat_r, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(lev), np.asarray(lev_r))

    def test_odd_length_non_divisible_batch(self):
        """Odd N and a prime batch: tiling edges on both axes at once."""
        p = jax.random.uniform(KEY, (11, 3, 129), dtype=jnp.float32)
        stat, lev = harmonic_sum_plane(p, 8, interpret=True)
        stat_r, lev_r = harmonic_sum_plane_ref(p, 8)
        np.testing.assert_allclose(stat, stat_r, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(lev), np.asarray(lev_r))

    def test_single_harmonic_edge(self):
        """n_harmonics=1: stat == P - 1 (z_1), level 0 everywhere."""
        p = jax.random.uniform(KEY, (2, 64), dtype=jnp.float32)
        stat, lev = harmonic_sum_plane(p, 1, interpret=True)
        np.testing.assert_allclose(stat, p - 1.0, rtol=1e-6, atol=1e-6)
        assert not np.asarray(lev).any()

    def test_planted_harmonic_signal_picks_deep_level(self):
        """Power split across harmonics k, 2k, 4k: summing the ladder to
        level 2 collects all three, so level 2 must win at bin k."""
        n, k = 256, 10
        p = jnp.ones((1, n))
        for m in (1, 2, 4):
            p = p.at[0, m * k].add(30.0)
        stat, lev = harmonic_sum_plane(p, 8, interpret=True)
        assert int(lev[0, k]) == 2
        assert int(jnp.argmax(stat[0])) == k

    def test_agrees_with_demo_ladder(self):
        """The fused plane must equal normalise+max over the demo ladder."""
        p = jax.random.uniform(KEY, (4, 128), dtype=jnp.float32) * 2.0
        ladder = harmonic_sum_kernel(p, 16, interpret=True)
        hs = 2.0 ** jnp.arange(ladder.shape[-2])
        z = (ladder - hs[:, None]) / jnp.sqrt(hs)[:, None]
        stat, lev = harmonic_sum_plane(p, 16, interpret=True)
        np.testing.assert_allclose(stat, z.max(axis=-2), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(lev),
                                      np.asarray(jnp.argmax(z, axis=-2)))


class TestSpectrumKernel:
    @pytest.mark.parametrize("n", [64, 1024, 8192])
    @pytest.mark.parametrize("batch", [1, 7, 16])
    def test_matches_oracle(self, n, batch):
        x = rand_c((batch, n))
        p, mean, std = power_spectrum_stats_kernel(x, interpret=True)
        pr, mr, sr = power_spectrum_stats_ref(x.real, x.imag)
        np.testing.assert_allclose(p, pr, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(mean, mr, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(std, sr, rtol=1e-3, atol=1e-5)

    def test_parseval_consistency(self):
        """mean(power) * N == mean |x|^2 (Parseval, ties kernel to FFT)."""
        x = rand_c((2, 512))
        X = fft_kernel_c2c(x, interpret=True)
        _, mean, _ = power_spectrum_stats_kernel(X, interpret=True)
        energy_time = jnp.mean(jnp.abs(x) ** 2, axis=-1)
        np.testing.assert_allclose(mean, energy_time, rtol=1e-4)


class TestKernelPipelineEquivalence:
    """The Pallas pipeline must agree with the pure-JAX pipeline end-to-end."""

    def test_full_pipeline(self):
        from repro.fft.pipeline import harmonic_sum as hs_jax
        from repro.fft.pipeline import power_spectrum as ps_jax

        x = rand_c((3, 1024))
        spec_k = fft_kernel_c2c(x, interpret=True)
        p_k, mean_k, std_k = power_spectrum_stats_kernel(spec_k,
                                                         interpret=True)
        hs_k = harmonic_sum_kernel(p_k, 8, interpret=True)

        spec_j = jnp.fft.fft(x)
        p_j = ps_jax(spec_j)
        np.testing.assert_allclose(p_k, p_j, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(hs_k, harmonic_sum_ref(p_j, 8),
                                   rtol=2e-4, atol=2e-4)


class TestKernelInputValidation:
    """Caller-input guards must survive ``python -O`` (ValueError, not
    assert) and reject empty trailing dims before they reach a kernel."""

    def test_harmonic_sum_rejects_non_pow2_harmonics(self):
        p = jnp.ones((2, 64))
        with pytest.raises(ValueError, match="power of two"):
            harmonic_sum_kernel(p, 12, interpret=True)
        with pytest.raises(ValueError, match="power of two"):
            harmonic_sum_kernel(p, 0, interpret=True)

    def test_harmonic_sum_rejects_empty_trailing_dim(self):
        with pytest.raises(ValueError, match="non-empty trailing"):
            harmonic_sum_kernel(jnp.ones((2, 0)), 8, interpret=True)
        with pytest.raises(ValueError, match="non-empty trailing"):
            harmonic_sum_plane(jnp.ones((2, 0)), 8, interpret=True)

    def test_harmonic_sum_rejects_complex_power(self):
        """Power planes are real (|X|**2); a complex spectrum here is an
        upstream bug, not something to silently .real away."""
        x = jnp.ones((2, 64), jnp.complex64)
        with pytest.raises(ValueError, match="complex dtype"):
            harmonic_sum_kernel(x, 8, interpret=True)
        with pytest.raises(ValueError, match="complex dtype"):
            harmonic_sum_plane(x, 8, interpret=True)

    def test_harmonic_sum_plane_rejects_non_pow2_harmonics(self):
        with pytest.raises(ValueError, match="power of two"):
            harmonic_sum_plane(jnp.ones((2, 64)), 3, interpret=True)

    def test_spectrum_stats_rejects_empty_trailing_dim(self):
        with pytest.raises(ValueError, match="non-empty trailing"):
            power_spectrum_stats_kernel(jnp.ones((2, 0), jnp.complex64),
                                        interpret=True)

    def test_fft_pallas_rejects_non_dividing_tile(self):
        """Kernel-level guards carry the offending shapes (ValueError, not
        assert: asserts vanish under ``python -O``)."""
        from repro.kernels.fft.fft_kernel import fft_pallas
        re = jnp.ones((10, 64))
        with pytest.raises(ValueError, match=r"batch=10.*\(4\)"):
            fft_pallas(re, re, tile_b=4, interpret=True)

    def test_fft_pallas_rejects_non_pow2_length(self):
        from repro.kernels.fft.fft_kernel import fft_pallas
        re = jnp.ones((4, 48))
        with pytest.raises(ValueError, match="power of two, got 48"):
            fft_pallas(re, re, tile_b=4, interpret=True)

    def test_harmonic_sum_pallas_rejects_non_dividing_tile(self):
        from repro.kernels.harmonic_sum.harmonic_sum_kernel import \
            harmonic_sum_pallas
        p = jnp.ones((10, 64))
        with pytest.raises(ValueError, match=r"batch=10.*\(4\)"):
            harmonic_sum_pallas(p, 8, tile_b=4, interpret=True)

    def test_spectrum_pallas_rejects_non_dividing_tile(self):
        from repro.kernels.spectrum.spectrum_kernel import \
            power_spectrum_stats_pallas
        re = jnp.ones((10, 64))
        with pytest.raises(ValueError, match=r"batch=10.*\(4\)"):
            power_spectrum_stats_pallas(re, re, tile_b=4, interpret=True)


class TestBackendMode:
    """Kernels compile on the TPU, run interpreted on the CPU (the test
    path), and refuse any other backend rather than emulate it."""

    @pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                                   ("tpu", False)])
    def test_known_backends(self, monkeypatch, backend, interpret):
        from repro.kernels import common
        monkeypatch.setattr(common.jax, "default_backend", lambda: backend)
        assert common.use_interpret() is interpret

    def test_unknown_backend_raises(self, monkeypatch):
        from repro.kernels import common
        monkeypatch.setattr(common.jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            common.use_interpret()
