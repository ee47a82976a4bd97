"""Dedispersion kernel tests: Pallas vs oracle parity, guards, properties.

The kernel reads a static (DM, channel) delay table from SMEM
(gather-free shift-and-sum, repro.kernels.dedisp); the oracle gathers
with ``take_along_axis``.  Property tests draw random DM tables and
non-divisible batch tiles; they skip cleanly when ``hypothesis`` is not
installed (tests/_hyp.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:
    from _hyp import given, settings, st

from repro.data.synthetic import (FilterbankSpec, InjectedPulsar,
                                  synthetic_filterbank)
from repro.kernels.dedisp import dedisperse_kernel, dedisperse_ref
from repro.kernels.dedisp.dedisp_kernel import dedisperse_pallas, row_layout
from repro.obs.ledger import LaunchLedger

KEY = jax.random.PRNGKey(7)


def _rand_fb(shape, key=KEY):
    return jax.random.normal(key, shape, jnp.float32)


def _rand_delays(rng, ndm, nchan, ntime):
    return rng.integers(0, ntime, size=(ndm, nchan), dtype=np.int64)


class TestDedisperseParity:
    @pytest.mark.parametrize("batch", [1, 3])
    @pytest.mark.parametrize("ndm", [1, 5])
    def test_matches_oracle(self, batch, ndm):
        rng = np.random.default_rng(0)
        nchan, n = 8, 256
        fb = _rand_fb((batch, nchan, n))
        delays = _rand_delays(rng, ndm, nchan, n)
        got = dedisperse_kernel(fb, delays, interpret=True)
        want = dedisperse_ref(fb, delays)
        assert got.shape == (batch, ndm, n)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("tile_c", [8, 16])
    def test_channel_tiles_sum_like_one_block(self, tile_c):
        """Channel slabs accumulate into the resident output block: the
        result equals the untiled kernel bit for bit (same channel order)
        and the oracle within float32 rounding."""
        rng = np.random.default_rng(4)
        fb = _rand_fb((2, 32, 256))
        delays = tuple(tuple(int(d) for d in row)
                       for row in _rand_delays(rng, 3, 32, 256))
        got = dedisperse_pallas(fb, delays, tile_c=tile_c, interpret=True)
        whole = dedisperse_pallas(fb, delays, interpret=True)
        np.testing.assert_array_equal(got, whole)
        np.testing.assert_allclose(got, dedisperse_ref(fb, delays),
                                   rtol=1e-5, atol=1e-5)
        with pytest.raises(ValueError, match="channel tile"):
            dedisperse_pallas(fb, delays, tile_c=12, interpret=True)

    def test_multidim_lead_axes(self):
        rng = np.random.default_rng(1)
        fb = _rand_fb((2, 3, 4, 128))
        delays = _rand_delays(rng, 6, 4, 128)
        got = dedisperse_kernel(fb, delays, interpret=True)
        assert got.shape == (2, 3, 6, 128)
        np.testing.assert_allclose(got, dedisperse_ref(fb, delays),
                                   rtol=1e-5, atol=1e-5)

    def test_rank2_payload(self):
        """A single (nchan, ntime) filterbank: no batch axis either side."""
        rng = np.random.default_rng(2)
        fb = _rand_fb((4, 64))
        delays = _rand_delays(rng, 3, 4, 64)
        got = dedisperse_kernel(fb, delays, interpret=True)
        assert got.shape == (3, 64)
        np.testing.assert_allclose(got, dedisperse_ref(fb, delays),
                                   rtol=1e-5, atol=1e-5)

    def test_non_divisible_batch_tile(self):
        """A prime batch far above any tile: the ops layer must pad to the
        tile multiple and slice back without corrupting edge rows."""
        rng = np.random.default_rng(3)
        fb = _rand_fb((13, 4, 512))
        delays = _rand_delays(rng, 4, 4, 512)
        got = dedisperse_kernel(fb, delays, interpret=True)
        np.testing.assert_allclose(got, dedisperse_ref(fb, delays),
                                   rtol=1e-5, atol=1e-5)

    def test_zero_delay_is_channel_sum(self):
        fb = _rand_fb((2, 6, 128))
        delays = np.zeros((1, 6), dtype=np.int64)
        got = dedisperse_kernel(fb, delays, interpret=True)
        np.testing.assert_allclose(got[:, 0], fb.sum(axis=1),
                                   rtol=1e-5, atol=1e-5)

    def test_plan_delays_cancel_injection(self):
        """The physics contract the pipeline rests on: dedispersing at the
        injected DM's own rounded delay table re-aligns the pulse exactly,
        so the matched trial carries the most power."""
        spec = FilterbankSpec(nchan=8, ntime=1024)
        dm = 40 * spec.dm_step          # ~40-sample sweep across the band
        fb = synthetic_filterbank(
            spec, (InjectedPulsar(dm=dm, k0=200, amp=0.5),), noise=0.5,
            seed=0)
        delays = np.stack([np.zeros(spec.nchan, np.int64),
                           spec.delay_samples(dm)])
        ts = dedisperse_kernel(fb, delays, interpret=True)
        spec_pow = jnp.abs(jnp.fft.rfft(ts - ts.mean(-1, keepdims=True)))**2
        # the k0 bin dominates only on the matched (second) trial
        assert int(jnp.argmax(spec_pow[1])) == 200
        assert float(spec_pow[1, 200]) > 4 * float(spec_pow[0, 200])


def _sequential_sum(fb, delays):
    """The kernel's sum, in numpy: float32, channels 0..C-1 in order, a
    sample at or past N reading 0."""
    fb = np.asarray(fb, np.float32)
    *lead, nchan, n = fb.shape
    out = np.zeros((*lead, len(delays), n), np.float32)
    for d, row in enumerate(delays):
        for c in range(nchan):
            s = int(row[c])
            out[..., d, :n - s] += fb[..., c, s:]
    return out


#: Delays that cross the row boundaries of the kernel's (rows, 128) layout;
#: a case keeps those below its N, and N - 1.
_ROW_DELAYS = (0, 1, 127, 128, 129, 255, 1000)


class TestDedisperseRowLayout:
    """The kernel lays each channel's time axis as rows of 128 lanes and
    splits a delay into rows and lanes: every output equals the same
    float32 sum in the same channel order, bit for bit."""

    @pytest.mark.parametrize("batch", [1, 3])
    @pytest.mark.parametrize("ndm", [1, 5])
    @pytest.mark.parametrize("nchan", [4, 8, 32])
    @pytest.mark.parametrize("n", [64, 200, 1000, 2048])
    def test_exact_against_sequential_sum(self, n, nchan, ndm, batch):
        rng = np.random.default_rng(n * 1000 + nchan * 10 + ndm)
        vals = [d for d in _ROW_DELAYS if d < n] + [n - 1]
        delays = rng.permutation(np.resize(vals, ndm * nchan))
        delays = delays.reshape(ndm, nchan)
        fb = _rand_fb((batch, nchan, n), jax.random.PRNGKey(n + nchan))
        got = dedisperse_kernel(fb, delays, interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      _sequential_sum(fb, delays))

    @pytest.mark.parametrize("n,max_delay,layout", [
        (2 ** 17, 252, (1024, 1032, 128)),   # the pulsar cell's block
        (1000, 999, (8, 16, 8)),
        (2048, 128, (16, 24, 16)),
        (64, 0, (8, 16, 8)),
    ])
    def test_layout_follows_the_shapes(self, n, max_delay, layout):
        """(output rows, input rows, time-tile rows): whole (8, 128)
        tiles of output, room for the largest delay's row and its
        successor, the largest power-of-two tile dividing the output."""
        assert row_layout(n, max_delay) == layout

    def test_launch_records_padded_length_and_time_tile(self):
        fb = _rand_fb((2, 8, 1000))
        delays = np.array([[0, 1, 2, 3, 4, 5, 6, 300]])
        led = LaunchLedger()
        with led.capture():
            dedisperse_kernel(fb, delays, interpret=True)
        (rec,) = led.records
        rows_out, rows_in, rows_t = row_layout(1000, 300)
        assert rec.kernel == "dedisperse"
        assert rec.shape == (2, 8, 1000)
        assert rec.tile == (2, 8, rows_in * 128, rows_t) == (2, 8, 2048, 8)


class TestDedisperseGuards:
    """ValueError-with-shapes guards (never assert: ``python -O`` strips
    asserts, and these reject caller input)."""

    def test_rejects_rank1(self):
        with pytest.raises(ValueError, match="nchan, ntime"):
            dedisperse_kernel(jnp.ones((64,)), [[0]], interpret=True)

    def test_rejects_complex(self):
        fb = jnp.ones((2, 4, 64), jnp.complex64)
        with pytest.raises(ValueError, match="must be real"):
            dedisperse_kernel(fb, np.zeros((1, 4), np.int64), interpret=True)

    def test_rejects_empty_axes(self):
        with pytest.raises(ValueError, match="non-empty"):
            dedisperse_kernel(jnp.ones((2, 0, 64)),
                              np.zeros((1, 0), np.int64), interpret=True)
        with pytest.raises(ValueError, match="non-empty"):
            dedisperse_kernel(jnp.ones((2, 4, 0)),
                              np.zeros((1, 4), np.int64), interpret=True)

    def test_rejects_channel_mismatch(self):
        fb = jnp.ones((2, 4, 64))
        with pytest.raises(ValueError, match="covers 3 channels"):
            dedisperse_kernel(fb, np.zeros((2, 3), np.int64), interpret=True)

    def test_rejects_empty_trial_table(self):
        fb = jnp.ones((2, 4, 64))
        with pytest.raises(ValueError, match="no DM trials"):
            dedisperse_kernel(fb, np.zeros((0, 4), np.int64), interpret=True)

    def test_rejects_non_integer_delays(self):
        fb = jnp.ones((2, 4, 64))
        with pytest.raises(ValueError, match="integer samples"):
            dedisperse_kernel(fb, np.zeros((1, 4), np.float32),
                              interpret=True)

    def test_rejects_wrong_table_rank(self):
        fb = jnp.ones((2, 4, 64))
        with pytest.raises(ValueError, match=r"\(n_dm, nchan\) table"):
            dedisperse_kernel(fb, np.zeros(4, np.int64), interpret=True)

    def test_pallas_rejects_non_dividing_tile(self):
        fb = jnp.ones((10, 2, 64))
        delays = ((0, 1),)
        with pytest.raises(ValueError, match=r"batch=10.*\(4\)"):
            dedisperse_pallas(fb, delays, tile_b=4, interpret=True)

    def test_pallas_rejects_out_of_range_delay(self):
        fb = jnp.ones((2, 2, 64))
        with pytest.raises(ValueError, match=r"outside \[0, ntime=64\)"):
            dedisperse_pallas(fb, ((0, 64),), tile_b=1, interpret=True)
        with pytest.raises(ValueError, match="outside"):
            dedisperse_pallas(fb, ((-1, 0),), tile_b=1, interpret=True)


class TestDedisperseProperties:
    @settings(deadline=None, max_examples=15)
    @given(st.integers(1, 9),          # batch (tile edges: primes included)
           st.integers(1, 6),          # nchan
           st.integers(1, 8),          # n_dm
           st.integers(0, 2 ** 31))    # delay-table seed
    def test_random_tables_match_oracle(self, batch, nchan, ndm, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.choice([96, 128, 200]))   # non-pow2 lengths included
        fb = jax.random.normal(jax.random.PRNGKey(seed % 997),
                               (batch, nchan, n), jnp.float32)
        delays = _rand_delays(rng, ndm, nchan, n)
        got = dedisperse_kernel(fb, delays, interpret=True)
        np.testing.assert_allclose(got, dedisperse_ref(fb, delays),
                                   rtol=1e-5, atol=1e-5)

    @settings(deadline=None, max_examples=10)
    @given(st.integers(0, 2 ** 31))
    def test_linearity(self, seed):
        """Dedispersion is linear in the filterbank: D(a+b) == D(a)+D(b)."""
        rng = np.random.default_rng(seed)
        a = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 128))
        b = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 128))
        delays = _rand_delays(rng, 3, 4, 128)
        lhs = dedisperse_kernel(a + b, delays, interpret=True)
        rhs = (dedisperse_kernel(a, delays, interpret=True)
               + dedisperse_kernel(b, delays, interpret=True))
        np.testing.assert_allclose(lhs, rhs, rtol=1e-4, atol=1e-4)
