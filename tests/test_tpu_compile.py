"""Compile every Pallas kernel of the served path for a described TPU v5e.

Interpret mode accepts block shapes, layouts and VMEM footprints the
chip's compiler refuses; these tests hand each kernel, at the widths the
chip smoke test serves, to the real Mosaic compiler for a v5e that is
described, not attached (``jax.experimental.topologies``).  Nothing runs:
a pass says the kernel lowers and fits VMEM, not that it is correct or
fast (the interpret-mode tests and ``chip_smoke.py`` say that).

The topology is described inside a fixture (never at import time) so that
every pytest-xdist worker collects the same tests and only the worker
given this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


C64, F32 = jnp.complex64, jnp.float32


@pytest.mark.parametrize("n,rows", [(64, 1024), (4096, 512), (8192, 256)])
def test_c2c(one_chip, n, rows):
    from repro.kernels.fft.ops import fft_kernel_c2c
    _compile(lambda x: fft_kernel_c2c(x, interpret=False), one_chip,
             ((rows, n), C64))


def test_served_c2c_executable_takes_the_planes_whole(one_chip,
                                                     monkeypatch):
    """The service's 4096-point C2C executable, at the benchmark's 32768
    rows, compiled for the chip: the planes go straight into the kernel,
    with no X64SplitLow / X64SplitHigh (the TPU's split of a complex
    array) before it and one X64Combine (its join) for the result.  The
    executable on the complex batch splits it twice."""
    import repro.kernels.fft.ops as ops
    from repro.core.hardware import TPU_V5E
    from repro.serving import FFTRequest, FFTService
    from repro.serving.request import Planes
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    key = FFTRequest(x=np.zeros((1, 4096), C64)).shape_key(TPU_V5E.name)
    entry = FFTService(TPU_V5E).cache.entry(key)
    plane = jax.ShapeDtypeStruct((32768, 4096), F32, sharding=one_chip)
    batch = jax.ShapeDtypeStruct((32768, 4096), C64, sharding=one_chip)

    def calls(compiled):
        text = compiled.as_text()
        return {t: text.count(f'custom_call_target="{t}"')
                for t in ("X64SplitLow", "X64SplitHigh", "X64Combine",
                          "tpu_custom_call")}

    planar = entry.fn.lower(Planes(plane, plane)).compile()
    assert calls(planar) == {"X64SplitLow": 0, "X64SplitHigh": 0,
                             "X64Combine": 1, "tpu_custom_call": 1}
    joined = jax.jit(entry.plan.fn).lower(batch).compile()
    assert calls(joined) == {"X64SplitLow": 1, "X64SplitHigh": 1,
                             "X64Combine": 1, "tpu_custom_call": 1}


@pytest.mark.parametrize("which", ["largest_candidate", "stale_cache_tile"])
def test_c2c_at_tuned_tile(one_chip, which):
    """The longest fused pass at the largest tile the autotuner can
    propose, and at a cached tile far past the VMEM budget (the ops layer
    clamps it)."""
    from repro.kernels.fft.ops import MAX_KERNEL_N, fft_kernel_c2c
    from repro.tune.tuner import TILE_CANDIDATES, _tile_candidates
    tiles = [t for t in _tile_candidates(MAX_KERNEL_N, batch=1024) if t]
    tile = max(tiles) if which == "largest_candidate" else TILE_CANDIDATES[-1]
    _compile(lambda x: fft_kernel_c2c(x, interpret=False, tile_b=tile),
             one_chip, ((1024, MAX_KERNEL_N), C64))


def test_c2c_with_bank(one_chip):
    """The FDAS forward pass with its (T, N) bank epilogue."""
    from repro.kernels.fft.ops import fft_kernel_c2c_mul
    bank = np.ones((16, 4096), np.complex64)
    _compile(lambda x: fft_kernel_c2c_mul(x, bank, interpret=False),
             one_chip, ((64, 4096), C64))


@pytest.mark.parametrize("n", [4096, 16384])
def test_r2c(one_chip, n):
    from repro.kernels.fft.ops import fft_kernel_r2c
    _compile(lambda x: fft_kernel_r2c(x, interpret=False), one_chip,
             ((256, n), F32))


def test_served_r2c_four_step_at_a_gib(one_chip, monkeypatch):
    """The R2C executable the service builds for 256 rows of 2^20 real
    samples (1 GiB, the fft_r2c_1m_1gib cell's batch): packed into 2^19
    complex points, the four-step's two kernel passes at 512 x 1024, the
    Hermitian split.  It fits HBM with room for the cell's payloads and
    retained receipts."""
    import repro.kernels.fft.ops as ops
    from repro.fft.plan import plan_for_length
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    plan = plan_for_length(2**20, "r2c")
    assert plan.algorithm == "four-step"
    compiled = _compile(plan.fn, one_chip, ((256, 2**20), F32))
    text = compiled.as_text()
    assert "fft_axis1_twiddle_pallas" in text and "fft_t_pallas" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 4 * 2**30


def test_c2r(one_chip):
    from repro.kernels.fft.ops import fft_kernel_c2r
    _compile(lambda x: fft_kernel_c2r(x, interpret=False), one_chip,
             ((256, 2049), C64))


@pytest.mark.parametrize("twiddle", [False, True])
def test_transposed_write(one_chip, twiddle):
    """Row pass of the fft2 phase (4096 x 4096) and of the four-step."""
    from repro.kernels.fft.ops import fft_kernel_c2c_t
    tw = np.ones((4096, 4096), np.complex64) if twiddle else None
    _compile(lambda x: fft_kernel_c2c_t(x, twiddle=tw, interpret=False),
             one_chip, ((2, 4096, 4096), C64))


@pytest.mark.parametrize("twiddle", [False, True])
def test_column_pass(one_chip, twiddle):
    """Column pass of the four-step split of 2^19 (512 x 1024)."""
    from repro.kernels.fft.ops import fft_kernel_c2c_axis1
    tw = np.ones((1024, 512), np.complex64) if twiddle else None
    _compile(lambda x: fft_kernel_c2c_axis1(x, twiddle=tw, interpret=False),
             one_chip, ((8, 512, 1024), C64))


def test_r2c_transposed(one_chip):
    from repro.kernels.fft.ops import fft_kernel_r2c_t
    _compile(lambda x: fft_kernel_r2c_t(x, interpret=False), one_chip,
             ((2, 1024, 4096), F32))


def test_transpose(one_chip):
    from repro.kernels.fft.ops import transpose_kernel
    _compile(lambda x: transpose_kernel(x, interpret=False), one_chip,
             ((4, 1024, 3000), C64))


@pytest.mark.parametrize("table,ndm", [("linear", 16), ("plan", 16),
                                        ("plan", 64)])
def test_dedisperse(one_chip, table, ndm):
    """The pulsar phase's filterbanks: 1024 channels x 2^17 samples, 16
    DMs and the pulsar cell's own 64 (channel-tiled; the scoped-VMEM
    limit raised for the resident (D, 2^17) output block).  ``plan`` is
    the service's table, 4 samples of band delay a trial."""
    from repro.data.synthetic import FilterbankSpec
    from repro.kernels.dedisp.ops import dedisperse_kernel
    from repro.search.pipeline import DispersionPlan
    if table == "plan":
        delays = DispersionPlan.from_spec(
            FilterbankSpec(nchan=1024, ntime=2**17), n_trials=ndm).delays
    else:
        delays = np.arange(ndm)[:, None] * np.arange(1024)[None, :] // 64
    _compile(lambda fb: dedisperse_kernel(fb, delays, interpret=False),
             one_chip, ((2, 1024, 2**17), F32))


@pytest.mark.parametrize("ntime,fits", [(2**17, True), (2**18, False)])
def test_harmonic_sum_plane(one_chip, ntime, fits):
    """The pulsar phase's power plane (2 x 16 DMs x 8 templates x
    ntime/2 + 1 bins) at its size, and the refusal one size up that caps
    the phase: the kernel keeps whole padded spectrum rows in VMEM."""
    from repro.kernels.harmonic_sum.ops import harmonic_sum_plane
    shape = ((2, 16, 8, ntime // 2 + 1), F32)
    fn = lambda p: harmonic_sum_plane(p, 8, interpret=False)[0]  # noqa: E731
    if fits:
        _compile(fn, one_chip, shape)
    else:
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="Ran out of memory in memory space vmem"):
            _compile(fn, one_chip, shape)


def test_power_spectrum_stats(one_chip):
    from repro.kernels.spectrum.ops import power_spectrum_stats_kernel
    _compile(lambda x: power_spectrum_stats_kernel(x, interpret=False)[1],
             one_chip, ((64, 8193), C64))
