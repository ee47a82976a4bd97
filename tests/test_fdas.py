"""FDAS subsystem: plane parity, kernel routing, recovery, DVFS, serving."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fft import plan as plan_mod
from repro.search import (TemplateBank, acceleration_response,
                          extract_candidates, fdas_conv_plan, fdas_search,
                          matched_filter_plane, matched_filter_taps)

KEY = jax.random.PRNGKey(0)


def rand_complex(shape, key=KEY):
    kr, ki = jax.random.split(key)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


def direct_plane(spec, bank):
    """Pad-to-full-length jnp.fft oracle for the matched-filter plane."""
    spec = np.atleast_2d(np.asarray(spec))
    nbins = spec.shape[-1]
    taps = bank.time_domain()
    m = 1 << (nbins + bank.taps - 2).bit_length()
    xs = np.asarray(jnp.fft.fft(jnp.asarray(spec), m, axis=-1))
    hs = np.asarray(jnp.fft.fft(jnp.asarray(taps), m, axis=-1))
    full = np.asarray(jnp.fft.ifft(jnp.asarray(xs[:, None, :] * hs[None]),
                                   axis=-1))
    return full[..., bank.offset:bank.offset + nbins]


def accelerated_series(n, k0, z, *, amp=0.3, noise=0.5, seed=1):
    """Real time series with a tone starting at bin k0, drifting z bins."""
    s = np.arange(n) / n
    rng = np.random.default_rng(seed)
    x = (amp * np.cos(2 * np.pi * (k0 * s + 0.5 * z * s * s))
         + noise * rng.standard_normal(n))
    return jnp.asarray(x.astype(np.float32))[None, :]


# ---------------------------------------------------------------------------
# Templates
# ---------------------------------------------------------------------------

def test_zero_drift_template_is_a_delta():
    t = acceleration_response(0.0, 32)
    peak = np.argmax(np.abs(t))
    assert peak == 32 // 2                       # centred window, u = 0
    assert np.abs(t)[peak] > 0.99
    assert np.abs(np.delete(t, peak)).max() < 0.05


def test_matched_taps_unit_energy():
    for z in (0.0, 3.0, -7.5):
        h = matched_filter_taps(z, 48)
        assert np.sum(np.abs(h) ** 2) == pytest.approx(1.0, rel=1e-6)


def test_bank_construction():
    bank = TemplateBank.linear(zmax=8, n_templates=9)
    assert bank.n_templates == 9
    assert bank.drifts[0] == -8.0 and bank.drifts[-1] == 8.0
    assert bank.taps >= 2 * 8
    assert TemplateBank.linear(zmax=0).drifts == (0.0,)
    with pytest.raises(ValueError):
        TemplateBank.linear(zmax=-1)
    # hashable -> usable as a static jit argument
    assert hash(bank) == hash(TemplateBank.linear(zmax=8, n_templates=9))


# ---------------------------------------------------------------------------
# Matched-filter plane: parity vs the direct oracle (acceptance <= 1e-4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbins", [513, 1025, 700])
def test_plane_matches_direct_oracle(nbins):
    bank = TemplateBank.linear(zmax=4, n_templates=5)
    spec = rand_complex((2, nbins), key=jax.random.PRNGKey(nbins))
    got = np.asarray(matched_filter_plane(spec, bank))
    want = direct_plane(spec, bank)
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel <= 1e-4, rel
    assert got.shape == (2, 5, nbins)


# ---------------------------------------------------------------------------
# Kernel routing: the bank runs as fused multiply epilogues (acceptance)
# ---------------------------------------------------------------------------

class _CountingKernel:
    def __init__(self, inner):
        self.inner = inner
        self.calls = 0
        self.forward_calls = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        if not kwargs.get("inverse"):
            self.forward_calls += 1
        return self.inner(*args, **kwargs)


def test_plane_runs_fused_epilogues_no_multiply_pass(monkeypatch):
    """Forward segment FFTs carry the template bank as in-kernel multiply
    epilogues: ONE fused forward launch, ONE batched inverse launch over
    the T planes, and no plain forward C2C (which would imply a chained
    standalone multiply) or transpose kernels anywhere."""
    mul = _CountingKernel(plan_mod.fft_kernel_c2c_mul)
    fft = _CountingKernel(plan_mod.fft_kernel_c2c)
    tr = _CountingKernel(plan_mod.transpose_kernel)
    monkeypatch.setattr(plan_mod, "_kernel_fft_mul", mul)
    monkeypatch.setattr(plan_mod, "_kernel_fft", fft)
    monkeypatch.setattr(plan_mod, "_kernel_transpose", tr)
    bank = TemplateBank.linear(zmax=3, n_templates=7)
    spec = rand_complex((2, 801), key=jax.random.PRNGKey(41))
    got = matched_filter_plane(spec, bank)
    assert mul.calls == 1 and mul.forward_calls == 1
    assert fft.calls == 1 and fft.forward_calls == 0     # the inverse only
    assert tr.calls == 0
    rel = (np.abs(np.asarray(got) - direct_plane(spec, bank)).max()
           / np.abs(direct_plane(spec, bank)).max())
    assert rel <= 1e-4


def test_fdas_search_routes_r2c_then_fused_conv(monkeypatch):
    rfft = _CountingKernel(plan_mod.fft_kernel_r2c)
    mul = _CountingKernel(plan_mod.fft_kernel_c2c_mul)
    monkeypatch.setattr(plan_mod, "_kernel_rfft", rfft)
    monkeypatch.setattr(plan_mod, "_kernel_fft_mul", mul)
    bank = TemplateBank.linear(zmax=2, n_templates=5)
    x = accelerated_series(1024, 200, 2.0, seed=5)
    res = fdas_search(x, bank, threshold=5.0)
    assert rfft.calls == 1                       # one R2C front-end pass
    assert mul.calls == 1                        # one fused forward launch
    assert res.power.shape == (1, 5, 513)


def test_fdas_falls_back_without_pallas(monkeypatch):
    for hook in ("_kernel_fft", "_kernel_rfft", "_kernel_irfft",
                 "_kernel_fft_mul", "_kernel_fft_t", "_kernel_fft_axis1",
                 "_kernel_rfft_t", "_kernel_transpose"):
        monkeypatch.setattr(plan_mod, hook, _no_kernel)
    bank = TemplateBank.linear(zmax=2, n_templates=5)
    spec = rand_complex((1, 700), key=jax.random.PRNGKey(43))
    with plan_mod.pallas_disabled():
        got = np.asarray(matched_filter_plane(spec, bank))
    want = direct_plane(spec, bank)
    assert np.abs(got - want).max() / np.abs(want).max() <= 1e-4


# ---------------------------------------------------------------------------
# End-to-end search: injected accelerated pulsar recovery (acceptance)
# ---------------------------------------------------------------------------

def test_injected_pulsar_recovered_at_correct_cell():
    n, k0, z = 4096, 300, 6.0
    bank = TemplateBank.linear(zmax=8, n_templates=9)   # drifts step 2
    res = fdas_search(accelerated_series(n, k0, z), bank, threshold=8.0)
    power = np.asarray(res.power)[0]
    t_hit, b_hit = np.unravel_index(int(power.argmax()), power.shape)
    assert bank.drifts[t_hit] == z
    assert abs(b_hit - k0) <= 1
    # ... and it is the top candidate
    c = res.candidates
    assert int(c.template[0, 0]) == t_hit
    assert abs(int(c.bin[0, 0]) - k0) <= 1
    assert float(c.power[0, 0]) > 50.0


def test_zero_drift_tone_prefers_zero_template():
    n = 2048
    s = np.arange(n) / n
    x = jnp.asarray(np.cos(2 * np.pi * 500 * s).astype(np.float32))[None]
    bank = TemplateBank.linear(zmax=4, n_templates=9)
    res = fdas_search(x, bank, threshold=5.0)
    power = np.asarray(res.power)[0]
    t_hit, b_hit = np.unravel_index(int(power.argmax()), power.shape)
    assert bank.drifts[t_hit] == 0.0 and b_hit == 500


def test_extract_candidates_threshold_masking():
    power = jnp.zeros((1, 3, 100)).at[0, 1, 40].set(50.0).at[0, 2, 7].set(9.0)
    c = extract_candidates(power, threshold=8.0, max_candidates=4)
    assert c.template[0, 0] == 1 and c.bin[0, 0] == 40
    assert c.template[0, 1] == 2 and c.bin[0, 1] == 7
    # below-threshold slots are masked
    assert int(c.template[0, 2]) == -1 and float(c.power[0, 2]) == 0.0


def test_fdas_conv_plan_accounting():
    bank = TemplateBank.linear(zmax=8, n_templates=9)
    plan = fdas_conv_plan(2**13, bank)
    assert plan.forward_passes == 1
    assert plan.inverse_passes == bank.n_templates
    assert plan.traffic_ratio > 1.0


# ---------------------------------------------------------------------------
# Cost model + scheduler threading
# ---------------------------------------------------------------------------

def test_conv_case_and_workload():
    from repro.core import ConvCase, TESLA_V100, conv_workload
    case = ConvCase(n=4097, templates=9, taps=32)
    prof = conv_workload(case, TESLA_V100)
    assert prof.t_mem > 0 and prof.t_issue > 0 and prof.flops > 0
    # doubling the bank scales the plane roughly linearly
    big = conv_workload(ConvCase(n=4097, templates=18, taps=32), TESLA_V100)
    assert 1.5 < big.t_mem / prof.t_mem < 2.5
    with pytest.raises(ValueError):
        ConvCase(n=0, templates=1, taps=1)
    with pytest.raises(ValueError):
        ConvCase(n=16, templates=0, taps=1)


def test_fdas_workload_stages_and_scheduler():
    from repro.core import (ConvCase, TESLA_V100, fdas_total_profile,
                            fdas_workload, sweep)
    from repro.core.scheduler import DVFSScheduler
    case = ConvCase(n=2**12 + 1, templates=9, taps=32)
    profs = fdas_workload(case, TESLA_V100, series_n=2**13)
    assert [p.name for p in profs] == ["fdas-fft", "fdas-conv",
                                       "fdas-detect"]
    # the FFT-class stages dominate this pipeline (the point of FDAS as a
    # DVFS workload): their time share exceeds the Sec. 5.3 demo's
    times = [p.time(TESLA_V100.f_max, TESLA_V100) for p in profs]
    assert (times[0] + times[1]) / sum(times) > 0.5
    sched = DVFSScheduler(TESLA_V100)
    f_opt = sweep(profs[1], TESLA_V100).optimal.f
    rep = sched.evaluate_pipeline(
        sched.plan(profs, locked={"fdas-conv": f_opt}))
    assert rep.i_ef > 1.0
    total = fdas_total_profile(case, TESLA_V100, series_n=2**13)
    assert total.t_mem == pytest.approx(sum(p.t_mem for p in profs))


# ---------------------------------------------------------------------------
# Serving: FDAS as a first-class request kind
# ---------------------------------------------------------------------------

def test_service_serves_fdas_requests():
    from repro.serving import FFTService, KIND_FDAS
    svc = FFTService(batch_bytes=2**24, time_budget=None)
    n = 2048
    x = np.asarray(accelerated_series(n, 150, 2.0, seed=3))
    r = svc.submit(x, kind=KIND_FDAS, templates=9)
    svc.drain()
    rec = svc.receipt(r)
    assert rec is not None and rec.energy_j > 0
    # candidates arrive as a (batch, k, 3) array: template, bin, power
    assert rec.result.shape == (1, 16, 3)
    top_template, top_bin, top_power = np.asarray(rec.result[0, 0])
    bank_drifts = np.linspace(-4, 4, 9)
    assert bank_drifts[int(top_template)] == 2.0
    assert abs(int(top_bin) - 150) <= 1
    assert top_power > 8.0


def test_fdas_cache_keyed_on_n_segment_templates():
    from repro.serving import FFTService, KIND_FDAS
    svc = FFTService(batch_bytes=2**24, time_budget=None)
    x = np.random.default_rng(0).standard_normal((1, 1024)).astype(np.float32)
    svc.submit(x, kind=KIND_FDAS, templates=5)
    svc.submit(x, kind=KIND_FDAS, templates=9)          # different bank
    svc.submit(x, kind=KIND_FDAS, templates=5, segment=128)  # pinned nfft
    svc.drain()
    assert svc.cache.stats.misses == 3
    assert svc.cache.stats.sweeps == 3
    svc.submit(x, kind=KIND_FDAS, templates=5)          # repeat: cache hit
    svc.drain()
    assert svc.cache.stats.hits >= 1
    assert svc.cache.stats.sweeps == 3                  # no re-sweep


def test_fdas_request_validation():
    from repro.serving.request import FFTRequest, KIND_FDAS
    with pytest.raises(ValueError, match="templates"):
        FFTRequest(x=jnp.zeros((2, 64)), kind=KIND_FDAS, templates=0)
    with pytest.raises(ValueError):
        FFTRequest(x=jnp.zeros((2, 8, 8)), kind=KIND_FDAS, ndim=2)
    # fdas keys carry (n, segment, templates); plain FFTs zero them out
    a = FFTRequest(x=jnp.zeros((2, 64)), kind=KIND_FDAS, templates=5)
    b = FFTRequest(x=jnp.zeros((2, 64)), kind=KIND_FDAS, templates=9)
    assert a.shape_key("d") != b.shape_key("d")
    c = FFTRequest(x=jnp.zeros((2, 64)), templates=5)
    assert c.shape_key("d").templates == 0


def _no_kernel(*args, **kwargs):
    raise AssertionError("a Pallas kernel ran with Pallas disabled")
