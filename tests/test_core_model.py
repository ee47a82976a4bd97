"""Unit tests for the DVFS model layers (hardware, power, perf, energy)."""
import numpy as np
import pytest

from repro.core import (JETSON_NANO, TESLA_V100, TPU_V5E, DVFSScheduler,
                        FFTCase, PowerModel, WorkloadProfile, evaluate,
                        fft_workload, sweep)
from repro.core.energy import energy_from_trace, fft_flops, ffts_per_batch
from repro.core.hardware import TITAN_V, TITAN_V_DRIVER_CAP_MHZ
from repro.core.scheduler import predicted_pipeline_i_ef
from repro.core.realtime import (RealTimeBudget, devices_required,
                                 extra_hardware)


def test_frequency_grid_matches_table1():
    f = TESLA_V100.frequencies()
    assert f[0] == 1530.0
    assert f[-1] >= 135.0
    assert np.all(np.diff(f) < 0)
    # paper Table 1: steps of 7/8 MHz -> nominal 7.5
    assert np.allclose(np.diff(f)[:-1], -7.5)

    fn = JETSON_NANO.frequencies()
    assert fn[0] == pytest.approx(921.6)
    assert np.allclose(np.diff(fn), -76.8)


def test_voltage_floor_and_monotonicity():
    f = TESLA_V100.frequencies()
    v = TESLA_V100.voltage(f)
    assert v[0] == pytest.approx(1.0)
    assert np.all(np.diff(v) <= 1e-12)           # non-increasing with f desc
    assert v[-1] == pytest.approx(TESLA_V100.v_floor)


def test_power_monotonic_in_frequency():
    pm = PowerModel(TESLA_V100)
    f = TESLA_V100.frequencies()
    p = pm.power(f)
    assert np.all(np.diff(p) <= 1e-9)            # power falls as f falls
    assert p[0] <= TESLA_V100.tdp + 1e-9
    assert p[-1] >= 0


def test_time_model_regimes():
    dev = TESLA_V100
    # regime (b): memory bound with headroom -> flat until the knee
    prof_b = WorkloadProfile("b", t_mem=1.0, t_issue=0.4)
    f = dev.frequencies()
    t = prof_b.time(f, dev)
    assert t[0] == pytest.approx(1.0, rel=0.02)
    knee_f = 0.4 ** (1 / dev.issue_superlinearity) * dev.f_max
    above = f > knee_f * 1.05
    assert np.allclose(t[above], t[0], rtol=0.02)
    assert t[-1] > 2.0                            # deep slowdown at f_min
    assert prof_b.regime() == "b"

    # regime (c): core-clocked resource saturated at f_max
    prof_c = WorkloadProfile("c", t_mem=1.0, t_cache=1.02)
    t_c = prof_c.time(f, dev)
    assert np.all(np.diff(t_c) >= -1e-12)         # rises with every step down
    assert prof_c.regime() == "c"

    # regime (a): contention relief -> slightly faster below f_max
    prof_a = WorkloadProfile("a", t_mem=1.0, t_issue=0.3, contention=0.02)
    t_a = prof_a.time(f, dev)
    assert t_a.min() < t_a[0]
    assert prof_a.regime() == "a"


def test_energy_u_shape_and_optimal_interior():
    """Paper Fig. 7: E(f) is U-shaped with an interior minimum."""
    case = FFTCase(n=2**14)
    prof = fft_workload(case, TESLA_V100)
    res = sweep(prof, TESLA_V100)
    energies = np.array([p.energy for p in res.points])
    i_opt = int(np.argmin(energies))
    assert 0 < i_opt < len(energies) - 1          # interior minimum
    assert res.optimal.energy < res.boost.energy


def test_eq5_eq6_fft_metrics():
    assert fft_flops(1024) == pytest.approx(5 * 1024 * 10)
    assert ffts_per_batch(2e9, 2**14, 8) == int(2e9 // (2**14 * 8))


def test_energy_from_trace_matches_analytic():
    p = np.full(100, 200.0)
    assert energy_from_trace(p, 0.01) == pytest.approx(200.0 * 1.0)


def test_driver_cap_titan_v():
    """Paper Sec. 4: Titan V compute clocks are capped at 1335 MHz."""
    prof = fft_workload(FFTCase(n=2**14), TITAN_V)
    res = sweep(prof, TITAN_V, driver_cap_mhz=TITAN_V_DRIVER_CAP_MHZ)
    assert max(p.f for p in res.points) <= TITAN_V_DRIVER_CAP_MHZ


def test_sweep_respects_time_budget():
    prof = fft_workload(FFTCase(n=2**14), JETSON_NANO)
    tight = sweep(prof, JETSON_NANO, time_budget=0.05)
    loose = sweep(prof, JETSON_NANO)
    assert tight.slowdown <= 0.05 + 1e-9
    assert loose.optimal.energy <= tight.optimal.energy + 1e-12


def test_realtime_sizing():
    assert extra_hardware(0.6) == pytest.approx(0.6)
    assert extra_hardware(0.6, margin=0.6) == pytest.approx(0.0)
    assert devices_required(10, 0.6) == 16
    b = RealTimeBudget(t_acquire=1.0, t_process=0.8)
    assert b.speedup == pytest.approx(1.25)
    assert b.is_realtime(0.2)
    assert not b.is_realtime(0.3)


def test_pipeline_share_arithmetic():
    """Sec. 6.2: 60% FFT share x I_ef 1.5 -> ~1.29 composite gain."""
    assert predicted_pipeline_i_ef(0.60, 1.5) == pytest.approx(1.25, abs=0.05)
    assert predicted_pipeline_i_ef(1.0, 1.5) == pytest.approx(1.5)
    assert predicted_pipeline_i_ef(0.0, 1.5) == pytest.approx(1.0)


def test_scheduler_stage_locking():
    dev = TESLA_V100
    sched = DVFSScheduler(dev)
    fft_prof = fft_workload(FFTCase(n=2**14), dev)
    rest = WorkloadProfile("rest", t_mem=fft_prof.t_mem * 0.6,
                           t_issue=fft_prof.t_mem * 0.55,
                           flops=fft_prof.flops * 0.3)
    opt = sweep(fft_prof, dev).optimal.f
    stages = sched.plan([fft_prof, rest], locked={fft_prof.name: opt})
    rep = sched.evaluate_pipeline(stages)
    assert rep.i_ef > 1.05                       # composite saving exists
    # composite gain must be smaller than the FFT-only gain
    assert rep.i_ef < sweep(fft_prof, dev).i_ef_boost
    t, p, f = sched.power_trace(stages)
    assert len(t) == len(p) == len(f)
    assert set(np.unique(f)) == {opt, dev.f_max}


def test_tpu_device_roofline_constants():
    assert TPU_V5E.peak_flops == pytest.approx(197e12)
    assert TPU_V5E.hbm_bandwidth == pytest.approx(819e9)
    assert TPU_V5E.link_bandwidth == pytest.approx(50e9)


def test_spec_for_device_kind_knows_v5e():
    """The kind string a v5e reports to JAX."""
    from repro.core.hardware import spec_for_device_kind
    assert spec_for_device_kind("TPU v5 lite") is TPU_V5E


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v5", "TPU v6 lite"])
def test_spec_for_device_kind_rejects_other_tpus(kind):
    """Another chip priced with the v5e model would be silently wrong."""
    from repro.core.hardware import spec_for_device_kind
    with pytest.raises(ValueError, match=kind):
        spec_for_device_kind(kind)
