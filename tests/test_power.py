"""Closed-loop power governance: sampler, watchdog, governor, site.

Covers the repro.power subsystem plus its integration satellites: the
watchdog edge cases ISSUE 8 names (stale-timeout boundary, single-sample
spike vs sustained step, dropout -> recovery re-arm), hypothesis
properties of the governor (output always inside [f_min, f_max];
monotone under a monotone power error), the bit-reproducible fallback
contract, site cap enforcement with priority-ordered shedding, the
telemetered serving receipts, the guarded-ratio conventions and the
sticky-first-sample ClockController trace.
"""
import math

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from _hyp import given, settings, st

from repro.core.energy import guarded_ratio
from repro.core.hardware import TPU_V5E
from repro.core.scheduler import ClockController
from repro.power import (DROPOUT, FRESH, HEALTHY, SPIKE, STALE, SUSPECT,
                         UNHEALTHY, FleetTelemetry, GovernorConfig,
                         PowerGovernor, PowerReading, SimulatedPowerSampler,
                         SiteBudgetScheduler, SitePipeline, TelemetryRing,
                         TelemetryWatchdog)
from repro.runtime.faults import (SENSOR_DROPOUT, SENSOR_SPIKE, SENSOR_STALE,
                                  FaultEvent, FaultPlan)

DEV = TPU_V5E
FALLBACK = 1020.0


def reading(p, t=0.0, dev=0):
    return PowerReading(device_index=dev, t=t, power_w=p)


# ---------------------------------------------------------------------------
# sampler + ring
# ---------------------------------------------------------------------------

class TestSampler:
    def test_same_seed_reproduces_every_reading(self):
        a = SimulatedPowerSampler(DEV, seed=7, drift_w=3.0)
        b = SimulatedPowerSampler(DEV, seed=7, drift_w=3.0)
        for k in range(10):
            assert a.sample(0, 0.1 * k) == b.sample(0, 0.1 * k)

    def test_device_streams_are_interleaving_independent(self):
        a = SimulatedPowerSampler(DEV, seed=3)
        b = SimulatedPowerSampler(DEV, seed=3)
        # a samples device 0 five times, then device 1; b interleaves.
        seq_a = [a.sample(0, 0.1 * k) for k in range(5)]
        b.sample(1, 0.0)
        seq_b = [b.sample(0, 0.1 * k) for k in range(5)]
        assert seq_a == seq_b

    def test_noise_bounded_by_noise_frac(self):
        s = SimulatedPowerSampler(DEV, seed=1, noise_frac=0.02)
        truth = s.truth_w(0)
        for k in range(50):
            r = s.sample(0, 0.0)
            assert abs(r.power_w - truth) <= 0.02 * truth + 1e-9

    def test_fault_plan_corrupts_readings(self):
        plan = FaultPlan(events=[FaultEvent(SENSOR_DROPOUT, batch_id=0),
                                 FaultEvent(SENSOR_SPIKE, batch_id=1),
                                 FaultEvent(SENSOR_STALE, batch_id=3)])
        s = SimulatedPowerSampler(DEV, seed=1, fault_plan=plan)
        assert math.isnan(s.sample(0, 0.0, token=0).power_w)
        assert s.sample(0, 0.1, token=1).power_w == pytest.approx(
            2.0 * DEV.tdp)
        ok = s.sample(0, 0.2, token=2)
        stale = s.sample(0, 0.3, token=3)
        assert stale == ok                   # frozen value AND timestamp

    def test_stale_needs_a_previous_reading(self):
        plan = FaultPlan(events=[FaultEvent(SENSOR_STALE, batch_id=0)])
        s = SimulatedPowerSampler(DEV, seed=1, fault_plan=plan)
        r = s.sample(0, 0.0, token=0)        # nothing to replay yet
        assert r.ok and plan.pending() == 1

    def test_ring_is_bounded_and_counts_drops(self):
        ring = TelemetryRing(capacity=4)
        for k in range(10):
            ring.push(reading(100.0 + k, t=0.1 * k))
        assert len(ring) == 4
        assert ring.pushed == 10 and ring.dropped == 6
        assert ring.latest().power_w == 109.0
        assert [r.power_w for r in ring.window(2)] == [108.0, 109.0]


# ---------------------------------------------------------------------------
# watchdog: classification edge cases + health state machine
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_stale_timeout_boundary_is_exclusive(self):
        dog = TelemetryWatchdog(DEV, stale_timeout_s=0.05)
        # age == timeout is still fresh; strictly older is stale.
        # (t=0 keeps the age arithmetic exact in binary floating point.)
        assert dog.classify(reading(150.0, t=0.0), now=0.05) == FRESH
        assert dog.classify(reading(150.0, t=0.0), now=0.0500001) == STALE

    def test_dropout_and_envelope_spike(self):
        dog = TelemetryWatchdog(DEV, envelope_frac=1.25)
        assert dog.classify(reading(float("nan")), now=0.0) == DROPOUT
        assert dog.classify(reading(-1.0), now=0.0) == SPIKE
        assert dog.classify(reading(1.25 * DEV.tdp + 1.0), now=0.0) == SPIKE

    def test_single_sample_spike_vs_sustained_step(self):
        # A one-sample glitch is flagged twice (up AND back down); a
        # sustained step is flagged exactly once, then accepted.
        glitch = TelemetryWatchdog(DEV, step_w=50.0)
        labels = [glitch.observe(reading(p, t=0.1 * k), now=0.1 * k)[0]
                  for k, p in enumerate([150.0, 151.0, 230.0, 150.0, 151.0])]
        assert labels == [FRESH, FRESH, SPIKE, SPIKE, FRESH]

        step = TelemetryWatchdog(DEV, step_w=50.0)
        labels = [step.observe(reading(p, t=0.1 * k), now=0.1 * k)[0]
                  for k, p in enumerate([150.0, 151.0, 230.0, 231.0, 230.0])]
        assert labels == [FRESH, FRESH, SPIKE, FRESH, FRESH]

    def test_dropout_recovery_rearm(self):
        dog = TelemetryWatchdog(DEV, unhealthy_after=3, rearm_after=2)
        assert dog.health == HEALTHY
        for k in range(3):
            dog.observe(reading(float("nan"), t=0.1 * k), now=0.1 * k)
        assert dog.health == UNHEALTHY and dog.unhealthy_entries == 1
        # One fresh reading is not enough to re-arm...
        dog.observe(reading(150.0, t=0.3), now=0.3)
        assert dog.health == UNHEALTHY
        # ...two consecutive fresh readings are.
        dog.observe(reading(150.5, t=0.4), now=0.4)
        assert dog.health == HEALTHY and dog.healthy

    def test_suspect_after_one_bad_counts_as_usable(self):
        dog = TelemetryWatchdog(DEV)
        dog.observe(reading(float("nan")), now=0.0)
        assert dog.health == SUSPECT and dog.healthy

    def test_rearm_counter_resets_on_interleaved_bad(self):
        dog = TelemetryWatchdog(DEV, unhealthy_after=2, rearm_after=2)
        dog.observe(reading(float("nan"), t=0.0), now=0.0)
        dog.observe(reading(float("nan"), t=0.1), now=0.1)
        assert dog.health == UNHEALTHY
        dog.observe(reading(150.0, t=0.2), now=0.2)
        dog.observe(reading(float("nan"), t=0.3), now=0.3)   # resets streak
        dog.observe(reading(150.0, t=0.4), now=0.4)
        assert dog.health == UNHEALTHY                       # streak is 1
        dog.observe(reading(150.0, t=0.5), now=0.5)
        assert dog.health == HEALTHY


# ---------------------------------------------------------------------------
# governor: guards, fallback contract, hypothesis properties
# ---------------------------------------------------------------------------

def governor(**kw):
    kw.setdefault("target_w", 150.0)
    kw.setdefault("fallback_mhz", FALLBACK)
    return PowerGovernor(DEV, **kw)


class TestGovernor:
    def test_starts_at_fallback_and_validates_it(self):
        assert governor().f_mhz == FALLBACK
        with pytest.raises(ValueError):
            governor(fallback_mhz=DEV.f_max + 100.0)

    def test_hysteresis_dead_band_holds(self):
        gov = governor(config=GovernorConfig(hysteresis_w=2.0))
        f0 = gov.f_mhz
        assert gov.step(149.0) == f0 and gov.mode == "hold"
        assert gov.integral_w == 0.0         # no windup while holding

    def test_slew_rate_limit_bounds_every_move(self):
        cfg = GovernorConfig(slew_mhz_per_tick=65.0)
        gov = governor(config=cfg)
        prev = gov.f_mhz
        for measured in [50.0, 40.0, 300.0, 30.0, 150.0, 90.0]:
            f = gov.step(measured)
            assert abs(f - prev) <= cfg.slew_mhz_per_tick + 1e-9
            prev = f

    def test_missing_sample_holds_without_windup(self):
        gov = governor()
        gov.step(100.0)                      # build some integral
        integral = gov.integral_w
        f = gov.f_mhz
        assert gov.step(None) == f and gov.mode == "hold"
        assert gov.step(float("nan")) == f
        assert gov.integral_w == integral

    def test_unhealthy_pins_bit_exact_fallback_and_resets(self):
        gov = governor()
        for _ in range(5):
            gov.step(60.0)                   # wind up, move off fallback
        assert gov.f_mhz != FALLBACK and gov.integral_w != 0.0
        f = gov.step(60.0, healthy=False)
        assert f == FALLBACK                 # exact, not approx: stored value
        assert gov.integral_w == 0.0 and gov.in_fallback
        assert gov.fallback_engagements == 1
        gov.step(None, healthy=False)
        assert gov.fallback_engagements == 1  # same engagement, no re-count

    def test_fallback_reproducible_across_runs(self):
        def run():
            gov = governor()
            out = []
            for k in range(20):
                healthy = not 8 <= k < 12
                out.append(gov.step(100.0 + k, healthy=healthy))
            return out
        assert run() == run()

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.one_of(
        st.none(),
        st.floats(min_value=-1e3, max_value=1e4,
                  allow_nan=False, allow_infinity=False)),
        min_size=1, max_size=40),
        st.booleans())
    def test_output_always_within_clock_bounds(self, measured, flip):
        gov = governor()
        for k, m in enumerate(measured):
            healthy = not (flip and k % 3 == 0)
            f = gov.step(m, healthy=healthy)
            assert DEV.f_min <= f <= DEV.f_max

    @settings(max_examples=50, deadline=None)
    @given(st.floats(min_value=0.0, max_value=500.0, allow_nan=False),
           st.floats(min_value=0.0, max_value=500.0, allow_nan=False))
    def test_single_step_monotone_in_power_error(self, m_low, m_high):
        # Lower measured power (larger error) never commands a lower
        # clock than higher measured power, from identical fresh state.
        lo, hi = min(m_low, m_high), max(m_low, m_high)
        f_hi_err = governor().step(lo)       # bigger error: speed up more
        f_lo_err = governor().step(hi)
        assert f_hi_err >= f_lo_err


# ---------------------------------------------------------------------------
# telemetry bundle
# ---------------------------------------------------------------------------

class TestFleetTelemetry:
    def test_fresh_read_exposes_measured_w(self):
        tel = FleetTelemetry(DEV, SimulatedPowerSampler(DEV, seed=2))
        tr = tel.read(0, 0.0)
        assert tr.fresh and tr.measured_w == tr.reading.power_w
        assert tel.healthy(0)

    def test_non_fresh_read_withholds_measured_w(self):
        plan = FaultPlan(events=[FaultEvent(SENSOR_DROPOUT, batch_id=0)])
        tel = FleetTelemetry(
            DEV, SimulatedPowerSampler(DEV, seed=2, fault_plan=plan))
        tr = tel.read(0, 0.0, token=0)
        assert tr.label == DROPOUT and tr.measured_w is None

    def test_summary_aggregates_per_device_watchdogs(self):
        tel = FleetTelemetry(DEV, SimulatedPowerSampler(DEV, seed=2))
        tel.read(0, 0.0)
        tel.read(1, 0.0)
        s = tel.summary()
        assert s["reads"] == 2 and s["labels"][FRESH] == 2
        assert s["health"] == {0: HEALTHY, 1: HEALTHY}

    def test_unread_devices_are_healthy(self):
        tel = FleetTelemetry(DEV, SimulatedPowerSampler(DEV, seed=2))
        assert tel.healthy(5)


# ---------------------------------------------------------------------------
# site budget scheduler
# ---------------------------------------------------------------------------

def make_site(seed=0, fault_plan=None, cap=1400.0, hard=1500.0, n=8):
    pipes = [SitePipeline(name=f"p{i}", device_index=i,
                          priority=(i % 4) + 1, fallback_mhz=FALLBACK,
                          u_core=0.9, u_mem=0.8)
             for i in range(n)]
    return SiteBudgetScheduler(DEV, pipes, site_cap_w=cap, hard_cap_w=hard,
                               seed=seed, fault_plan=fault_plan)


class TestSite:
    def test_cap_never_exceeded_and_converges(self):
        site = make_site()
        ticks = site.run(60, dt=0.1)
        assert max(t.truth_w for t in ticks) <= site.site_cap_w
        assert site.first_converged_tick is not None
        assert site.first_converged_tick <= 40

    def test_digest_reproducible_across_fresh_runs(self):
        a, b = make_site(seed=5), make_site(seed=5)
        a.run(40, dt=0.1)
        b.run(40, dt=0.1)
        assert a.digest() == b.digest()

    @staticmethod
    def _storm_engages_exact_fallback_then_rearms(kind):
        plan = FaultPlan(events=[FaultEvent(kind, batch_id=k, worker=0)
                                 for k in range(10, 14)])
        site = make_site(fault_plan=plan)
        ticks = site.run(30, dt=0.1)
        fb = [k for k, t in enumerate(ticks) if t.modes[0] == "fallback"]
        assert fb, "governor never fell back under the sensor storm"
        assert all(ticks[k].clocks_mhz[0] == FALLBACK for k in fb)
        assert ticks[-1].health[0] == HEALTHY    # re-armed after recovery

    def test_sensor_storm_engages_exact_fallback_then_rearms(self):
        self._storm_engages_exact_fallback_then_rearms(SENSOR_SPIKE)

    @pytest.mark.parametrize("kind", [SENSOR_DROPOUT, SENSOR_STALE])
    def test_other_sensor_storms_engage_exact_fallback(self, kind):
        self._storm_engages_exact_fallback_then_rearms(kind)

    def test_shed_order_is_lowest_priority_first(self):
        # A cap whose budget (headroom * cap = 368 W) cannot hold all
        # eight f_min floors (~430 W) must shed priority-1 names first.
        site = make_site(cap=400.0, hard=450.0)
        shed = [p.name for p in site.shed]
        assert shed, "tight cap must shed"
        survivors = {p.priority for p in site.active}
        victims = {p.priority for p in site.shed}
        assert max(victims) <= min(survivors)

    def test_emergency_rung_floors_sheds_and_restores(self):
        site = make_site()
        site.run(20, dt=0.1)
        pre = len(site.active)
        site.site_cap_w, site.hard_cap_w = 850.0, 900.0
        ticks = site.run(20, dt=0.1)[20:]
        assert site.emergencies >= 1
        assert len(site.active) < pre
        emergency_tick = next(t for t in ticks if t.emergency)
        active_names = set(emergency_tick.active)
        floored = [f for p, f in zip(site.pipelines,
                                     emergency_tick.clocks_mhz)
                   if p.name in active_names]
        assert all(f == DEV.f_min for f in floored)
        assert ticks[-1].truth_w <= site.hard_cap_w

    def test_distinct_devices_required(self):
        pipes = [SitePipeline(name="a", device_index=0, priority=1,
                              fallback_mhz=FALLBACK),
                 SitePipeline(name="b", device_index=0, priority=2,
                              fallback_mhz=FALLBACK)]
        with pytest.raises(ValueError):
            SiteBudgetScheduler(DEV, pipes, site_cap_w=400.0)


# ---------------------------------------------------------------------------
# serving integration: measured_energy_j on receipts
# ---------------------------------------------------------------------------

class TestServingIntegration:
    def _service(self, telemetry):
        from repro.serving.service import FFTService
        return FFTService(DEV, keep_results=False, telemetry=telemetry)

    def _submit(self, svc, n=4):
        rng = np.random.default_rng(0)
        for _ in range(n):
            svc.submit((rng.standard_normal((2, 256))
                        + 1j * rng.standard_normal((2, 256))
                        ).astype(np.complex64))

    def test_unmetered_service_reports_none(self):
        svc = self._service(None)
        self._submit(svc)
        for r in svc.drain():
            assert r.measured_energy_j is None
            assert r.energy_error_frac is None
        assert svc.report().telemetry is None

    def test_fresh_telemetry_prices_receipts_at_measured_power(self):
        tel = FleetTelemetry.for_serving(DEV, seed=9, noise_frac=0.01)
        svc = self._service(tel)
        self._submit(svc)
        receipts = svc.drain()
        assert receipts
        for r in receipts:
            assert r.measured_energy_j is not None
            # within the sampler's noise band of the modelled energy
            assert abs(r.energy_error_frac) <= 0.011
        rep = svc.report()
        assert rep.measured_energy_j > 0.0
        assert rep.telemetry["labels"][FRESH] == rep.telemetry["reads"]

    def test_faulted_telemetry_falls_back_to_modelled_energy(self):
        # Every sample drops out: measured_energy_j must equal the
        # modelled energy_j exactly (never freewheel on bad telemetry).
        plan = FaultPlan(events=[FaultEvent(SENSOR_DROPOUT)
                                 for _ in range(64)])
        tel = FleetTelemetry.for_serving(DEV, seed=9, fault_plan=plan)
        svc = self._service(tel)
        self._submit(svc)
        for r in svc.drain():
            assert r.measured_energy_j == r.energy_j


# ---------------------------------------------------------------------------
# satellites: guarded ratios + sticky-first-sample clock trace
# ---------------------------------------------------------------------------

class TestGuardedRatio:
    def test_zero_over_zero_returns_on_zero(self):
        assert guarded_ratio(0.0, 0.0) == 1.0
        assert guarded_ratio(0.0, 0.0, on_zero=0.0) == 0.0
        assert math.isnan(guarded_ratio(0.0, 0.0, on_zero=float("nan")))

    def test_nonzero_over_zero_is_a_contradiction(self):
        assert math.isnan(guarded_ratio(3.0, 0.0))
        assert math.isnan(guarded_ratio(-1.0, 0.0, on_zero=0.0))

    def test_normal_division(self):
        assert guarded_ratio(3.0, 4.0) == 0.75

    def test_report_conventions(self):
        from repro.serving.cache import CacheStats
        from repro.serving.service import ServiceReport
        empty = ServiceReport(
            n_requests=0, n_transforms=0, n_batches=0, wall_s=0.0,
            energy_j=0.0, boost_energy_j=0.0, p50_latency_s=0.0,
            p99_latency_s=0.0, mean_latency_s=0.0, cache=CacheStats(),
            steals=0, clock_locks=0)
        assert empty.availability == 1.0     # no demand, nothing unserved
        assert empty.i_ef == 1.0
        assert empty.throughput_tps == 0.0
        assert empty.joules_per_transform == 0.0
        assert CacheStats().hit_rate == 0.0  # no lookups, no hits

    def test_shed_receipt_i_ef_is_one(self):
        from repro.serving.request import FFTRequest, RequestReceipt
        req = FFTRequest(x=np.zeros((1, 8), dtype=np.complex64))
        shed = RequestReceipt.make_shed(req, "admission:deadline", 0.0)
        assert shed.i_ef_boost == 1.0


class FakeTimer:
    def __init__(self, dt=1.0):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


class TestClockTrace:
    def test_trace_starts_from_boost_even_after_eviction(self):
        ctrl = ClockController(DEV, timer=FakeTimer(), max_events=4)
        for f in (900.0, 1000.0, 1100.0, 1200.0):
            with ctrl.locked(f):
                pass
        assert len(ctrl.events) == 4         # deque dropped the oldest
        ts, fs = ctrl.trace()
        assert ts[0] == 0.0 and fs[0] == DEV.f_max
        assert len(ts) == 5                  # sticky first + 4 retained

    def test_unbounded_trace_also_prepends_initial_state(self):
        ctrl = ClockController(DEV, timer=FakeTimer())
        with ctrl.locked(800.0):
            pass
        ts, fs = ctrl.trace()
        assert fs[0] == DEV.f_max and fs[1] == 800.0
        assert fs[-1] == DEV.f_max           # reset restored boost
