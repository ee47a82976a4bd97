"""Robustness layer: fault injection, breakers, retries, admission
control, the degradation ladder and the every-request-gets-a-receipt
invariant (docs/robustness.md)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.hardware import TPU_V5E
from repro.runtime.faults import (CLOSED, FAIL_CLOCK_LOCK, FAIL_PLAN_BUILD,
                                  HALF_OPEN, KILL_DEVICE, OPEN, STALL_WORKER,
                                  CircuitBreaker, DeviceLostError,
                                  DrainDeadlineError, FaultEvent, FaultPlan,
                                  RetryPolicy)
from repro.serving import (RUNG_BOOST_HEURISTIC, RUNG_PURE_JAX, SLO,
                           FFTService, SLOPolicy, max_rung_for_kind)

KEY = jax.random.PRNGKey(0)


def rand_complex(shape, key=KEY):
    kr, ki = jax.random.split(key)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


class FakeTimer:
    """Deterministic clock: advances ``dt`` per call (0 = frozen)."""

    def __init__(self, dt=0.0, t0=0.0):
        self.t = t0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t

    def advance(self, dt):
        self.t += dt


def service(n_workers=2, timer=None, **kw):
    return FFTService(TPU_V5E, devices=[None] * n_workers,
                      timer=timer if timer is not None else FakeTimer(),
                      **kw)


# ---------------------------------------------------------------------------
# FaultPlan / RetryPolicy / CircuitBreaker units
# ---------------------------------------------------------------------------

def test_fault_events_fire_exactly_once():
    plan = FaultPlan([FaultEvent(KILL_DEVICE, batch_id=3),
                      FaultEvent(KILL_DEVICE)])
    assert plan.take(KILL_DEVICE, batch_id=1) is not None   # wildcard event
    assert plan.take(KILL_DEVICE, batch_id=3) is not None
    assert plan.take(KILL_DEVICE, batch_id=3) is None       # one-shot
    assert plan.pending() == 0 and plan.fired_count(KILL_DEVICE) == 2


def test_fault_event_worker_constraint():
    plan = FaultPlan([FaultEvent(STALL_WORKER, worker=1, duration=0.5)])
    assert plan.take(STALL_WORKER, batch_id=0, worker=0) is None
    ev = plan.take(STALL_WORKER, batch_id=0, worker=1)
    assert ev is not None and ev.duration == 0.5


def test_fault_plan_generation_is_seed_deterministic():
    a = FaultPlan.generate(seed=7, n_batches=200)
    b = FaultPlan.generate(seed=7, n_batches=200)
    assert a.events == b.events
    # the pinned one-of-each events cover the chaos harness requirement
    kinds = {ev.kind for ev in a.events}
    assert {KILL_DEVICE, FAIL_CLOCK_LOCK, STALL_WORKER} <= kinds


def test_retry_backoff_is_deterministic_and_bounded():
    pol = RetryPolicy(max_retries=3, base_delay_s=0.01, max_delay_s=0.05)
    d = [pol.delay(a, token=42) for a in (1, 2, 3)]
    assert d == [pol.delay(a, token=42) for a in (1, 2, 3)]
    assert d != [pol.delay(a, token=43) for a in (1, 2, 3)]  # per-work jitter
    for attempt, delay in enumerate(d, start=1):
        raw = min(0.01 * 2.0 ** (attempt - 1), 0.05)
        assert 0.5 * raw <= delay < 1.5 * raw


def test_circuit_breaker_state_machine():
    br = CircuitBreaker(failure_threshold=2, cooldown_s=1.0)
    assert br.state == CLOSED and br.allow(0.0)
    br.record_failure(0.0)
    assert br.state == CLOSED                     # below threshold
    br.record_failure(0.1)
    assert br.state == OPEN and br.opens == 1
    assert not br.allow(0.5)                      # cooling down
    assert br.would_allow(1.2) and br.state == OPEN   # pure peek
    assert br.allow(1.2) and br.state == HALF_OPEN and br.probes == 1
    assert not br.allow(1.3)                      # one probe in flight
    br.record_failure(1.4)                        # probe failed
    assert br.state == OPEN and br.opens == 2
    assert br.allow(2.5)                          # second probe
    br.record_success()
    assert br.state == CLOSED and br.allow(2.6)


# ---------------------------------------------------------------------------
# device lost mid-batch -> retried elsewhere, no request lost
# ---------------------------------------------------------------------------

def test_device_lost_mid_batch_is_retried_no_request_lost():
    plan = FaultPlan([FaultEvent(KILL_DEVICE, batch_id=0)])
    svc = service(n_workers=2, fault_plan=plan)
    reqs = [svc.submit(rand_complex((2, 256), jax.random.PRNGKey(i)))
            for i in range(3)]
    receipts = svc.drain()
    assert len(receipts) == len(reqs)             # exactly one receipt each
    assert all(r.status == "served" for r in receipts)
    assert all(r.outcome == "retried" and r.retries == 1 for r in receipts)
    assert plan.fired_count(KILL_DEVICE) == 1
    ref = np.fft.fft(np.asarray(reqs[0].x), axis=-1)
    np.testing.assert_allclose(np.asarray(receipts[0].result), ref,
                               rtol=1e-4, atol=1e-3)
    rep = svc.report()
    assert rep.retried == 3 and rep.availability == 1.0


def test_device_lost_at_the_wait_is_retried_like_at_launch():
    """A device error that surfaces while the launched batch is waited on
    takes the launch's path: redistribution, then a retry."""
    svc = service(n_workers=2)
    finish = svc._finish
    lost = []

    def lose_first(f):
        if not lost:
            lost.append(f.worker)
            raise DeviceLostError(f.worker)
        return finish(f)

    svc._finish = lose_first
    reqs = [svc.submit(rand_complex((2, 256), jax.random.PRNGKey(i)))
            for i in range(3)]
    receipts = svc.drain()
    assert len(receipts) == len(reqs)
    assert all(r.status == "served" and r.retries == 1 for r in receipts)
    rep = svc.report()
    assert rep.redistributions == 1 and rep.retried == 3


def test_retries_exhausted_sheds_with_receipts():
    plan = FaultPlan([FaultEvent(KILL_DEVICE, batch_id=0)] * 3)
    svc = service(n_workers=2, fault_plan=plan)   # default max_retries=2
    reqs = [svc.submit(rand_complex((1, 256), jax.random.PRNGKey(i)))
            for i in range(2)]
    receipts = svc.drain()
    assert len(receipts) == len(reqs)
    assert all(r.status == "shed" and r.outcome == "shed" for r in receipts)
    assert all(r.reason == "fault:retries-exhausted" for r in receipts)
    rep = svc.report()
    assert rep.shed == 2 and rep.fault_shed == 2
    assert rep.availability == 0.0
    # the service is not wedged: the next wave serves normally
    ok = svc.submit(rand_complex((1, 256)))
    (r,) = svc.drain()
    assert r.status == "served" and svc.receipt(ok) is r


# ---------------------------------------------------------------------------
# clock-lock failure -> boost, not crash
# ---------------------------------------------------------------------------

def test_failed_clock_lock_degrades_to_boost():
    plan = FaultPlan([FaultEvent(FAIL_CLOCK_LOCK, batch_id=0)])
    svc = service(n_workers=1, fault_plan=plan)
    svc.submit(rand_complex((2, 512)))
    (r,) = svc.drain()
    assert r.status == "served"
    assert r.rung == RUNG_BOOST_HEURISTIC
    assert r.reason == "fault:clock-lock-failed"
    assert r.clock_mhz == pytest.approx(TPU_V5E.f_max)
    assert svc.clock.lock_count == 0              # the lock was never taken
    # same shape, next batch: the tuned DVFS path is back
    svc.submit(rand_complex((2, 512), jax.random.PRNGKey(1)))
    (r2,) = svc.drain()
    assert r2.rung == 0 and r2.reason is None
    assert svc.clock.lock_count == 1


def test_plan_build_failure_walks_down_the_ladder():
    plan = FaultPlan([FaultEvent(FAIL_PLAN_BUILD, batch_id=0)])
    svc = service(n_workers=1, fault_plan=plan)
    req = svc.submit(rand_complex((2, 512)))
    (r,) = svc.drain()
    assert r.status == "served" and r.rung == RUNG_BOOST_HEURISTIC
    assert r.reason == "fault:plan-build-failed"
    assert svc.cache.stats.degraded_builds == 1
    ref = np.fft.fft(np.asarray(req.x), axis=-1)
    np.testing.assert_allclose(np.asarray(r.result), ref,
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# stalls, redistribution, drain deadline
# ---------------------------------------------------------------------------

def test_stalled_worker_work_is_redistributed():
    plan = FaultPlan([FaultEvent(STALL_WORKER, batch_id=0, duration=1e9)])
    svc = service(n_workers=2, fault_plan=plan)
    reqs = [svc.submit(rand_complex((1, 256), jax.random.PRNGKey(i)))
            for i in range(2)]
    receipts = svc.drain()
    assert len(receipts) == len(reqs)
    assert all(r.status == "served" for r in receipts)
    assert svc.stalls_honoured == 1
    assert svc.redistributions >= 1
    assert all(r.worker == 1 for r in receipts)   # worker 0 is wedged


def test_drain_deadline_surfaces_stuck_shape():
    plan = FaultPlan([FaultEvent(STALL_WORKER, batch_id=0, duration=1e9)])
    svc = service(n_workers=1, timer=FakeTimer(dt=1.0), fault_plan=plan)
    svc.submit(rand_complex((1, 256)))
    with pytest.raises(DrainDeadlineError) as err:
        svc.drain(deadline_s=25.0)
    assert err.value.deadline_s == 25.0
    assert [k.n for k in err.value.stuck] == [256]
    # the unserved request was re-queued, not dropped
    assert len(svc._pending) == 1


def test_breaker_quarantines_then_readmits_after_probe():
    timer = FakeTimer(dt=0.0, t0=1.0)             # frozen; advanced by hand
    plan = FaultPlan([FaultEvent(KILL_DEVICE, batch_id=0, worker=0)])
    svc = service(n_workers=2, timer=timer, fault_plan=plan,
                  breaker_threshold=1, breaker_cooldown_s=10.0)
    svc.submit(rand_complex((1, 256)))
    (r,) = svc.drain()                            # kill -> open -> retried
    assert r.retries == 1 and svc.breakers[0].state == OPEN
    # while quarantined, new work for worker 0 is pushed to worker 1
    svc.submit(rand_complex((1, 256), jax.random.PRNGKey(1)))
    (r2,) = svc.drain()
    assert r2.worker == 1 and svc.breakers[0].state == OPEN
    # after the cooldown the next batch is the probe; success re-admits
    timer.advance(60.0)
    svc.submit(rand_complex((1, 256), jax.random.PRNGKey(2)))
    (r3,) = svc.drain()
    assert r3.worker == 0 and r3.status == "served"
    assert svc.breakers[0].state == CLOSED
    assert svc.breakers[0].probes == 1
    assert svc.report().breaker_opens == 1


# ---------------------------------------------------------------------------
# admission control and the degradation ladder
# ---------------------------------------------------------------------------

def test_queue_depth_cap_sheds_with_receipts():
    policy = SLOPolicy(default=SLO(max_queue_transforms=4))
    svc = service(n_workers=1, slo=policy)
    reqs = [svc.submit(rand_complex((2, 256), jax.random.PRNGKey(i)))
            for i in range(3)]                    # 6 transforms > cap 4
    receipts = svc.drain()
    assert len(receipts) == 3                     # every request terminated
    by_req = {r.request.request_id: r for r in receipts}
    assert by_req[reqs[0].request_id].status == "served"
    assert by_req[reqs[1].request_id].status == "served"
    shed = by_req[reqs[2].request_id]
    assert shed.status == "shed"
    assert shed.reason == "admission:queue-full"
    rep = svc.report()
    assert rep.shed == 1 and rep.fault_shed == 0
    assert rep.availability == 1.0                # admission sheds excluded


def test_backlog_pressure_degrades_to_boost_heuristic():
    policy = SLOPolicy(default=SLO(deadline_s=1.0, degrade_at=0.0,
                                   degrade_hard_at=None, shed_at=None))
    svc = service(n_workers=1, slo=policy)
    svc.submit(rand_complex((2, 512)))
    (r,) = svc.drain()
    assert r.status == "served" and r.rung == RUNG_BOOST_HEURISTIC
    assert r.reason == "admission:backlog"
    assert r.clock_mhz == pytest.approx(TPU_V5E.f_max)
    assert svc.cache.stats.sweeps == 0            # sweep skipped entirely
    assert svc.cache.stats.degraded_builds == 1


def test_hard_pressure_reaches_pure_jax_rung():
    from repro.fft import plan as plan_mod
    calls = []
    orig = plan_mod._kernel_fft

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    policy = SLOPolicy(default=SLO(deadline_s=1.0, degrade_at=0.0,
                                   degrade_hard_at=0.0, shed_at=None))
    svc = service(n_workers=1, slo=policy)
    req = svc.submit(rand_complex((2, 4096), jax.random.PRNGKey(3)))
    old = plan_mod._kernel_fft
    plan_mod._kernel_fft = counting
    try:
        (r,) = svc.drain()
    finally:
        plan_mod._kernel_fft = old
    assert r.rung == RUNG_PURE_JAX and r.rung_name == "pure-jax"
    assert r.reason == "admission:backlog-hard"
    assert calls == []                            # zero Pallas launches
    ref = np.fft.fft(np.asarray(req.x), axis=-1)
    np.testing.assert_allclose(np.asarray(r.result), ref,
                               rtol=1e-4, atol=1e-2)


def test_deadline_pressure_sheds():
    policy = SLOPolicy(default=SLO(deadline_s=1e-12))
    svc = service(n_workers=1, slo=policy)
    req = svc.submit(rand_complex((2, 256)))
    (r,) = svc.drain()
    assert r.status == "shed" and r.reason == "admission:deadline"
    assert svc.receipt(req) is r
    assert svc.admission.shed == 1


def test_science_kinds_cap_at_boost_heuristic():
    assert max_rung_for_kind("fft") == RUNG_PURE_JAX
    assert max_rung_for_kind("fdas") == RUNG_BOOST_HEURISTIC
    assert max_rung_for_kind("pulsar") == RUNG_BOOST_HEURISTIC


# ---------------------------------------------------------------------------
# reproducibility: same fault-plan seed => same outcomes
# ---------------------------------------------------------------------------

def _chaos_outcomes(seed):
    svc = service(n_workers=2,
                  fault_plan=FaultPlan.generate(
                      seed, n_batches=8, kill_rate=0.2, clock_fail_rate=0.2,
                      plan_fail_rate=0.2, stall_rate=0.1,
                      stall_duration_s=0.0),
                  timer=FakeTimer(dt=1e-4))
    out = []
    for wave in range(8):
        for i in range(2):
            svc.submit(rand_complex((1, 256), jax.random.PRNGKey(wave * 2 + i)))
        out.extend((r.outcome, r.rung, r.reason) for r in svc.drain())
    return out


def test_same_fault_seed_reproduces_outcomes():
    assert _chaos_outcomes(5) == _chaos_outcomes(5)
