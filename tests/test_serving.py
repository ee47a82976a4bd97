"""Serving runtime: coalescing, plan/sweep caching, work stealing,
end-to-end correctness against the single-device oracle."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import dvfs
from repro.core.hardware import TESLA_V100, TPU_V5E
from repro.core.scheduler import ClockController
from repro.core.workloads import COMPLEX_BYTES
from repro.fft.plan import plan_for_length
from repro.runtime.workqueue import WorkStealingQueue
from repro.serving import FFTService, FFTRequest, coalesce
from repro.serving.request import Planes

KEY = jax.random.PRNGKey(0)


def rand_complex(shape, key=KEY):
    kr, ki = jax.random.split(key)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


def requests(sizes, n):
    return [FFTRequest(x=rand_complex((b, n), jax.random.PRNGKey(i)))
            for i, b in enumerate(sizes)]


# ---------------------------------------------------------------------------
# batch coalescing (Eq. 6 memory budget)
# ---------------------------------------------------------------------------

def test_coalescing_respects_memory_budget():
    n = 256
    budget = 8 * n * COMPLEX_BYTES["fp32"]        # room for 8 transforms
    reqs = requests([3, 3, 3, 3, 3], n)           # 15 transforms total
    batches = coalesce(reqs, device_name="d", batch_bytes=budget)
    assert sum(b.n_transforms for b in batches) == 15
    for b in batches:
        assert b.bytes <= budget
    # FIFO order preserved across the split
    flat = [r.request_id for b in batches for r in b.requests]
    assert flat == [r.request_id for r in reqs]


def test_coalescing_never_mixes_shapes():
    reqs = requests([2, 2], 256) + requests([2], 512)
    batches = coalesce(reqs, device_name="d", batch_bytes=1e9)
    assert len(batches) == 2
    assert {b.key.n for b in batches} == {256, 512}


def test_oversized_single_request_gets_own_batch():
    n = 256
    budget = 4 * n * COMPLEX_BYTES["fp32"]
    reqs = requests([2, 10, 2], n)                # middle one exceeds budget
    batches = coalesce(reqs, device_name="d", batch_bytes=budget)
    # the oversized request is not split, and not merged with others
    oversized = [b for b in batches if b.n_transforms > 4]
    assert len(oversized) == 1 and len(oversized[0].requests) == 1


def test_strictest_latency_budget_governs_batch():
    n = 128
    reqs = requests([1, 1, 1], n)
    reqs[1].latency_budget = 0.30
    reqs[2].latency_budget = 0.05
    (batch,) = coalesce(reqs, device_name="d", batch_bytes=1e9)
    assert batch.latency_budget == pytest.approx(0.05)


# ---------------------------------------------------------------------------
# plan + sweep cache (call counting)
# ---------------------------------------------------------------------------

def test_cache_hits_skip_recomputation():
    plan_calls, sweep_calls = [], []

    def counting_plan(n):
        plan_calls.append(n)
        return plan_for_length(n)

    def counting_sweep(profile, device, power_model=None, **kw):
        sweep_calls.append(profile.name)
        return dvfs.sweep(profile, device, power_model, **kw)

    svc = FFTService(TPU_V5E, plan_fn=counting_plan, sweep_fn=counting_sweep)
    for wave in range(3):                          # repeated-shape stream
        for i in range(4):
            svc.submit(rand_complex((2, 512), jax.random.PRNGKey(wave * 4 + i)))
        svc.drain()
    # one plan build and one sweep ever, despite 12 requests / 3 drains
    assert plan_calls == [512]
    assert len(sweep_calls) == 1
    stats = svc.cache.stats
    assert stats.misses == 1 and stats.hits >= 2
    assert stats.sweeps == 1 and stats.plan_builds == 1


def test_budget_reselects_from_cached_sweep_without_resweep():
    sweep_calls = []

    def counting_sweep(profile, device, power_model=None, **kw):
        sweep_calls.append(profile.name)
        return dvfs.sweep(profile, device, power_model, **kw)

    # N=8192 on the V100: the unconstrained optimum carries a small positive
    # slowdown, so a zero budget must select a higher clock.  Separate
    # drains put the two requests in separate batches (a batch runs at its
    # strictest member budget).
    svc = FFTService(TESLA_V100, sweep_fn=counting_sweep)
    tight = svc.submit(rand_complex((2, 8192)), latency_budget=0.0)
    svc.drain()
    loose = svc.submit(rand_complex((2, 8192), jax.random.PRNGKey(9)),
                       latency_budget=2.0)
    svc.drain()
    assert len(sweep_calls) == 1                  # same shape: one sweep
    rt, rl = svc.receipt(tight), svc.receipt(loose)
    assert rt.clock_mhz > rl.clock_mhz
    entry = svc.cache.entry(tight.shape_key(TESLA_V100.name))
    pt = entry.sweep.at(rt.clock_mhz)
    assert pt.time / entry.sweep.boost.time - 1.0 <= 1e-9


def test_service_default_budget_not_relaxed_by_loose_neighbour():
    """A coalesced request with a loose explicit budget must not strip the
    service-default guarantee from a budget-less neighbour."""
    svc = FFTService(TESLA_V100, time_budget=0.0)
    a = svc.submit(rand_complex((1, 8192)))              # service default
    svc.submit(rand_complex((1, 8192), jax.random.PRNGKey(2)),
               latency_budget=2.0)                       # same batch, loose
    svc.drain()
    ra = svc.receipt(a)
    entry = svc.cache.entry(a.shape_key(TESLA_V100.name))
    pt = entry.sweep.at(ra.clock_mhz)
    assert pt.time / entry.sweep.boost.time - 1.0 <= 1e-9


def test_sweep_optimal_under_budget_monotone():
    from repro.core.workloads import FFTCase, fft_workload
    res = dvfs.sweep(fft_workload(FFTCase(n=2**14), TESLA_V100), TESLA_V100)
    clocks = [res.optimal_under_budget(b).f for b in (0.0, 0.02, 0.10, None)]
    assert clocks == sorted(clocks, reverse=True)
    assert res.optimal_under_budget(None).f == res.optimal.f


# ---------------------------------------------------------------------------
# work stealing
# ---------------------------------------------------------------------------

def test_work_stealing_balances_queues():
    q = WorkStealingQueue(2)
    for i in range(4):
        q.push(0, f"job{i}")                      # all work on worker 0
    got = [q.pop(1), q.pop(1)]                    # worker 1 must steal
    assert q.steals == 2
    assert got == ["job3", "job2"]                # thief takes from the back
    assert q.pop(0) == "job0"                     # owner pops FIFO
    assert q.pop(0) == "job1"
    assert q.pop(0) is None and q.pending() == 0


def test_push_least_loaded_round_robins():
    q = WorkStealingQueue(3)
    workers = [q.push_least_loaded(i) for i in range(6)]
    assert sorted(workers) == [0, 0, 1, 1, 2, 2]
    assert q.lengths() == [2, 2, 2]


# ---------------------------------------------------------------------------
# end-to-end service
# ---------------------------------------------------------------------------

def test_service_results_match_oracle():
    svc = FFTService(TPU_V5E)
    payloads = [np.asarray(rand_complex((b, 1024), jax.random.PRNGKey(b)))
                for b in (1, 3, 2)]
    reqs = [svc.submit(p) for p in payloads]
    svc.drain()
    for req, p in zip(reqs, payloads):
        r = svc.receipt(req)
        np.testing.assert_allclose(np.asarray(r.result),
                                   np.fft.fft(p, axis=-1),
                                   rtol=3e-3, atol=3e-3)
        assert r.energy_j > 0 and r.boost_energy_j >= r.energy_j
        assert r.latency >= 0 and r.clock_mhz <= TPU_V5E.f_max
    rep = svc.report()
    assert rep.n_requests == 3 and rep.n_transforms == 6
    assert rep.n_batches == 1                     # all coalesced
    assert rep.i_ef >= 1.0
    assert rep.p50_latency_s <= rep.p99_latency_s
    assert rep.joules_per_transform > 0


def test_r2c_batches_execute_real_and_pack_double():
    """R2C payloads stack as real arrays (half the device bytes) and the
    Eq. 6 coalescer fits twice as many of them per memory budget."""
    n = 256
    budget = 8 * n * COMPLEX_BYTES["fp32"]        # 8 complex transforms
    xr = jax.random.normal(KEY, (4, n))
    reqs_c = [FFTRequest(x=rand_complex((4, n))) for _ in range(4)]
    reqs_r = [FFTRequest(x=xr, transform="r2c") for _ in range(4)]
    b_c = coalesce(reqs_c, device_name="d", batch_bytes=budget)
    b_r = coalesce(reqs_r, device_name="d", batch_bytes=budget)
    assert len(b_c) == 2 and len(b_r) == 1        # 16 real transforms fit
    assert b_r[0].bytes == b_c[0].bytes           # same footprint, 2x work
    # and the executor stacks the r2c batch as a real array
    svc = FFTService(TPU_V5E)
    svc.submit(xr, transform="r2c")
    (batch,) = coalesce(svc._pending, device_name=TPU_V5E.name,
                        batch_bytes=budget)
    stacked = svc._stack(batch, svc._bucket(batch, 4))
    assert stacked.dtype == jnp.float32


def test_service_r2c_requests_halve_energy():
    """R2C requests serve through their own plan/sweep cache entry and
    cost about half the modelled energy of C2C at the same length."""
    n = 1024
    svc = FFTService(TPU_V5E)
    xr = jax.random.normal(KEY, (4, n))
    rc = svc.submit(xr, transform="r2c")
    cc = svc.submit(xr.astype(jnp.complex64))
    svc.drain()
    rec_r, rec_c = svc.receipt(rc), svc.receipt(cc)
    np.testing.assert_allclose(rec_r.result, jnp.fft.rfft(xr),
                               rtol=3e-3, atol=3e-3)
    assert rec_r.request.bytes == rec_c.request.bytes // 2
    assert rec_r.energy_j < 0.7 * rec_c.energy_j
    # distinct transforms must not share a cache entry
    assert len(svc.cache) == 2


def test_service_pulsar_requests():
    """KIND_PULSAR runs the full filterbank pipeline: the receipt's
    result is the packed sifted-candidate array and the receipt carries
    per-stage DVFS shares plus the real-time margin."""
    from repro.data.synthetic import (FilterbankSpec, InjectedPulsar,
                                      synthetic_filterbank)
    from repro.search.pipeline import DispersionPlan
    svc = FFTService(TPU_V5E)
    spec = FilterbankSpec(nchan=8, ntime=512)
    plan = DispersionPlan.from_spec(spec, n_trials=4)
    pulsar = InjectedPulsar(dm=plan.dms[2], k0=90, z=0.0, amp=0.4)
    fb = synthetic_filterbank(spec, (pulsar,), noise=1.0, seed=0)
    req = svc.submit(fb, kind="pulsar", n_harmonics=4, templates=5,
                     dm_trials=4)
    svc.drain()
    r = svc.receipt(req)
    # Packed candidates: (rows, k, 5) = (dm, template, bin, level, snr).
    assert r.result.shape == (1, 16, 5)
    top = np.asarray(r.result)[0, 0]
    assert top[0] == 2                            # the injected DM trial
    assert top[1] == 2                            # z=0 -> centre template
    assert top[2] == 90                           # the injected bin
    assert top[4] > 25.0
    # Per-stage DVFS receipts for all four stages.
    assert [s.name for s in r.stages] == ["dedisp", "fdas",
                                          "harmonic-sum", "sift"]
    assert all(s.clock_mhz > 0 and s.energy_j > 0 for s in r.stages)
    assert r.realtime_margin is not None and r.realtime_margin > 0
    # Plain FFT receipts carry no stage breakdown.
    other = svc.submit(np.asarray(jax.random.normal(KEY, (2, 256)),
                                  dtype=np.complex64))
    svc.drain()
    assert svc.receipt(other).stages is None


def test_clock_controller_pairs_lock_and_reset():
    ctrl = ClockController(TPU_V5E)
    with ctrl.locked(800.0):
        assert ctrl.current_f == 800.0
        with ctrl.locked(600.0):                  # nested lock restores outer
            assert ctrl.current_f == 600.0
        assert ctrl.current_f == 800.0
    assert ctrl.current_f == TPU_V5E.f_max
    assert ctrl.lock_count == 2
    actions = [e.action for e in ctrl.events]
    assert actions == ["lock", "lock", "reset", "reset"]


def test_service_clock_locks_bracket_batches():
    svc = FFTService(TPU_V5E)
    svc.submit(rand_complex((1, 256)))
    svc.submit(rand_complex((1, 512), jax.random.PRNGKey(1)))
    svc.drain()
    rep = svc.report()
    assert rep.n_batches == 2
    assert rep.clock_locks == 2                   # one lock/reset per batch
    assert svc.clock.current_f == TPU_V5E.f_max   # always reset after


def test_malformed_payload_rejected_at_submit():
    svc = FFTService(TPU_V5E)
    with pytest.raises(ValueError, match="payload"):
        svc.submit(np.float32(5.0))               # 0-d scalar
    with pytest.raises(ValueError, match="precision"):
        svc.submit(np.zeros((1, 8), np.complex64), precision="fp8")


def test_failed_batch_requeues_unserved_requests():
    svc = FFTService(TPU_V5E)
    ok = svc.submit(rand_complex((1, 128)))
    bad = svc.submit(rand_complex((1, 256), jax.random.PRNGKey(1)))
    boom = RuntimeError("injected device failure")
    real_execute = svc._execute

    def flaky(batch, worker, device):
        if batch.key.n == 256:
            raise boom
        real_execute(batch, worker, device)

    svc._execute = flaky
    with pytest.raises(RuntimeError):
        svc.drain()
    # the healthy request was served; the failed one is re-queued, and no
    # stale batch lingers in the dispatcher
    assert svc.receipt(ok) is not None
    assert svc.receipt(bad) is None
    assert [r.request_id for r in svc._pending] == [bad.request_id]
    assert svc.dispatcher.queue.pending() == 0
    svc._execute = real_execute
    svc.drain()                                   # next cycle serves it
    assert svc.receipt(bad) is not None


def test_receipt_retention_cap_evicts_oldest():
    svc = FFTService(TPU_V5E, max_retained_receipts=3)
    reqs = [svc.submit(rand_complex((1, 64), jax.random.PRNGKey(i)))
            for i in range(5)]
    svc.drain()
    assert len(svc.receipts) == 3
    assert svc.receipt(reqs[0]) is None           # evicted
    assert svc.receipt(reqs[-1]) is not None
    assert svc.report().n_requests == 3           # report covers the window


# ---------------------------------------------------------------------------
# batches stacked on the device from the submitted payloads
# ---------------------------------------------------------------------------

def host_stack(batch, target):
    """A batch as the service stacked it on the host before it stacked on
    the device: payloads pulled back, concatenated, cast, zero-padded."""
    key = batch.key
    rows = [np.asarray(r.x).reshape((-1, *(key.shape or (key.n,))))
            for r in batch.requests]
    x = np.concatenate(rows, axis=0)
    if key.kind == "fft" and key.transform == "c2c":
        x = x.astype(jax.dtypes.canonicalize_dtype(
            np.complex128 if key.precision == "fp64" else np.complex64))
    else:
        x = x.real.astype(np.float32)
    pad = np.zeros((target - len(x), *x.shape[1:]), x.dtype)
    return np.concatenate([x, pad], axis=0)


def _real(shape, seed):
    return np.asarray(jax.random.normal(jax.random.PRNGKey(seed), shape))


PULSAR_KW = dict(kind="pulsar", n_harmonics=4, templates=5, dm_trials=4)


@pytest.mark.parametrize("payloads,kw,target", [
    ([rand_complex((4, 64)), rand_complex((4, 64), KEY + 1)], {}, 8),
    ([np.asarray(rand_complex((4, 64))), rand_complex((2, 64), KEY + 1)],
     dict(transform="r2c"), 8),
    ([rand_complex((2, 16, 16)), rand_complex((16, 16), KEY + 1)],
     dict(ndim=2), 4),
    ([_real((8, 512), 0), _real((8, 512), 1)], PULSAR_KW, 2),
    ([rand_complex((3, 64)), rand_complex((2, 64), KEY + 1)], {}, 8),
    ([rand_complex((8, 64))], {}, 8),
], ids=["c2c", "r2c-from-complex", "fft2", "pulsar-pair", "padded-split",
        "pass-through"])
def test_device_stacked_batch_matches_host_stacking(monkeypatch, payloads,
                                                    kw, target):
    """The executable gets the bits the host stacking gave it: the same
    rows, cast and zero pad, built on the device from the payloads; a
    complex batch as its real and imaginary float32 planes."""
    svc = FFTService(TPU_V5E, devices=jax.devices()[:1])
    reqs = [svc.submit(p, **kw) for p in payloads]
    seen = []
    run = svc._run

    def capture(batch, entry, rung, x, device, **kw):
        seen.append((batch, x, np.asarray(x)))
        return run(batch, entry, rung, x, device, **kw)

    monkeypatch.setattr(svc, "_run", capture)
    svc.drain()
    ((batch, x, got),) = seen
    want = host_stack(batch, target)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    if want.dtype == np.complex64:
        assert isinstance(x, Planes)
        assert x.re.dtype == x.im.dtype == np.float32
        assert x.re.shape == x.im.shape == want.shape
    else:
        assert isinstance(x, jax.Array)
    if len(reqs) == 1:
        assert x is reqs[0].x                     # handed through
    assert all(svc.receipt(r).status == "served" for r in reqs)


@pytest.mark.parametrize("make", [
    lambda: np.array(rand_complex((3, 64))),
    lambda: np.array(rand_complex((2, 8, 16))),
    lambda: np.array(rand_complex((64,))),
    lambda: np.array(rand_complex((3, 64)), dtype=np.complex128),
    lambda: np.array(rand_complex((64, 3))).T,
], ids=["c64", "n-d", "1-d", "c128", "strided"])
def test_host_complex_payload_is_copied_bit_for_bit(make):
    """A host complex payload crosses the link as its real view and is
    split on the device into the real and imaginary planes of the array
    a complex copy gives."""
    x = make()
    svc = FFTService(TPU_V5E, devices=jax.devices()[:1])
    want = np.asarray(jnp.asarray(x))
    req = svc.submit(x, ndim=2 if x.ndim == 3 else 1)
    assert isinstance(req.x, Planes)
    assert req.x.shape == want.shape and req.x.dtype == want.dtype
    for plane, part in ((req.x.re, want.real), (req.x.im, want.imag)):
        got = np.asarray(plane)
        assert got.dtype == part.dtype and got.shape == part.shape
        assert got.tobytes() == np.ascontiguousarray(part).tobytes()
    assert req.x.nbytes == want.nbytes
    assert np.asarray(req.x).tobytes() == want.tobytes()


def _pure_jax_service(**kw):
    from repro.serving import SLO, SLOPolicy
    return FFTService(TPU_V5E, slo=SLOPolicy(default=SLO(
        deadline_s=1.0, degrade_at=0.0, degrade_hard_at=0.0,
        shed_at=None)), **kw)


def _one_device_mesh_service(**kw):
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    return FFTService(TPU_V5E, mesh=mesh, **kw)


def _complex_path(svc, batch, entry, rung, x):
    """The result the service gave before it kept complex batches in
    planes: the batch's executable, ``jax.jit(plan.fn)`` (the pure-JAX
    twin at rung 2, the mesh's sharded FFT on a mesh), on the complex
    batch stacked on the host."""
    if svc.mesh is not None:
        from repro.fft.distributed import batch_parallel_fft
        return batch_parallel_fft(x, svc.mesh, fft_fn=entry.plan)
    if rung >= 2:
        from repro.fft.plan import pallas_disabled
        with pallas_disabled():
            return svc._rung2_fn(batch.key)(x)
    return jax.jit(entry.plan.fn)(x)


def _c128(shape, seed):
    return np.asarray(rand_complex(shape, jax.random.PRNGKey(seed)),
                      np.complex128)


@pytest.mark.parametrize("make_svc,make_payloads,kw,joined", [
    (None, lambda: [np.asarray(rand_complex((2, 64), jax.random.PRNGKey(i)))
                    for i in range(4)], {}, 0),
    (None, lambda: [rand_complex((3, 64)),
                    np.asarray(rand_complex((2, 64), KEY + 1))], {}, 0),
    (None, lambda: [np.asarray(rand_complex((8, 64)))], {}, 0),
    (None, lambda: [_c128((3, 64), 0), _c128((2, 64), 1)],
     dict(precision="fp64"), 0),
    (_pure_jax_service, lambda: [rand_complex((3, 64)),
                                 np.asarray(rand_complex((2, 64), KEY + 1))],
     {}, 1),
    (_one_device_mesh_service,
     lambda: [np.asarray(rand_complex((3, 64))), rand_complex((2, 64))],
     {}, 1),
], ids=["c2c-several", "padded", "pass-through", "c128-x64", "rung2",
        "mesh"])
def test_served_results_equal_the_complex_path_bit_for_bit(
        monkeypatch, make_svc, make_payloads, kw, joined):
    """Each request's result is, bit for bit, what the complex path gave:
    the executable joins the planes where the kernel splits them, and
    real(complex(a, b)) is a.  The mesh and the pure-JAX rung take the
    batch joined once, as a complex array; every other batch reaches its
    executable in its planes, with no complex array formed before it."""
    from repro.obs import Tracer
    with jax.enable_x64(kw.get("precision") == "fp64"):
        tracer = Tracer(timer=lambda: 0.0)
        svc = (make_svc or FFTService)(devices=jax.devices()[:1],
                                       tracer=tracer)
        reqs = [svc.submit(p, **kw) for p in make_payloads()]
        seen = []
        run = svc._run

        def capture(batch, entry, rung, x, device, **kw):
            seen.append((batch, entry, rung, x))
            return run(batch, entry, rung, x, device, **kw)

        monkeypatch.setattr(svc, "_run", capture)
        svc.drain()
        ((batch, entry, rung, x),) = seen
        (stack,) = [s for s in tracer.spans if s.name == "stack"]
        assert stack.attrs["joined"] == joined
        assert isinstance(x, jax.Array if joined else Planes)
        stacked = host_stack(batch, 1 << (batch.n_transforms - 1)
                             .bit_length())
        want = np.asarray(_complex_path(svc, batch, entry, rung,
                                        jnp.asarray(stacked)))
        offset = 0
        for req in reqs:
            got = np.asarray(svc.receipt(req).result)
            part = want[offset:offset + req.batch]
            offset += req.batch
            assert got.dtype == part.dtype and got.shape == part.shape
            assert got.tobytes() == part.tobytes()
        if len(reqs) == 1:
            assert x is reqs[0].x                 # handed through


def _hlo_ops(text, *names):
    import re
    return {name: len(re.findall(rf"\s{name}\(", text)) for name in names}


def test_c2c_executable_splits_no_complex_array_before_the_kernel():
    """The service's 4096-point C2C executable, compiled on the CPU,
    takes float32 planes, and its optimized HLO holds no real or imag
    (the join of the planes and the kernel's split of the same array
    cancel) and at most one complex: the executable the service built
    for the complex batch held both."""
    svc = FFTService(TPU_V5E, devices=jax.devices()[:1])
    key = FFTRequest(x=np.zeros((8, 4096), np.complex64)).shape_key(
        TPU_V5E.name)
    entry = svc.cache.entry(key)
    plane = jax.ShapeDtypeStruct((8, 4096), jnp.float32)
    hlo = entry.fn.lower(Planes(plane, plane)).compile().as_text()
    assert "c64[8,4096]" not in hlo.split("ENTRY")[1].split("->")[0]
    ops = _hlo_ops(hlo, "real", "imag", "complex")
    assert ops["real"] == ops["imag"] == 0 and ops["complex"] <= 1, ops
    complex_in = jax.ShapeDtypeStruct((8, 4096), jnp.complex64)
    before = jax.jit(entry.plan.fn).lower(complex_in).compile().as_text()
    assert _hlo_ops(before, "real", "imag") == {"real": 1, "imag": 1}


def test_stack_compiles_once_per_bucket_and_request_size():
    """Shuffled row splits over two request sizes: once a (bucket,
    request size) pair has been stacked, stacking it again at any row
    offset builds no program."""
    from repro.obs import Tracer
    rng = np.random.default_rng(7)
    tracer = Tracer(timer=lambda: 0.0)
    svc = FFTService(TPU_V5E, devices=jax.devices()[:1], tracer=tracer)
    payloads = {3: rand_complex((3, 64)), 5: rand_complex((5, 64), KEY + 1)}
    splits = [[3, 3], [3, 5], [5, 5], [3, 3, 3], [3, 5, 3], [5, 5, 3]]
    seen, checked = set(), 0
    for i in rng.permutation(len(splits) * 2) % len(splits):
        split = [int(v) for v in rng.permutation(splits[i])]
        for size in split:
            svc.submit(payloads[size])
        n_spans = len(tracer.spans)
        svc.drain()
        (stack,) = [s for s in tracer.spans[n_spans:] if s.name == "stack"]
        bucket = 1 << (sum(split) - 1).bit_length()
        assert stack.attrs["device_stacked"] == len(split)
        pairs = {(bucket, size) for size in split}
        if pairs <= seen:
            assert stack.compiles == 0, (split, bucket)
            checked += 1
        seen |= pairs
    assert checked >= 6


# ---------------------------------------------------------------------------
# multi-device sharding vs the single-device oracle (subprocess, slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_service_matches_single_device_oracle():
    from test_distributed import run_with_devices
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.hardware import TPU_V5E
        from repro.serving import FFTService

        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        svc = FFTService(TPU_V5E, mesh=mesh)
        key = jax.random.PRNGKey(0)
        # 5 transforms: not divisible by 4 devices -> exercises padding
        x = (jax.random.normal(key, (5, 512)) +
             1j * jax.random.normal(jax.random.PRNGKey(1), (5, 512))
             ).astype(jnp.complex64)
        req = svc.submit(np.asarray(x))
        svc.drain()
        got = np.asarray(svc.receipt(req).result)
        np.testing.assert_allclose(got, np.fft.fft(np.asarray(x), axis=-1),
                                   rtol=2e-3, atol=2e-3)
        print("sharded ok")
    """, n_devices=4)


def test_batch_on_another_worker_than_its_payloads():
    """Two workers on two devices: the batch that lands on the worker not
    holding its payloads is moved there once and still matches numpy."""
    from test_distributed import run_with_devices
    out = run_with_devices("""
        import jax, numpy as np
        from repro.core.hardware import TPU_V5E
        from repro.serving import FFTService

        devs = jax.devices()
        svc = FFTService(TPU_V5E, devices=devs[:2])
        rng = np.random.default_rng(0)
        xs = [(rng.standard_normal((3, n))
               + 1j * rng.standard_normal((3, n))).astype(np.complex64)
              for n in (64, 128)]
        reqs = [svc.submit(x) for x in xs]
        svc.drain()
        for x, req in zip(xs, reqs):
            rec = svc.receipt(req)
            assert req.x.devices() == {devs[0]}
            assert rec.result.devices() == {devs[rec.worker]}
            np.testing.assert_allclose(np.asarray(rec.result),
                                       np.fft.fft(x, axis=-1),
                                       rtol=2e-3, atol=2e-3)
        print("workers", sorted(svc.receipt(r).worker for r in reqs))
    """, n_devices=2)
    assert "workers [0, 1]" in out
