"""The unified observability plane (repro.obs): deterministic tracing,
the metrics registry, the kernel launch ledger, drift detection, the
structured logger, and their serving integration (docs/observability.md).
"""
import io
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.hardware import TPU_V5E
from repro.obs import (DriftDetector, FlightRecorder, LaunchLedger,
                       LaunchRecord, MetricsRegistry, Span, StructuredLogger,
                       Tracer, latency_summary, launches_digest,
                       record_launch, to_jsonl)
from repro.obs import trace as trace_mod
from repro.obs.metrics import Counter, Gauge, Histogram
from repro.power.telemetry import FleetTelemetry
from repro.runtime.faults import (ClockLockError, DeviceLostError,
                                  DrainDeadlineError, PlanBuildError,
                                  WorkerStalledError)
from repro.serving import FFTService
from repro.serving import request as request_mod

KEY = jax.random.PRNGKey(0)


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


def rand_complex(shape, key=KEY):
    kr, ki = jax.random.split(key)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


# ---------------------------------------------------------------------------
# tracer: nesting, attribute propagation, exporters
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_nesting_and_attr_inheritance(self):
        tr = Tracer(timer=FakeTimer(dt=1.0))
        with tr.span("batch", kind="fft", shape=(4, 64), rung=0,
                     clock_mhz=940.0):
            with tr.span("execute"):
                pass
            with tr.span("account", rung=1):
                pass
        by_name = {s.name: s for s in tr.spans}
        batch, execute, account = (by_name["batch"], by_name["execute"],
                                   by_name["account"])
        # children inherit every parent attr...
        assert execute.attrs["kind"] == "fft"
        assert execute.attrs["shape"] == (4, 64)
        assert execute.attrs["clock_mhz"] == 940.0
        # ...but their own keys win
        assert account.attrs["rung"] == 1 and batch.attrs["rung"] == 0
        assert execute.parent == "batch" and execute.depth == 1
        assert batch.parent is None and batch.depth == 0
        # completion order: children close before the parent
        assert [s.name for s in tr.spans] == ["execute", "account", "batch"]

    def test_durations_come_from_the_injected_clock(self):
        tr = Tracer(timer=FakeTimer(dt=0.5))
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        inner, outer = tr.spans
        # every timer() call advances 0.5: open/open/close/close
        assert inner.duration == pytest.approx(0.5)
        assert outer.duration == pytest.approx(1.5)

    def test_jsonl_digest_reproducible_and_attr_sensitive(self):
        def run(clock):
            tr = Tracer(timer=FakeTimer(dt=1.0))
            with tr.span("batch", clock_mhz=clock):
                with tr.span("execute"):
                    pass
            return tr.spans
        a, b, c = run(940.0), run(940.0), run(600.0)
        assert trace_mod.digest(a) == trace_mod.digest(b)
        assert trace_mod.digest(a) != trace_mod.digest(c)
        # one canonical JSON object per line
        assert len(to_jsonl(a).splitlines()) == 2


    def test_compile_counts_land_on_the_innermost_span(self):
        tr = Tracer(timer=FakeTimer(dt=1.0))
        fresh = jax.jit(lambda x: x * 2.5 + 0.75)
        with tr.span("outer", shape=(7,)):
            with tr.span("inner"):
                fresh(jnp.ones(7)).block_until_ready()
        inner, outer = tr.spans
        assert inner.compiles >= 1 and inner.compile_s > 0
        assert outer.compiles == 0 and outer.compile_s == 0
        # counters are neither inherited attrs nor part of the digest
        assert "compiles" not in inner.attrs
        assert "compiles" not in inner.to_dict()

    def test_annotate_opens_one_prefixed_annotation_per_span(
            self, monkeypatch):
        opened = []

        class Annotation:
            def __init__(self, name):
                opened.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        tr = Tracer(timer=FakeTimer(dt=1.0), annotate="service.")
        with tr.span("batch"):
            with tr.span("execute"):
                pass
        assert opened == ["service.batch", "service.execute"]
        plain = Tracer(timer=FakeTimer(dt=1.0))
        with plain.span("batch"):
            pass
        assert opened == ["service.batch", "service.execute"]
        assert [s.name for s in tr.spans] == ["execute", "batch"]


# ---------------------------------------------------------------------------
# flight recorder: bounded rings + per-fault snapshots
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded_per_device(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.push(Span(name=f"s{i}", t_start=float(i),
                         attrs={"worker": i % 2}))
        assert [s.name for s in fr.ring(0)] == ["s2", "s4", "s6", "s8"]
        assert len(fr.ring(1)) == 4
        assert [s.name for s in fr.ring(1)] == ["s3", "s5", "s7", "s9"]

    @pytest.mark.parametrize("make_error", [
        lambda: DeviceLostError(1),
        lambda: ClockLockError("nvml lock refused"),
        lambda: PlanBuildError("no plan for shape"),
        lambda: WorkerStalledError(2, 0.5),
        lambda: DrainDeadlineError(1.0, ["stuck-key"]),
    ], ids=["device-lost", "clock-lock", "plan-build", "worker-stalled",
            "drain-deadline"])
    def test_every_fault_kind_snapshots_live_tracers(self, make_error):
        tr = Tracer(timer=FakeTimer(dt=1.0))
        with tr.span("batch", worker=0):
            pass
        err = make_error()                 # construction triggers snapshot
        assert len(tr.flight.snapshots) == 1
        snap = tr.flight.snapshots[0]
        assert snap.error_type == type(err).__name__
        assert str(err) in snap.message or snap.message == str(err)
        assert [s.name for s in snap.spans[0]] == ["batch"]

    def test_snapshot_captures_spans_still_open_at_failure(self):
        tr = Tracer(timer=FakeTimer(dt=1.0))
        with pytest.raises(DeviceLostError):
            with tr.span("batch", worker=1):
                with tr.span("execute"):
                    raise DeviceLostError(1)
        snap = tr.flight.snapshots[0]
        assert [s.name for s in snap.open_spans] == ["batch", "execute"]

    def test_no_tracer_no_snapshot_no_error(self):
        # fault construction with no live tracer is a silent no-op
        import gc
        gc.collect()                       # drop tracers from other tests
        DeviceLostError(0)


# ---------------------------------------------------------------------------
# launch ledger: trace-time Pallas accounting
# ---------------------------------------------------------------------------

class TestLaunchLedger:
    def test_record_is_noop_without_active_capture(self):
        led = LaunchLedger()
        record_launch("fft-c2c", grid=(1,), tile=(4, 64))
        assert led.records == []

    def test_capture_records_and_counts(self):
        led = LaunchLedger()
        with led.capture():
            record_launch("fft-c2c", grid=(2,), tile=(4, 64),
                          bytes_moved=100, shape=(8, 64))
            record_launch("transpose", bytes_moved=50)
        assert led.counts() == {"fft-c2c": 1, "transpose": 1}
        assert led.total_bytes() == 150
        assert led.records[0] == LaunchRecord(
            kernel="fft-c2c", grid=(2,), tile=(4, 64), bytes_moved=100,
            shape=(8, 64))

    def test_first_capture_wins_for_signatures(self):
        led = LaunchLedger()
        with led.capture(key="obs-test-k"):
            record_launch("fft-c2c")
        with led.capture(key="obs-test-k"):  # warm executable: no records
            pass
        sig = led.signature(key="obs-test-k")
        assert [r.kernel for r in sig] == ["fft-c2c"]
        assert led.signature("never-seen") == []

    def test_signature_survives_fresh_ledger_via_global_store(self):
        # jit executables are cached process-wide, so the signature store
        # is too: a fresh ledger replays what an earlier one captured
        with LaunchLedger().capture(key="obs-test-global"):
            record_launch("fft-c2c", grid=(1,), tile=(4, 64))
        sig = LaunchLedger().signature("obs-test-global")
        assert [r.kernel for r in sig] == ["fft-c2c"]

    def test_launches_digest_over_receipt_signatures(self):
        a = [LaunchRecord(kernel="fft-c2c", grid=(1,), tile=(4, 64))]
        assert launches_digest([a, a]) == launches_digest([list(a), list(a)])
        assert launches_digest([a]) != launches_digest([a, a])

    def test_fft2_plan_launches_exactly_two_fused_passes(self):
        """PR 3's routing-counter claim, read from the ledger: a pow2 2-D
        plan is two transposed-write fused passes, nothing else."""
        from repro.fft.plan_nd import plan_nd
        plan = plan_nd((64, 64))
        x = rand_complex((2, 64, 64))
        led = LaunchLedger()
        with led.capture():
            y = plan.fn(x)                  # eager: one record per launch
        assert led.counts() == {"fft-c2c-t": 2}
        assert led.counts()["fft-c2c-t"] == plan.passes
        np.testing.assert_allclose(np.asarray(y),
                                   np.fft.fft2(np.asarray(x)),
                                   rtol=2e-3, atol=2e-2)

    def test_fused_conv_is_one_forward_plus_t_plane_inverse(self):
        """PR 4's fdas claim: 1 fused forward+multiply launch, and one
        *batched* inverse launch whose rows cover all T template planes
        (the paper's 1 + T HBM passes)."""
        from repro.fft.convolve import conv_plan, overlap_save_conv
        n, taps, t, nfft = 1000, 17, 3, 256
        plan = conv_plan(n, taps, t, nfft)
        x = rand_complex((n,))
        filters = np.asarray(
            jax.random.normal(jax.random.PRNGKey(1), (t, taps)))
        led = LaunchLedger()
        with led.capture():
            overlap_save_conv(x, filters, nfft=nfft)
        counts = led.counts()
        assert counts["fft-c2c-mul"] == 1      # forward + bank multiply
        assert counts["fft-c2c"] == 1          # one batched inverse launch
        (inv,) = [r for r in led.records if r.kernel == "fft-c2c"]
        assert inv.shape[0] == plan.n_segments * t
        assert inv.shape[0] // plan.n_segments == plan.inverse_passes == t

    def test_pipeline_launches_each_fused_kernel_once(self):
        """PR 6's claim: the pulsar graph traces one launch per fused
        kernel — dedispersion, the bank multiply, the harmonic plane."""
        from repro.data.synthetic import FilterbankSpec, synthetic_filterbank
        from repro.search.pipeline import DispersionPlan, pulsar_search
        from repro.search.templates import TemplateBank
        spec = FilterbankSpec(nchan=8, ntime=512)
        plan = DispersionPlan.from_spec(spec, n_trials=4)
        bank = TemplateBank.linear(zmax=2.0, n_templates=3)
        fb = synthetic_filterbank(spec, (), noise=1.0, seed=0)
        led = LaunchLedger()
        with led.capture():
            res = pulsar_search(fb, plan, bank, n_harmonics=4)
            jax.block_until_ready(res.stat)
        counts = led.counts()
        assert counts["dedisperse"] == 1
        assert counts["fft-c2c-mul"] == 1
        assert counts["harmonic-sum-plane"] == 1

    def test_ledger_digest_reproducible(self):
        def run():
            led = LaunchLedger()
            with led.capture():
                record_launch("fft-c2c", grid=(2,), tile=(4, 64),
                              bytes_moved=4096, shape=(8, 64))
            return led
        assert run().digest() == run().digest()
        other = LaunchLedger()
        with other.capture():
            record_launch("fft-c2c", grid=(4,), tile=(4, 64))
        assert other.digest() != run().digest()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_is_monotonic(self):
        c = Counter("n")
        c.inc()
        c.inc(3)
        assert c.value == 4
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_histogram_quantiles_are_bucket_bounds(self):
        h = Histogram("lat", buckets=(0.01, 0.1, 1.0))
        assert h.quantile(0.99) == 0.0                 # empty -> 0
        for v in (0.005, 0.005, 0.05, 5.0):
            h.observe(v)
        assert h.n == 4
        assert h.quantile(0.50) == 0.01                # upper bucket bound
        assert h.quantile(0.99) == 1.0                 # overflow -> top bound
        assert h.counts[-1] == 1                       # +Inf bucket

    def test_histogram_rejects_bad_buckets(self):
        with pytest.raises(ValueError):
            Histogram("h", buckets=(1.0, 1.0, 2.0))
        with pytest.raises(ValueError):
            Histogram("h", buckets=(2.0, 1.0))
        with pytest.raises(ValueError):
            Histogram("h", buckets=())

    def test_registry_get_or_create_and_type_guard(self):
        m = MetricsRegistry()
        c = m.counter("repro_x_total", "things")
        assert m.counter("repro_x_total") is c
        assert "repro_x_total" in m and "nope" not in m
        with pytest.raises(TypeError):
            m.gauge("repro_x_total")

    def test_render_is_prometheus_text(self):
        m = MetricsRegistry()
        m.counter("repro_served_total", "served requests").inc(2)
        m.gauge("repro_i_ef").set(1.25)
        h = m.histogram("repro_lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = m.render()
        assert "# HELP repro_served_total served requests" in text
        assert "# TYPE repro_served_total counter" in text
        assert "repro_served_total 2" in text
        assert "repro_i_ef 1.25" in text
        assert 'repro_lat_seconds_bucket{le="0.1"} 1' in text
        assert 'repro_lat_seconds_bucket{le="1"} 2' in text      # cumulative
        assert 'repro_lat_seconds_bucket{le="+Inf"} 2' in text
        assert "repro_lat_seconds_count 2" in text
        # deterministic: same registry state, same text
        assert text == m.render()

    def test_latency_summary_empty_convention(self):
        s = latency_summary([])
        assert (s.n, s.mean, s.p50, s.p99) == (0, 0.0, 0.0, 0.0)
        s = latency_summary([], on_empty=float("nan"))
        assert np.isnan(s.p99)
        s = latency_summary([1.0, 2.0])
        assert s.n == 2 and s.mean == pytest.approx(1.5)
        assert s.p50 == pytest.approx(1.5)
        assert s.p99 == pytest.approx(1.99)


# ---------------------------------------------------------------------------
# drift detector
# ---------------------------------------------------------------------------

class TestDriftDetector:
    def test_silent_below_min_samples_even_with_large_error(self):
        d = DriftDetector(min_samples=4, threshold=0.2)
        for _ in range(3):
            d.observe("k", modelled=1.0, measured=2.0)     # +100% error
        assert not d.alerting("k") and d.drift_alerts == 0

    def test_sustained_error_alerts_noise_does_not(self):
        d = DriftDetector(min_samples=4, threshold=0.2, alpha=0.25)
        for i in range(8):
            d.observe("hot", modelled=1.0, measured=1.5)   # +50% sustained
            # zero-mean noise: alternating +/-10% never crosses 20%
            d.observe("ok", modelled=1.0,
                      measured=1.1 if i % 2 == 0 else 0.9)
        assert d.alerting("hot") and not d.alerting("ok")
        assert d.alerts == ["hot"]
        s = d.summary()
        assert s["drift_alerts"] == 1 and s["tracked_keys"] == 2
        assert s["observations"] == 16
        assert s["worst_ewma_error"] == pytest.approx(0.5, abs=0.01)

    def test_zero_modelled_follows_guarded_ratio(self):
        d = DriftDetector()
        assert d.observe("z", modelled=0.0, measured=0.0) == 0.0

    def test_fill_metrics_publishes_gauges(self):
        d = DriftDetector(min_samples=1, threshold=0.1)
        d.observe(("fft", (64,), 940.0), modelled=1.0, measured=2.0)
        m = MetricsRegistry()
        d.fill_metrics(m)
        text = m.render()
        assert "repro_drift_alerts 1" in text
        assert "repro_drift_tracked_keys 1" in text
        assert "repro_drift_worst_ewma_error 1" in text

    def test_rejects_bad_alpha(self):
        with pytest.raises(ValueError):
            DriftDetector(alpha=0.0)


# ---------------------------------------------------------------------------
# structured logger
# ---------------------------------------------------------------------------

class TestStructuredLogger:
    def test_silenced_under_pytest_by_default(self):
        buf = io.StringIO()
        StructuredLogger("x", stream=buf).info("event", a=1)
        assert buf.getvalue() == ""        # PYTEST_CURRENT_TEST is set

    def test_env_level_overrides_pytest_silence(self, monkeypatch):
        monkeypatch.setenv("REPRO_LOG_LEVEL", "info")
        buf = io.StringIO()
        log = StructuredLogger("dryrun", stream=buf)
        log.info("lowered", tag="fft-4096", fits=True)
        log.debug("hidden")                # below threshold
        lines = buf.getvalue().splitlines()
        assert len(lines) == 1
        assert lines[0].startswith("INFO")
        assert "dryrun: lowered" in lines[0]
        assert "tag=fft-4096" in lines[0] and "fits=True" in lines[0]

    def test_off_silences_everything(self, monkeypatch):
        monkeypatch.setenv("REPRO_LOG_LEVEL", "off")
        buf = io.StringIO()
        StructuredLogger("x", stream=buf).error("boom")
        assert buf.getvalue() == ""

    def test_unknown_level_raises(self):
        with pytest.raises(ValueError):
            StructuredLogger("x").log("loud", "event")


# ---------------------------------------------------------------------------
# timer injection (runtime.fault) + serving integration
# ---------------------------------------------------------------------------

class TestDriverTimerInjection:
    def test_wall_metrics_deterministic_under_fake_timer(self, tmp_path):
        from repro.runtime.checkpoint import CheckpointManager
        from repro.runtime.fault import FaultTolerantDriver
        driver = FaultTolerantDriver(
            train_step=lambda s, i, l: (s + 1, {}),
            state=jnp.zeros(()),
            data_iter_fn=lambda i: (None, None),
            ckpt=CheckpointManager(str(tmp_path)), ckpt_every=100,
            timer=FakeTimer(dt=0.25),
        )
        _, log, _ = driver.run(3)
        assert [m["wall"] for m in log] == [pytest.approx(0.25)] * 3


class TestServingIntegration:
    def _run(self, *, power_model=None):
        timer = FakeTimer(dt=1e-4)
        tracer = Tracer(timer=timer)
        svc = FFTService(
            TPU_V5E, devices=[None, None], timer=timer, tracer=tracer,
            telemetry=FleetTelemetry.for_serving(TPU_V5E, seed=7,
                                                 noise_frac=0.0,
                                                 power_model=power_model))
        for i in range(4):
            # one drain per submit: four metered batches, so the drift
            # detector sees four observations on the same (kind, shape,
            # clock) key — enough to clear its min_samples gate
            svc.submit(rand_complex((2, 64), jax.random.PRNGKey(i)))
            svc.drain()
        return svc, tracer

    def test_receipts_carry_ledger_backed_launches(self):
        svc, tracer = self._run()
        for r in svc.receipts:
            assert [l.kernel for l in r.launches] == ["fft-c2c"]
            assert all(l.bytes_moved > 0 for l in r.launches)
        # spans nested batch > execute (launch) and collect > execute
        # (wait) with inherited attrs
        execs = [s for s in tracer.spans if s.name == "execute"]
        assert (sorted(s.parent for s in execs)
                == ["batch"] * 4 + ["collect"] * 4)
        assert all(s.attrs["kind"] == "fft" for s in execs)
        rep = svc.report()
        assert rep.drift is not None and rep.drift["observations"] > 0

    def test_trace_digest_reproducible_across_runs(self, monkeypatch):
        # Each run stands for a fresh process, in which request ids (a
        # submit span's request_id) restart from zero.
        monkeypatch.setattr(request_mod, "_REQUEST_IDS", itertools.count())
        s1, t1 = self._run()
        monkeypatch.setattr(request_mod, "_REQUEST_IDS", itertools.count())
        s2, t2 = self._run()
        assert trace_mod.digest(t1.spans) == trace_mod.digest(t2.spans)
        # the second service reuses warm jit executables (its ledger
        # records nothing live), yet its receipts replay the same launch
        # signatures from the process-wide store
        assert (launches_digest(r.launches for r in s1.receipts)
                == launches_digest(r.launches for r in s2.receipts))
        assert all(r.launches for r in s2.receipts)

    def test_metrics_text_covers_every_subsystem(self):
        svc, _ = self._run()
        text = svc.metrics_text()
        for series in ("repro_requests_served_total 4",
                       "repro_request_latency_seconds_count 4",
                       "repro_availability 1",
                       "repro_cache_hits", "repro_dispatch_workers 2",
                       "repro_telemetry_reads", "repro_drift_tracked_keys",
                       "repro_kernel_launches_recorded"):
            assert series in text, series

    def test_calibrated_model_stays_silent_miscalibrated_alerts(self):
        import dataclasses as dc
        from repro.core.power_model import PowerModel
        svc, _ = self._run()
        assert svc.drift.drift_alerts == 0            # calibrated sensor
        hot = PowerModel(dc.replace(TPU_V5E, name="hot-v5e",
                                    tdp=2.0 * TPU_V5E.tdp))
        svc2, _ = self._run(power_model=hot)
        assert svc2.drift.observations >= 4
        assert svc2.drift.drift_alerts >= 1           # model disagrees


class TestServicePath:
    """The host path of one served batch as spans and byte counters."""

    #: (span, parent) of one served batch's steps: its launch under
    #: ``batch``, its wait and accounting under ``collect``.
    BATCH_CHILDREN = [("pad", "batch"), ("stack", "batch"),
                      ("execute", "batch"), ("compute", "execute"),
                      ("execute", "collect"), ("h2d", "execute"),
                      ("compute", "execute"), ("slice", "collect"),
                      ("account", "collect")]

    def _serve(self, payloads, *, tracer, bucket_batches=True):
        svc = FFTService(TPU_V5E, devices=jax.devices()[:1],
                         tracer=tracer, bucket_batches=bucket_batches)
        for p in payloads:
            svc.submit(p)
        svc.drain()
        return svc

    def test_served_batch_yields_the_span_tree(self):
        tracer = Tracer(timer=FakeTimer(dt=1e-4))
        # 3 + 2 rows pad to 8
        payloads = [np.asarray(rand_complex((3, 64))),
                    np.asarray(rand_complex((2, 64)))]
        self._serve(payloads, tracer=tracer)
        by_name = {}
        for s in tracer.spans:
            by_name.setdefault(s.name, []).append(s)
        assert len(by_name["submit"]) == 2
        assert all(s.parent is None for s in by_name["submit"])
        (drain,) = by_name["drain"]
        (batch,) = by_name["batch"]
        (collect,) = by_name["collect"]
        assert batch.parent == "drain" and drain.parent is None
        assert collect.parent == "drain" and collect.attrs == batch.attrs
        assert batch.attrs["rows"] == 5 and batch.attrs["requests"] == 2
        steps = [s for s in tracer.spans
                 if s.name not in ("submit", "drain", "batch", "collect")]
        assert (sorted((s.name, s.parent) for s in steps)
                == sorted(self.BATCH_CHILDREN))
        for span in steps:
            assert span.attrs["batch_id"] == batch.attrs["batch_id"], span
        assert by_name["pad"][0].attrs["pad_rows"] == 3
        assert by_name["stack"][0].attrs["device_stacked"] == 2
        # the c2c batch reaches its executable in planes, never joined
        assert by_name["stack"][0].attrs["joined"] == 0
        assert set(by_name) == {"submit", "drain", "batch", "collect",
                                *(name for name, _ in self.BATCH_CHILDREN)}

    def test_one_worker_device_waits_sit_inside_execute(self, monkeypatch):
        """With one worker the batch's waits (its placement, traced, then
        its result) each take one clock second inside ``execute``, in
        ``h2d`` and ``compute``, and the host's other steps take none:
        ``execute`` spans sum to the whole of the client's time, as when
        launch and wait were one step."""
        clock = [0.0]
        block = jax.block_until_ready

        def waiting(x):
            clock[0] += 1.0
            return block(x)

        monkeypatch.setattr(jax, "block_until_ready", waiting)
        tracer = Tracer(timer=lambda: clock[0])
        payloads = [np.asarray(rand_complex((4, 64), jax.random.PRNGKey(i)))
                    for i in range(2)]
        t0 = clock[0]
        self._serve(payloads, tracer=tracer)
        host = clock[0] - t0

        def total(name):
            return sum(s.duration for s in tracer.spans if s.name == name)

        assert host == 2.0
        assert total("execute") == host
        assert total("h2d") == 1.0 and total("compute") == 1.0
        (collect,) = [s for s in tracer.spans if s.name == "collect"]
        assert collect.duration == host

    @pytest.mark.parametrize("on_host,copies", [(True, 1), (False, 0)],
                             ids=["host-array", "device-array"])
    def test_byte_counters_total_the_copies(self, on_host, copies):
        tracer = Tracer(timer=FakeTimer(dt=1e-4))
        payloads = [rand_complex((4, 64), jax.random.PRNGKey(i))
                    for i in range(2)]
        if on_host:
            payloads = [np.asarray(p) for p in payloads]
        self._serve(payloads, tracer=tracer)

        def total(name, key):
            return sum(s.attrs[key] for s in tracer.spans if s.name == name)

        payload = total("submit", "payload_bytes")
        assert payload == 2 * 4 * 64 * 8
        moved = total("submit", "h2d_bytes") + total("h2d", "h2d_bytes")
        assert moved == copies * payload
        assert not any(s.name == "pull" for s in tracer.spans)

    def test_no_tracer_opens_no_span_adds_no_sync_or_listener(
            self, monkeypatch):
        spans = []

        class CountingSpan(trace_mod.Span):
            def __init__(self, name, *args, **kwargs):
                super().__init__(name, *args, **kwargs)
                spans.append(name)

        syncs = []
        block = jax.block_until_ready

        def counting_block(x):
            syncs.append(1)
            return block(x)

        registered = []
        monkeypatch.setattr(trace_mod, "Span", CountingSpan)
        monkeypatch.setattr(jax, "block_until_ready", counting_block)
        monkeypatch.setattr(trace_mod, "_compile_listener_registered", False)
        monkeypatch.setattr(jax.monitoring,
                            "register_event_duration_secs_listener",
                            registered.append)
        payloads = [np.asarray(rand_complex((4, 64)))]
        self._serve(payloads, tracer=None)
        assert spans == [] and registered == []
        untraced = len(syncs)
        assert untraced >= 1
        syncs.clear()
        self._serve(payloads, tracer=Tracer(timer=FakeTimer(dt=1e-4)))
        assert registered == [trace_mod._on_duration]
        assert "h2d" in spans
        assert len(syncs) == untraced + 1      # the h2d span's wait
