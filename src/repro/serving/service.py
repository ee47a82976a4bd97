"""The energy-aware streaming FFT service.

Request lifecycle (docs/serving.md walks through a full example):

  enqueue      submit() stamps arrival time and parks the request, its
               payload on its home worker's device when there are several
  batch        drain() coalesces pending requests into Eq. 6-sized batches
  plan-cache   each batch's shape hits the plan + sweep cache (one FFT plan
               and one DVFS sweep per distinct shape, ever)
  clock-plan   the batch's operating point is selected from the cached
               sweep under the strictest per-request real-time budget
  execute      the batch is launched with the clock locked
               (ClockController) on the device the work-stealing
               dispatcher assigned — or sharded over the whole mesh for
               oversized batches — and waited for once every worker's
               batch of the round is launched
  account      every request gets a receipt: queue/service latency
               (measured) and energy at the locked vs boost clock
               (modelled, Eqs. 3-4)

The energy numbers come from the repository's analytic model
(repro.core), because a TPU v5e gives JAX neither a power sensor nor a
clock lock.  An optional ``telemetry`` bundle
(repro.power.FleetTelemetry) adds a *measured* energy estimate next to
the modelled one: each executed batch takes one watchdog-classified
power sample, and receipts carry ``measured_energy_j`` priced at the
measured power when the reading is fresh, at the model otherwise (the
never-freewheel contract applied to accounting).  On instrumented
hardware the same hook wraps NVML via a hardware PowerSampler.

Robustness (repro.serving.slo + repro.runtime.faults): an optional
``slo`` policy turns drain() into admission-controlled serving — every
rejected or pressure-degraded request still terminates in a receipt
stating why.  An optional ``fault_plan`` injects deterministic serving
faults; the service answers with per-device circuit breakers,
jittered-backoff retries, work redistribution through the work-stealing
queue, and the graceful-degradation ladder (tuned-dvfs -> boost-heuristic
-> pure-jax) instead of crashing.  Every receipt records the rung it was
served at.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.energy import guarded_ratio
from repro.core.hardware import TPU_V5E, DeviceSpec
from repro.core.power_model import PowerModel
from repro.core.scheduler import ClockController
from repro.obs.drift import DriftDetector
from repro.obs.ledger import LaunchLedger
from repro.obs.metrics import MetricsRegistry, latency_summary
from repro.runtime import journal as wal
from repro.runtime.faults import (FAIL_CLOCK_LOCK, FAIL_PLAN_BUILD,
                                  KILL_DEVICE, KILL_HOST, STALL_WORKER,
                                  CircuitBreaker, ClockLockError,
                                  DeviceLostError, FaultPlan, HostLostError,
                                  HostTopology, PlanBuildError, RetryPolicy)
from repro.serving.batcher import Batch, coalesce
from repro.serving.cache import CacheStats, PlanSweepCache
from repro.serving.dispatch import Dispatcher
from repro.serving.request import (KIND_FFT, KIND_PULSAR, FFTRequest,
                                   Planes, RequestReceipt, ShapeKey,
                                   StageReceipt)
from repro.serving.slo import (RUNG_BOOST_HEURISTIC, RUNG_PURE_JAX,
                               RUNG_TUNED_DVFS, SHED, SLOPolicy,
                               AdmissionController, max_rung_for_kind)

_EXEC_DTYPE = {"fp16": jnp.complex64, "fp32": jnp.complex64,
               "fp64": jnp.complex128}
# Real execution dtypes for R2C payloads — stacking them as complex would
# double the device bytes and forfeit the R2C saving the receipts report.
_REAL_EXEC_DTYPE = {"fp16": jnp.float32, "fp32": jnp.float32,
                    "fp64": jnp.float64}


def _exec_dtype(key: ShapeKey) -> np.dtype:
    """The dtype ``key``'s executable takes: complex for C2C, real for
    R2C, float32 for the pulsar pipeline and the FDAS search (which
    consume real time series); fp64 only where 64-bit is enabled."""
    if key.kind != KIND_FFT:
        dtype = jnp.float32
    elif key.transform == "r2c":
        dtype = _REAL_EXEC_DTYPE[key.precision]
    else:
        dtype = _EXEC_DTYPE[key.precision]
    return jax.dtypes.canonicalize_dtype(dtype)


@jax.jit
def _deinterleave(v: jax.Array) -> Planes:
    """The planes of the complex array whose (real, imaginary) pairs
    ``v`` holds along its last axis: a host complex array's real view."""
    return Planes(v[..., 0::2], v[..., 1::2])


@jax.jit
def _split(x: jax.Array) -> Planes:
    return Planes(jnp.real(x), jnp.imag(x))


def _to_device(x: Any, device: Any = None) -> jax.Array | Planes:
    """``x`` on ``device`` (a host array; the default device when None),
    or where it is (a device array); a complex ``x`` as its planes.  A
    host complex array crosses the link as its real view (the same bytes)
    and is split into planes on the device: copied as complex, it is
    transposed on the host in small chunks, and a profiler records each
    of them."""
    if isinstance(x, jax.Array):
        x = jnp.asarray(x)
        return _split(x) if jnp.iscomplexobj(x) else x
    xh = np.asarray(x)
    if not np.iscomplexobj(xh):
        return jnp.asarray(xh, device=device)
    pairs = np.ascontiguousarray(xh).view(np.finfo(xh.dtype).dtype)
    return _deinterleave(jnp.asarray(pairs, device=device))


def _stray_bytes(batch: Batch, planes: tuple | None) -> int:
    """Bytes of the batch's payloads held on another device than its
    planes: what stacking moves from chip to chip."""
    if planes is None:
        return 0
    home = planes[0].devices()
    return sum(r.x.nbytes for r in batch.requests if r.x.devices() != home)


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(planes: tuple, x: jax.Array | Planes, offset) -> tuple:
    """``planes`` with request ``x`` written in place from row ``offset``
    on, as (rows, *shape): its real part into ``planes[0]`` and, for a
    complex batch and payload, its imaginary part into ``planes[1]`` (a
    real payload's is the zero the plane holds).  The offset is traced,
    so the programs are one per (bucket, payload) shape and dtype pair,
    whatever the batch's row split."""
    parts = (x.re, x.im) if isinstance(x, Planes) else (x,)
    start = (offset,) + (0,) * (planes[0].ndim - 1)
    return tuple(lax.dynamic_update_slice(
        p, v.reshape((-1, *p.shape[1:])).astype(p.dtype), start)
        for p, v in zip(planes, parts))


@dataclasses.dataclass
class _InFlight:
    """A batch launched on its worker and not yet waited for."""

    batch: Batch
    worker: int
    entry: Any
    point: Any
    rung: int
    reason: str | None
    rows: int
    attrs: dict          # the batch span's, for the collect span
    x: Any               # the batch as placed on the worker's device
    y: Any               # its executable's result, still being computed
    d2d_bytes: int       # bytes the placement moved from another device
    t_start: float


@dataclasses.dataclass(frozen=True)
class ServiceReport:
    """Service-level summary over every receipt issued so far."""

    n_requests: int
    n_transforms: int
    n_batches: int
    wall_s: float                  # wall time spent executing batches
    energy_j: float                # modelled energy at the locked clocks
    boost_energy_j: float          # same work at boost (the GPU default)
    p50_latency_s: float
    p99_latency_s: float
    mean_latency_s: float
    cache: CacheStats
    steals: int
    clock_locks: int
    # --- robustness (zero on a fault-free, SLO-less service) --------------
    shed: int = 0                  # terminal shed receipts (all reasons)
    fault_shed: int = 0            # shed with a fault:* reason
    degraded: int = 0              # served at rung > 0
    retried: int = 0               # served after >= 1 lost execution
    redistributions: int = 0       # batches pushed away from a sick worker
    breaker_opens: int = 0         # circuit-breaker quarantines
    slo: dict | None = None        # SLOPolicy.evaluate() scorecard
    # --- power telemetry (repro.power), zero/None when unmetered ----------
    measured_energy_j: float = 0.0  # watchdog-fresh measured J (model-filled
    #                                 for non-fresh samples: never freewheels)
    telemetry: dict | None = None   # FleetTelemetry.summary()
    # --- observability (repro.obs), None when the service runs unmetered --
    drift: dict | None = None       # DriftDetector.summary()

    # Zero-denominator edges below follow the single documented
    # convention of repro.core.energy.guarded_ratio.

    @property
    def availability(self) -> float:
        """Served / (served + fault-shed).  Admission sheds are excluded:
        refusing work the SLO says cannot be served on time is the
        contract working, not the service failing.  An empty report is
        availability 1.0 (no demand, nothing unserved)."""
        return guarded_ratio(self.n_requests,
                             self.n_requests + self.fault_shed, on_zero=1.0)

    @property
    def joules_per_transform(self) -> float:
        return guarded_ratio(self.energy_j, self.n_transforms, on_zero=0.0)

    @property
    def i_ef(self) -> float:
        """Service-level Eq. 7 (identical work => energy ratio)."""
        return guarded_ratio(self.boost_energy_j, self.energy_j, on_zero=1.0)

    @property
    def throughput_tps(self) -> float:
        return guarded_ratio(self.n_transforms, self.wall_s, on_zero=0.0)


class FFTService:
    """Asynchronous-style FFT serving with batching, caching and DVFS.

    ``device_spec`` drives the analytic DVFS/energy model (which clock each
    batch locks to, what it costs); execution runs on the host's actual
    JAX devices.  ``mesh`` (optional) shards plain-FFT batches over every
    mesh device via repro.fft.distributed instead of placing them whole.
    ``coalesce_requests=False`` disables batching: every request executes
    alone, as its own batch.
    """

    def __init__(
        self,
        device_spec: DeviceSpec = TPU_V5E,
        *,
        batch_bytes: float | None = None,
        time_budget: float | None = 0.10,
        devices: Sequence[Any] | None = None,
        mesh: Any = None,
        coalesce_requests: bool = True,
        bucket_batches: bool = True,
        keep_results: bool = True,
        max_retained_receipts: int | None = None,
        plan_fn=None,
        sweep_fn=None,
        power_model: PowerModel | None = None,
        timer=time.monotonic,
        slo: SLOPolicy | None = None,
        fault_plan: FaultPlan | None = None,
        breaker_threshold: int = 2,
        breaker_cooldown_s: float = 0.05,
        sleep_fn: Callable[[float], None] | None = None,
        telemetry=None,
        tracer=None,
        journal=None,
        topology: HostTopology | None = None,
    ):
        self.device_spec = device_spec
        # Default batch budget: an eighth of device memory, capped at the
        # paper's ~2 GB measurement batches (Sec. 4).
        self.batch_bytes = (batch_bytes if batch_bytes is not None
                            else min(2e9, device_spec.memory_bytes / 8))
        self.time_budget = time_budget
        self.mesh = mesh
        self.coalesce_requests = coalesce_requests
        self.bucket_batches = bucket_batches
        self.keep_results = keep_results
        # Receipts (which pin request payloads and, with keep_results,
        # outputs) grow with traffic; long-running servers should bound
        # retention — oldest receipts are evicted past the cap.  report()
        # then summarises the retained window.
        self.max_retained_receipts = max_retained_receipts
        self._timer = timer
        kwargs = {}
        if plan_fn is not None:
            kwargs["plan_fn"] = plan_fn
        if sweep_fn is not None:
            kwargs["sweep_fn"] = sweep_fn
        self.cache = PlanSweepCache(
            device_spec, batch_bytes=self.batch_bytes,
            power_model=power_model, **kwargs)
        self.clock = ClockController(
            device_spec, timer=timer,
            max_events=(None if max_retained_receipts is None
                        else 2 * max_retained_receipts))
        # With a mesh the whole mesh executes each batch, so one worker.
        self.dispatcher = Dispatcher(
            devices=[None] if mesh is not None else devices)
        self._pending: list[FFTRequest] = []
        self._copying: dict[int, jax.Array] = {}   # worker -> last routed copy
        self._inflight: list[_InFlight] = []
        self._receipts: dict[int, RequestReceipt] = {}
        self._next_batch_id = 0
        # --- robustness state ---------------------------------------------
        self.slo = slo
        self.admission = (AdmissionController(slo, device_spec)
                          if slo is not None else None)
        self.faults = fault_plan
        self.retry = RetryPolicy()
        # Backoff sleeps are computed deterministically but not actually
        # slept by default — the cooperative drain loop would only be
        # blocking itself.  Threaded deployments pass time.sleep.
        self._sleep = sleep_fn if sleep_fn is not None else (lambda s: None)
        self._breaker_threshold = breaker_threshold
        self._breaker_cooldown_s = breaker_cooldown_s
        self.breakers: dict[int, CircuitBreaker] = {}
        self._stalled_until: dict[int, float] = {}
        self._attempts: dict[int, int] = {}      # batch_id -> lost executions
        self._forced: dict[int, tuple[int, str]] = {}  # req_id -> rung, why
        self._rung2_fns: dict[Any, Callable] = {}
        self.redistributions = 0
        self.stalls_honoured = 0
        # --- power telemetry (repro.power.FleetTelemetry, optional) -------
        # One watchdog-classified power sample per executed batch; receipts
        # carry measured_energy_j next to the modelled energy_j.  None
        # leaves the service unmetered (receipts report None).
        self.telemetry = telemetry
        # --- observability (repro.obs) ------------------------------------
        # The launch ledger is always on (recording costs one truthiness
        # check per kernel at trace time); tracing is opt-in via tracer=
        # (a repro.obs.Tracer — pass one sharing the service timer for
        # reproducible traces).  The drift detector only accumulates when
        # telemetry hands back watchdog-fresh power samples.
        self.tracer = tracer
        self.metrics = MetricsRegistry()
        self.ledger = LaunchLedger()
        self.drift = DriftDetector()
        # --- crash consistency (repro.runtime.journal, optional) ----------
        # With a journal attached every admit/assign/terminal transition is
        # logged write-ahead; the journal seq of the admit record is the
        # request's durable identity (FFTRequest.jseq).  See
        # repro.serving.recovery for the replay/re-enqueue half.
        self.journal = journal
        # Host fault domains: which workers share a simulated host (a
        # KILL_HOST event takes the whole group down together).  None
        # means every worker is its own host.
        self.topology = topology
        self._by_seq: dict[int, RequestReceipt] = {}
        self.recovered_receipts: list[RequestReceipt] = []
        self.replay = None              # ReplayResult set by recover()
        self.host_kills = 0

    # ------------------------------------------------------------------ #
    # enqueue
    # ------------------------------------------------------------------ #

    def submit(
        self,
        x: Any,
        *,
        precision: str = "fp32",
        kind: str = KIND_FFT,
        latency_budget: float | None = None,
        n_harmonics: int = 32,
        transform: str = "c2c",
        ndim: int = 1,
        templates: int = 16,
        segment: int = 0,
        dm_trials: int = 16,
        payload_ref: Any = None,
    ) -> FFTRequest:
        """Enqueue one request (a (batch, *shape) or (*shape,) array).

        ``transform="r2c"`` serves real payloads through the R2C plan —
        half the energy per transform at the same length (Eq. 5/6).
        ``ndim=2`` serves 2-D transforms (e.g. imaging grids) through the
        N-D plan graph — one fused kernel pass per pow2 axis — with their
        own first-class plan + sweep cache entries.  ``kind="fdas"`` runs
        the full acceleration search (repro.search) on real time series;
        ``templates`` sizes the bank and ``segment`` pins the
        overlap-save FFT length (0 = cost-model auto-selection), and both
        are part of the plan/sweep cache key.  ``kind="pulsar"`` runs the
        end-to-end pulsar search (repro.search.pipeline) on (nchan,
        ntime) filterbanks — ``dm_trials`` sizes the dedispersion grid,
        ``templates``/``n_harmonics`` the bank and harmonic ladder, and
        all three join the cache key; its receipts carry per-stage DVFS
        shares (clock, modelled J) and the real-time margin.  The
        request's receipt becomes available after the next drain():
        ``service.receipt(request)``.
        """
        with self._span("submit") as span:
            on_host = not isinstance(x, jax.Array)
            if on_host:
                x = np.asarray(x)
            req = FFTRequest(x=x, precision=precision,
                             kind=kind, latency_budget=latency_budget,
                             n_harmonics=n_harmonics, transform=transform,
                             ndim=ndim, templates=templates,
                             segment=segment, dm_trials=dm_trials)
            req.home = self._route(req)
            copied = on_host and req.home is not None
            if copied and req.home in self._copying:
                # One host copy in flight per chip: with many in flight
                # at once the copies cross several times slower.
                self._copying.pop(req.home).block_until_ready()
            req.x = _to_device(x, None if req.home is None
                               else self.dispatcher.devices[req.home])
            if copied:
                self._copying[req.home] = req.x
            if span is not None:
                span.attrs.update(request_id=req.request_id,
                                  payload_bytes=req.x.nbytes,
                                  h2d_bytes=req.x.nbytes if on_host else 0)
                if req.home is not None:
                    span.attrs["worker"] = req.home
            req.t_enqueue = self._timer()
            req.payload_ref = payload_ref
            if self.journal is not None:
                # Write-ahead: the admit record is durable (and its seq is
                # the request's crash-stable identity) before the service
                # takes the request.  ``payload_ref`` is the caller's
                # token for re-resolving the payload after a crash —
                # arrays themselves are not journaled.
                from repro.serving.recovery import admit_record
                req.jseq = self.journal.append(wal.ADMIT, admit_record(req))
            self._pending.append(req)
        return req

    def _route(self, req: FFTRequest) -> int | None:
        """The worker whose device a new payload is put on, so that the
        batch holding it runs where it already is.  A host payload goes
        to the worker with the fewest queued bytes of its shape key among
        those not stalled or quarantined (the lowest index on a tie); a
        device payload stays on its device, homed at the worker there.
        None with one worker, or with workers that hold no device (a
        mesh, a simulated fleet): the payload goes to the default device.
        """
        devices = self.dispatcher.devices
        if len(devices) < 2 or any(d is None for d in devices):
            return None
        if isinstance(req.x, jax.Array):
            return next((w for w, d in enumerate(devices)
                         if req.x.devices() == {d}), None)
        key = req.shape_key(self.device_spec.name)
        queued = [0] * len(devices)
        for r in self._pending:
            if (r.home is not None
                    and r.shape_key(self.device_spec.name) == key):
                queued[r.home] += r.x.nbytes
        now = self._timer()
        ready = [w for w in range(len(devices))
                 if not self._peek_blocked(w, now)]
        return min(ready or range(len(devices)), key=queued.__getitem__)

    def receipt(self, request: FFTRequest) -> RequestReceipt | None:
        return self._receipts.get(request.request_id)

    def receipt_for_seq(self, jseq: int) -> RequestReceipt | None:
        """The receipt for a journal admit seq (survives recovery, where
        process-local request ids reset but journal seqs never do)."""
        return self._by_seq.get(jseq)

    def _remember_seq(self, jseq: int | None, receipt: RequestReceipt) -> None:
        if jseq is None:
            return
        cap = self.max_retained_receipts
        if (cap is not None and jseq not in self._by_seq
                and len(self._by_seq) >= cap):
            self._by_seq.pop(next(iter(self._by_seq)))      # oldest
        self._by_seq[jseq] = receipt

    @property
    def receipts(self) -> list[RequestReceipt]:
        return [self._receipts[k] for k in sorted(self._receipts)]

    # ------------------------------------------------------------------ #
    # batch -> plan-cache -> clock-plan -> execute -> account
    # ------------------------------------------------------------------ #

    def drain(self, *, deadline_s: float | None = None
              ) -> list[RequestReceipt]:
        """Serve every pending request; returns their receipts in order.

        With an ``slo`` policy the admission controller runs first: shed
        requests terminate immediately in a ``status="shed"`` receipt
        (with the reason), pressure-degraded ones carry their forced
        rung into execution.  ``deadline_s`` (default: none) bounds the
        drain loop on the service timer so a wedged worker surfaces a
        DrainDeadlineError naming the stuck shapes instead of looping
        forever.

        If a batch fails mid-cycle, already-served requests keep their
        receipts and every unserved request is re-queued for the next
        drain before the error propagates — one bad batch never drops
        the rest of the wave.
        """
        with self._span("drain"):
            self._copying.clear()
            pending, self._pending = self._pending, []
            if not pending:
                return []
            serve = pending
            if self.admission is not None:
                serve = []
                for d in self.admission.decide(pending, self.cache):
                    if d.action == SHED:
                        self._store(RequestReceipt.make_shed(
                            d.request, d.reason, self._timer()))
                    else:
                        if d.rung > RUNG_TUNED_DVFS:
                            self._forced[d.request.request_id] = (
                                d.rung, d.reason)
                        serve.append(d.request)
            try:
                if serve:
                    if self.coalesce_requests:
                        batches = coalesce(
                            serve, device_name=self.device_spec.name,
                            batch_bytes=self.batch_bytes,
                            start_id=self._next_batch_id,
                            pow2_rows=self.bucket_batches)
                    else:
                        batches = [
                            Batch(self._next_batch_id + i,
                                  r.shape_key(self.device_spec.name), [r],
                                  r.home)
                            for i, r in enumerate(serve)
                        ]
                    self._next_batch_id += len(batches)
                    if self.journal is not None:
                        for batch in batches:
                            self.journal.append(wal.ASSIGN, {
                                "batch_id": batch.batch_id,
                                "rseqs": [r.jseq for r in batch.requests]})
                    for batch in batches:
                        self.dispatcher.submit(batch)
                    self.dispatcher.drain(self._execute, self._collect,
                                          timer=self._timer,
                                          deadline_s=deadline_s)
            except BaseException:
                self.dispatcher.clear()          # drop stale queued batches
                self._inflight.clear()
                unserved = [r for r in serve
                            if r.request_id not in self._receipts]
                self._pending = unserved + self._pending
                raise
            finally:
                for r in serve:
                    self._forced.pop(r.request_id, None)
            # The receipt cap may have evicted some.
            return [self._receipts[r.request_id] for r in pending
                    if r.request_id in self._receipts]

    def _bucket(self, batch: Batch, target: int) -> tuple | None:
        """The zero-filled (``target``, *shape) planes the batch is written
        into (one for a real batch, real and imaginary for a complex
        one), on the device holding its first payload: its home worker's
        where submit routed it, else the default device; their rows past
        the requests' are the bucketing pad.
        None when the batch is one payload that already has the
        executable's shape and dtype: it is handed through as it is (a
        complex one in its planes).
        """
        shape = (target, *(batch.key.shape or (batch.key.n,)))
        dtype = _exec_dtype(batch.key)
        first = batch.requests[0].x
        if (len(batch.requests) == 1 and first.shape == shape
                and first.dtype == dtype):
            return None
        plane = jnp.zeros(shape, np.finfo(dtype).dtype,
                          device=next(iter(first.devices())))
        if not jnp.issubdtype(dtype, jnp.complexfloating):
            return (plane,)
        return plane, jnp.zeros_like(plane)

    def _stack(self, batch: Batch, planes: tuple | None, *,
               join: bool = False) -> jax.Array | Planes:
        """Write every payload of the batch into ``planes`` on the device,
        one ``_write_rows`` call per request at its row offset (a payload
        on another device, which routing leaves only to payloads submitted
        as device arrays, is moved to the planes' first); with no planes,
        the batch's one payload.  A complex batch stays in its planes,
        which its executable takes, unless ``join``: then it is joined
        into the complex array the mesh and the pure-JAX rung take.
        """
        if planes is None:
            x = batch.requests[0].x
        else:
            home = planes[0].sharding
            offset = 0
            for r in batch.requests:
                x = r.x
                if x.devices() != home.device_set:
                    x = jax.device_put(x, home)
                planes = _write_rows(planes, x, offset)
                offset += r.batch
            x = planes[0] if len(planes) == 1 else Planes(*planes)
        return x.join() if join and isinstance(x, Planes) else x

    def _effective_budget(self, batch: Batch) -> float | None:
        """Strictest real-time budget across the batch's requests.

        Budget-less requests fall back to the service default, so a loose
        explicit budget on one request can never relax the guarantee owed
        to a coalesced neighbour; None (from a request AND the default)
        means unconstrained.
        """
        budgets = [self.time_budget if r.latency_budget is None
                   else r.latency_budget for r in batch.requests]
        constrained = [b for b in budgets if b is not None]
        return min(constrained) if constrained else None

    # ------------------------------------------------------------------ #
    # fault handling
    # ------------------------------------------------------------------ #

    def _breaker(self, worker: int) -> CircuitBreaker:
        br = self.breakers.get(worker)
        if br is None:
            br = CircuitBreaker(failure_threshold=self._breaker_threshold,
                                cooldown_s=self._breaker_cooldown_s)
            self.breakers[worker] = br
        return br

    def _peek_blocked(self, worker: int, now: float) -> bool:
        """Is ``worker`` stalled or quarantined?  Pure — no probe consumed."""
        if self._stalled_until.get(worker, 0.0) > now:
            return True
        br = self.breakers.get(worker)
        return br is not None and not br.would_allow(now)

    def _reassign(self, batch: Batch, *, exclude, now: float) -> None:
        """Push ``batch`` back onto the healthiest other worker's queue.

        ``exclude`` is one worker index or an iterable of them (a host
        fault domain).  When the exclusion covers every worker the batch
        still has to land somewhere — it goes back to the excluded set
        and waits out the breaker cooldowns there.
        """
        excluded = ({exclude} if isinstance(exclude, int) else set(exclude))
        others = [w for w in range(self.dispatcher.queue.n_workers)
                  if w not in excluded]
        healthy = [w for w in others if not self._peek_blocked(w, now)]
        self.dispatcher.queue.push_least_loaded(
            batch, allowed=healthy or others or sorted(excluded))
        self.redistributions += 1

    def _batch_rung(self, batch: Batch) -> tuple[int, list[str]]:
        """The admission-forced rung of the batch: the deepest rung forced
        on any member (a coalesced neighbour's pressure degrade applies to
        the whole batch), capped at what the kind supports."""
        rung, reasons = RUNG_TUNED_DVFS, []
        for r in batch.requests:
            forced = self._forced.get(r.request_id)
            if forced is None:
                continue
            rung = max(rung, forced[0])
            if forced[1] not in reasons:
                reasons.append(forced[1])
        return min(rung, max_rung_for_kind(batch.key.kind)), reasons

    def _rung2_fn(self, key) -> Callable:
        """The pure-JAX twin of ``key``'s executable (bottom rung).

        Traced once per key under ``pallas_disabled()`` so the jitted
        function captures the pure-JAX engine permanently — a kernel-level
        miscompile or Pallas-runtime fault can never reach this rung.
        """
        fn = self._rung2_fns.get(key)
        if fn is None:
            from repro.fft.plan import pallas_disabled, plan_with_config
            with pallas_disabled():
                if key.shape:
                    from repro.fft.plan_nd import plan_nd_with_config
                    plan = plan_nd_with_config(key.shape, key.transform)
                else:
                    plan = plan_with_config(key.n, key.transform)
            fn = jax.jit(plan.fn)
            self._rung2_fns[key] = fn
        return fn

    def _span(self, name: str, **attrs):
        """A tracer span when tracing is on, else a free nullcontext."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, **attrs)

    def _execute(self, batch: Batch, worker: int, device: Any) -> None:
        """Fault-aware launch of one batch (:meth:`_launch`); the wait for
        it is :meth:`_collect`'s.

        Blocked workers (stalled or breaker-open) hand the batch to a
        healthy peer; an injected stall marks the worker and redistributes;
        a lost device trips the breaker and retries the batch elsewhere
        under the retry policy (:meth:`_lost`).
        """
        now = self._timer()
        if self._stalled_until.get(worker, 0.0) > now:
            self._reassign(batch, exclude=worker, now=now)
            return
        if not self._breaker(worker).allow(now):
            self._reassign(batch, exclude=worker, now=now)
            return
        if self.faults is not None:
            ev = self.faults.take(STALL_WORKER, batch_id=batch.batch_id,
                                  worker=worker)
            if ev is not None:
                self.stalls_honoured += 1
                self._stalled_until[worker] = now + ev.duration
                self._reassign(batch, exclude=worker, now=now)
                return
        try:
            self._inflight.append(self._launch(batch, worker, device))
        except DeviceLostError as e:
            self._lost(batch, worker, e)

    def _collect(self) -> None:
        """Wait for every batch launched since the last collect, in launch
        (worker) order, and account it.  A device lost at the wait takes
        the path of one lost at launch."""
        inflight, self._inflight = self._inflight, []
        for f in inflight:
            try:
                self._finish(f)
            except DeviceLostError as e:
                self._lost(f.batch, f.worker, e)
            else:
                self._breaker(f.worker).record_success()

    def _lost(self, batch: Batch, worker: int, error: DeviceLostError) -> None:
        """Retry a batch whose device (or host) was lost on another worker,
        shedding it with "fault:*" receipts once the retry policy is
        spent."""
        now = self._timer()
        if isinstance(error, HostLostError):
            # The whole fault domain died: every worker on the host is
            # quarantined at once (breaker tripped straight to open — no
            # point counting failures towards a threshold when the host
            # is demonstrably gone) and its telemetry rings are wiped
            # (the readings lived in that host's memory).  The batch then
            # follows the normal retry/redistribute/shed ladder, with the
            # whole domain excluded.
            self.host_kills += 1
            for w in error.workers:
                self._breaker(w).trip(now)
                if self.telemetry is not None:
                    ring = self.telemetry.rings.get(w)
                    if ring is not None:
                        ring.clear()
            exclude, why = error.workers, "fault:host-lost"
        else:
            self._breaker(worker).record_failure(now)
            exclude, why = worker, "fault:retries-exhausted"
        attempts = self._attempts.get(batch.batch_id, 0) + 1
        self._attempts[batch.batch_id] = attempts
        if attempts > self.retry.max_retries:
            self._attempts.pop(batch.batch_id, None)
            for req in batch.requests:
                self._store(RequestReceipt.make_shed(req, why, now))
            return
        self._sleep(self.retry.delay(attempts, token=batch.batch_id))
        self._reassign(batch, exclude=exclude, now=now)

    def _launch(self, batch: Batch, worker: int, device: Any) -> _InFlight:
        """Build the batch on the device and enqueue its executable on the
        worker's device, without waiting for either."""
        rung, reasons = self._batch_rung(batch)
        if (self.faults is not None
                and self.faults.take(FAIL_PLAN_BUILD, batch_id=batch.batch_id,
                                     worker=worker)):
            rung = max(rung, RUNG_BOOST_HEURISTIC)
            reasons.append("fault:plan-build-failed")
        try:
            entry = (self.cache.entry(batch.key) if rung == RUNG_TUNED_DVFS
                     else self.cache.degraded_entry(batch.key))
        except PlanBuildError:
            # A real tuned-build failure (not just an injected event):
            # walk down the ladder instead of crashing.
            rung = max(rung, RUNG_BOOST_HEURISTIC)
            if "fault:plan-build-failed" not in reasons:
                reasons.append("fault:plan-build-failed")
            entry = self.cache.degraded_entry(batch.key)
        point = (entry.point_for(self._effective_budget(batch))
                 if rung == RUNG_TUNED_DVFS else entry.sweep.boost)
        # Rung 0 locks at the sweep optimum; degraded rungs still lock, at
        # boost, to pin against governor drift — clock control is
        # independent of which compute path runs, so a lock failure is
        # observable on every rung.
        lock_f = point.f
        if lock_f is not None and self.faults is not None \
                and self.faults.take(FAIL_CLOCK_LOCK, batch_id=batch.batch_id,
                                     worker=worker):
            # The clock lock could not be acquired: run unlocked at the
            # device's boost default.  At rung 0 the tuned plan is kept —
            # only the clock guarantee is lost.
            if rung == RUNG_TUNED_DVFS:
                rung = RUNG_BOOST_HEURISTIC
                point = entry.sweep.boost
            reasons.append("fault:clock-lock-failed")
            lock_f = None
        rows = sum(r.batch for r in batch.requests)
        target = (1 << (rows - 1).bit_length() if self.bucket_batches
                  else rows)
        fft = batch.key.kind == KIND_FFT
        sharded = (self.mesh is not None and fft and target > 1
                   and rung < RUNG_PURE_JAX)
        # The mesh and the pure-JAX rung take a complex array; every other
        # complex executable takes the batch's planes.
        join = (jnp.issubdtype(_exec_dtype(batch.key), jnp.complexfloating)
                and (sharded or (fft and rung >= RUNG_PURE_JAX)))
        # Span attributes (kind/shape/rung/clock) inherit to child spans;
        # the ledger capture rides the execution so a first-trace records
        # the shape's launch signature (repro.obs.ledger).
        attrs = dict(batch_id=batch.batch_id, worker=worker,
                     kind=batch.key.kind,
                     shape=batch.key.shape or (batch.key.n,),
                     rung=rung, clock_mhz=point.f, rows=rows,
                     requests=len(batch.requests))
        with self._span("batch", **attrs):
            # The batch is built on the device from the payloads submit
            # put there: zero-filled bucket planes, then one in-place write
            # per request at a traced row offset.  The programs are one
            # zero fill per bucket shape and one write per
            # (bucket, payload) shape and dtype pair, so a stream of new
            # row splits compiles nothing.  Shape bucketing pads the row
            # count to the next power of two, so streaming drains reuse a
            # handful of executables instead of compiling one per size.
            with self._span("pad", pad_rows=target - rows):
                planes = self._bucket(batch, target)
            with self._span("stack", device_stacked=(
                    0 if planes is None else len(batch.requests)),
                    d2d_bytes=_stray_bytes(batch, planes),
                    joined=int(join)):
                x = self._stack(batch, planes, join=join)
            t_start = self._timer()
            ctx = (self.clock.locked(lock_f) if lock_f is not None
                   else contextlib.nullcontext())
            with ctx:
                # Injected kills fire mid-batch: after the lock and
                # dispatch decisions, before results exist.  A host kill
                # takes the worker's whole fault domain with it.
                if (self.faults is not None
                        and self.faults.take(KILL_HOST,
                                             batch_id=batch.batch_id,
                                             worker=worker)):
                    topo = self.topology or HostTopology(
                        self.dispatcher.queue.n_workers)
                    host = topo.host_of(worker)
                    raise HostLostError(worker, host,
                                        topo.workers_of(host))
                if (self.faults is not None
                        and self.faults.take(KILL_DEVICE,
                                             batch_id=batch.batch_id,
                                             worker=worker)):
                    raise DeviceLostError(worker)
                with self._span("execute"), \
                        self.ledger.capture(key=batch.key):
                    xd, y = self._run(batch, entry, rung, x, device,
                                      sharded=sharded)
        return _InFlight(batch=batch, worker=worker, entry=entry,
                         point=point, rung=rung,
                         reason="; ".join(reasons) or None, rows=rows,
                         attrs=attrs, x=xd, y=y,
                         d2d_bytes=0 if xd is x else xd.nbytes,
                         t_start=t_start)

    def _finish(self, f: _InFlight) -> None:
        """Wait for a launched batch (``h2d``: its placement, when traced;
        ``compute``: its result), slice off the bucket pad and account
        it, under a ``collect`` span with the batch span's attributes."""
        with self._span("collect", **f.attrs):
            with self._span("execute"):
                with self._span("h2d", h2d_bytes=0, d2d_bytes=f.d2d_bytes):
                    if self.tracer is not None:
                        jax.block_until_ready(f.x)
                f.x = None     # the stacked batch's memory, before the slices
                with self._span("compute"):
                    y = jax.block_until_ready(f.y)
                f.y = None
            with self._span("slice"):
                y = y[:f.rows]
            t_done = self._timer()
            with self._span("account"):
                self._account(f.batch, f.worker, f.entry, f.point, y,
                              f.t_start, t_done, rung=f.rung,
                              reason=f.reason)

    def _run(self, batch: Batch, entry, rung: int, x: jax.Array | Planes,
             device: Any, *, sharded: bool = False
             ) -> tuple[jax.Array | Planes, jax.Array]:
        """Place the stacked batch on the worker's device and enqueue its
        executable (``compute``), or shard it over the mesh; returns the
        placed batch and the result, neither waited for.  The batch is
        already on a device, so no host bytes cross the link here: a
        worker on another device (one that stole or was handed the batch)
        gets one device-to-device copy."""
        xd = (x if sharded or device is None or x.devices() == {device}
              else jax.device_put(x, device))
        with self._span("compute"):
            if sharded:
                from repro.fft.distributed import batch_parallel_fft
                y = batch_parallel_fft(xd, self.mesh, fft_fn=entry.plan)
            elif rung >= RUNG_PURE_JAX and batch.key.kind == KIND_FFT:
                from repro.fft.plan import pallas_disabled
                with pallas_disabled():
                    y = self._rung2_fn(batch.key)(xd)
            else:
                y = entry.fn(xd)
        return xd, y

    def _store(self, receipt: RequestReceipt, *, key=None) -> None:
        jseq = getattr(receipt.request, "jseq", None)
        if self.journal is not None and jseq is not None:
            # Durability point: the terminal record hits the journal
            # BEFORE the in-memory receipt exists, so a crash can lose an
            # execution (at-least-once) but never a receipt — replay
            # either finds the terminal record (receipt reconstructed
            # bit-identically) or re-enqueues the admit (executed again,
            # receipted once).  ``key`` (the batch's shape key) lets
            # recovery replay the launch signature from the ledger.
            from repro.serving.recovery import terminal_record
            receipt.incarnation = self.journal.incarnation
            rtype = wal.SERVED if receipt.status == "served" else wal.SHED
            self.journal.append(rtype, terminal_record(receipt, key))
            self._remember_seq(jseq, receipt)
        if (self.max_retained_receipts is not None
                and len(self._receipts) >= self.max_retained_receipts):
            self._receipts.pop(next(iter(self._receipts)))  # oldest
        self._receipts[receipt.request.request_id] = receipt
        # Terminal-receipt metrics: counters live beyond receipt retention.
        if receipt.status == "served":
            self.metrics.counter(
                "repro_requests_served_total",
                "requests served (any rung, incl. after retries)").inc()
            self.metrics.histogram(
                "repro_request_latency_seconds",
                "end-to-end (queue + service) request latency").observe(
                    receipt.latency)
            if receipt.rung > RUNG_TUNED_DVFS:
                self.metrics.counter(
                    "repro_requests_degraded_total",
                    "requests served below the tuned-DVFS rung").inc()
        else:
            self.metrics.counter(
                "repro_requests_shed_total",
                "requests terminated without execution").inc()

    def _account(self, batch, worker, entry, point, y, t_start, t_done,
                 rung=RUNG_TUNED_DVFS, reason=None):
        per_time, per_energy = entry.per_transform(point)
        _, per_boost = entry.per_transform(entry.sweep.boost)
        retries = self._attempts.pop(batch.batch_id, 0)
        # One telemetry sample per executed batch, at the clock it locked.
        # Watchdog-fresh readings price the batch at measured power; any
        # other label falls back to the modelled energy — receipts never
        # carry a number derived from telemetry the watchdog distrusts.
        measured_w = None
        if self.telemetry is not None:
            tr = self.telemetry.read(
                worker, t_done, token=batch.batch_id, f_mhz=point.f,
                u_core=entry.profile.core_utilisation(self.device_spec),
                u_mem=entry.profile.mem_utilisation(self.device_spec))
            measured_w = tr.measured_w
        if measured_w is not None:
            # Model-drift loop: one per-transform modelled-vs-measured
            # observation per metered batch, keyed on (kind, shape,
            # clock).  Fresh-only: suspect telemetry never moves EWMAs.
            self.drift.observe(
                (batch.key.kind, batch.key.shape or (batch.key.n,),
                 point.f),
                modelled=per_energy, measured=measured_w * per_time)
        launches = self.ledger.signature(batch.key)
        offset = 0
        for req in batch.requests:
            rows = req.batch
            result = y[offset:offset + rows] if self.keep_results else None
            offset += rows
            stages = None
            if entry.stages is not None:
                # Pipeline entries: scale the modelled batch's per-stage
                # plan (clock + J/stage) to this request's row share.
                share = rows / max(entry.n_fft_model, 1)
                stages = [StageReceipt(name=s.name, clock_mhz=s.f,
                                       time_s=s.time * share,
                                       energy_j=s.energy * share)
                          for s in entry.stages.stages]
            self._store(RequestReceipt(
                request=req,
                batch_id=batch.batch_id,
                worker=worker,
                queue_latency=max(t_start - req.t_enqueue, 0.0),
                service_latency=t_done - t_start,
                clock_mhz=point.f,
                modelled_time_s=per_time * rows,
                energy_j=per_energy * rows,
                boost_energy_j=per_boost * rows,
                measured_energy_j=(
                    None if self.telemetry is None
                    else (measured_w * per_time * rows
                          if measured_w is not None
                          else per_energy * rows)),
                result=result,
                stages=stages,
                realtime_margin=entry.realtime_margin,
                rung=rung,
                retries=retries,
                reason=reason,
                launches=list(launches),
            ), key=batch.key)

    # ------------------------------------------------------------------ #
    # crash consistency
    # ------------------------------------------------------------------ #

    def snapshot(self, *, governors: dict | None = None) -> str:
        """Persist the durable service state to the attached journal.

        Captures the plan/sweep cache keys, breaker and watchdog health,
        drift EWMAs, metrics counters and the batch-id high-water mark
        (plus any caller-managed power ``governors``) as an atomic
        snapshot; recovery replays only the journal records written
        after it.  Returns the snapshot path.
        """
        if self.journal is None:
            raise ValueError("snapshot() requires a journal-attached "
                             "service (pass journal= to the constructor)")
        from repro.serving.recovery import ServiceSnapshot
        return self.journal.write_snapshot(
            ServiceSnapshot.capture(self, governors=governors))

    @classmethod
    def recover(cls, journal_dir: str, **kwargs) -> "FFTService":
        """Rebuild a service from a journal directory after a crash.

        See :func:`repro.serving.recovery.recover_service` — replayed
        receipts land in ``recovered_receipts`` (and
        ``receipt_for_seq``), in-flight admits are re-enqueued via
        ``payload_fn``, and the replay accounting is on ``.replay``.
        """
        from repro.serving.recovery import recover_service
        return recover_service(journal_dir, **kwargs)

    # ------------------------------------------------------------------ #
    # service-level reporting
    # ------------------------------------------------------------------ #

    def report(self) -> ServiceReport:
        receipts = self.receipts
        served = [r for r in receipts if r.status == "served"]
        shed = [r for r in receipts if r.status == "shed"]
        fault_shed = sum(1 for r in shed
                         if (r.reason or "").startswith("fault:"))
        lat = latency_summary(r.latency for r in served)
        # One wall-time contribution per batch (receipts in a batch share
        # the batch's service latency), over the *retained* window so every
        # report field covers the same receipts when retention is capped.
        batch_wall = {r.batch_id: r.service_latency for r in served}
        return ServiceReport(
            n_requests=len(served),
            n_transforms=sum(r.request.batch for r in served),
            n_batches=len(batch_wall),
            wall_s=sum(batch_wall.values()),
            energy_j=sum(r.energy_j for r in served),
            boost_energy_j=sum(r.boost_energy_j for r in served),
            p50_latency_s=lat.p50,
            p99_latency_s=lat.p99,
            mean_latency_s=lat.mean,
            cache=self.cache.stats,
            steals=self.dispatcher.steals,
            clock_locks=self.clock.lock_count,
            shed=len(shed),
            fault_shed=fault_shed,
            degraded=sum(1 for r in served if r.rung > RUNG_TUNED_DVFS),
            retried=sum(1 for r in served if r.retries > 0),
            redistributions=self.redistributions,
            breaker_opens=sum(b.opens for b in self.breakers.values()),
            slo=self.slo.evaluate(receipts) if self.slo is not None else None,
            measured_energy_j=sum(r.measured_energy_j or 0.0 for r in served),
            telemetry=(self.telemetry.summary()
                       if self.telemetry is not None else None),
            drift=(self.drift.summary()
                   if self.drift.observations else None),
        )

    def fill_metrics(self) -> MetricsRegistry:
        """Refresh the registry from the current report and subsystem
        counters; returns the registry (render with ``.render()``).

        Terminal-receipt counters and the latency histogram accrue live
        in :meth:`_store`; everything gauge-like — cache stats, steals,
        breaker opens, telemetry labels, drift EWMAs, histogram-derived
        p50/p99 — is refreshed here in one deterministic pass.
        """
        m = self.metrics
        rep = self.report()
        h = m.histogram("repro_request_latency_seconds",
                        "end-to-end (queue + service) request latency")
        m.gauge("repro_request_latency_p50_seconds",
                "histogram-derived median latency").set(h.quantile(0.50))
        m.gauge("repro_request_latency_p99_seconds",
                "histogram-derived tail latency").set(h.quantile(0.99))
        m.gauge("repro_availability",
                "served / (served + fault-shed)").set(rep.availability)
        m.gauge("repro_energy_joules",
                "modelled energy at the locked clocks").set(rep.energy_j)
        m.gauge("repro_measured_energy_joules",
                "telemetry-priced energy (fresh samples)").set(
                    rep.measured_energy_j)
        m.gauge("repro_i_ef", "service-level Eq. 7 efficiency increase"
                ).set(rep.i_ef)
        m.gauge("repro_clock_locks", "DVFS clock locks taken").set(
            rep.clock_locks)
        m.gauge("repro_breaker_opens", "circuit-breaker quarantines").set(
            rep.breaker_opens)
        m.gauge("repro_redistributions",
                "batches pushed away from sick workers").set(
                    rep.redistributions)
        m.gauge("repro_kernel_launches_recorded",
                "ledger records captured (trace-time)").set(
                    len(self.ledger.records))
        self.cache.stats.fill_metrics(m)
        self.dispatcher.fill_metrics(m)
        if self.telemetry is not None:
            self.telemetry.fill_metrics(m)
        self.drift.fill_metrics(m)
        return m

    def metrics_text(self) -> str:
        """One Prometheus-style exposition of the whole service."""
        return self.fill_metrics().render()
