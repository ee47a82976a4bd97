"""repro.obs — the unified observability plane.

Four cooperating pieces (docs/observability.md walks through the loop):

* :mod:`repro.obs.trace` — deterministic nested-span tracing on an
  injectable clock, with a per-device flight recorder snapshotted on
  every ``runtime.faults`` error, per-span program-build counts,
  optional profiler annotations, and JSONL / blake2b exporters.
* :mod:`repro.obs.metrics` — a counters/gauges/fixed-bucket-histogram
  registry with one Prometheus-style text rendering, plus the shared
  guarded percentile helper.
* :mod:`repro.obs.ledger` — the kernel launch ledger: every Pallas
  kernel wrapper records its launches (name, grid, tile, bytes moved);
  serving receipts carry per-shape launch signatures and benchmarks
  audit pass counts from it.
* :mod:`repro.obs.drift` — EWMA model-vs-measured drift detection per
  (kind, shape, clock), fed from watchdog-fresh telemetry.
"""
from repro.obs.drift import DriftDetector, DriftState
from repro.obs.ledger import (LaunchLedger, LaunchRecord, launches_digest,
                              record_launch)
from repro.obs.log import StructuredLogger, get_logger
from repro.obs.metrics import (DEFAULT_LATENCY_BUCKETS, Counter, Gauge,
                               Histogram, LatencySummary, MetricsRegistry,
                               latency_summary)
from repro.obs.trace import (FlightRecorder, FlightSnapshot, Span, Tracer,
                             digest, notify_fault, to_jsonl)

__all__ = [
    "DriftDetector", "DriftState",
    "LaunchLedger", "LaunchRecord", "launches_digest", "record_launch",
    "StructuredLogger", "get_logger",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "LatencySummary", "latency_summary", "DEFAULT_LATENCY_BUCKETS",
    "FlightRecorder", "FlightSnapshot", "Span", "Tracer",
    "digest", "notify_fault", "to_jsonl",
]
