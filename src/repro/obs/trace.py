"""Deterministic tracing: nested spans, flight recorder, exporters.

The :class:`Tracer` mirrors the repository's injectable-clock idiom
(``tune.timing.time_fn``'s ``timer=`` / the tests' FakeTimer): span
timestamps come from whatever monotonic callable the caller provides, so
a trace driven by a fake timer is bit-reproducible — the property the
``obs`` benchmark gates with a blake2b digest over two fresh runs.

Spans nest via a context-manager stack and *inherit* their parent's
attributes (``kind``/``shape``/``rung``/``clock_mhz`` set on a batch
span flow down to its children unless overridden).  Completed spans also
feed a bounded per-device :class:`FlightRecorder` ring; when any
``repro.runtime.faults`` error is raised, every live tracer snapshots
its rings (plus the spans still open at the moment of failure) for
postmortems — the crash-dump analogue of an aircraft flight recorder.

The first :class:`Tracer` built registers one ``jax.monitoring``
listener for XLA's backend-compile event; each event adds one to the
``compiles`` and its seconds to the ``compile_s`` of the innermost span
open in every live tracer, so a trace shows which step built a program.
``Tracer(annotate=prefix)`` also opens a
``jax.profiler.TraceAnnotation(prefix + name)`` around every span, which
puts the spans in a profile on the device trace's clock.

Exporters: :func:`to_jsonl` (one span per line, canonical key order)
and :func:`digest` (blake2b of the JSONL).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import hashlib
import json
import time
import weakref
from typing import Any

__all__ = ["Span", "FlightSnapshot", "FlightRecorder", "Tracer",
           "notify_fault", "to_jsonl", "digest"]

#: The ``jax.monitoring`` duration event of one XLA program build.  It
#: also wraps a read from the persistent compilation cache, so a cache
#: hit counts as a build.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Span:
    """One timed region on the tracer's clock."""

    name: str
    t_start: float
    duration: float = 0.0
    depth: int = 0                      # nesting depth at open time
    parent: str | None = None           # enclosing span's name
    attrs: dict = dataclasses.field(default_factory=dict)
    # Programs built while this was the innermost open span, and their
    # seconds on the process clock.  Kept out of attrs (children do not
    # inherit them) and out of to_dict (a fake-clock digest stays
    # reproducible whether or not jit's caches were warm).
    compiles: int = 0
    compile_s: float = 0.0

    def to_dict(self) -> dict:
        return {"name": self.name, "t_start": self.t_start,
                "duration": self.duration, "depth": self.depth,
                "parent": self.parent,
                "attrs": {k: (list(v) if isinstance(v, tuple) else v)
                          for k, v in sorted(self.attrs.items())}}


@dataclasses.dataclass(frozen=True)
class FlightSnapshot:
    """The flight-recorder state frozen at the moment of one fault."""

    error_type: str                     # e.g. "DeviceLostError"
    message: str
    spans: dict                         # device -> last-N completed spans
    open_spans: tuple                   # spans still open when it fired


class FlightRecorder:
    """Bounded per-device ring of the most recent completed spans."""

    def __init__(self, capacity: int = 64):
        self.capacity = capacity
        self._rings: dict[Any, collections.deque] = {}
        self.snapshots: list[FlightSnapshot] = []

    def push(self, span: Span) -> None:
        dev = span.attrs.get("worker", -1)
        ring = self._rings.get(dev)
        if ring is None:
            ring = self._rings[dev] = collections.deque(
                maxlen=self.capacity)
        ring.append(span)

    def ring(self, device: Any = -1) -> list[Span]:
        return list(self._rings.get(device, ()))

    def snapshot(self, error: BaseException,
                 open_spans: tuple = ()) -> FlightSnapshot:
        snap = FlightSnapshot(
            error_type=type(error).__name__, message=str(error),
            spans={dev: list(ring)
                   for dev, ring in sorted(self._rings.items(),
                                           key=lambda kv: str(kv[0]))},
            open_spans=tuple(open_spans))
        self.snapshots.append(snap)
        return snap


#: Live tracers, notified on every runtime.faults error.  A WeakSet so
#: abandoned tracers (and their retained spans) are collectable.
_TRACERS: "weakref.WeakSet[Tracer]" = weakref.WeakSet()


_compile_listener_registered = False


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    for tracer in list(_TRACERS):
        if tracer._stack:
            span = tracer._stack[-1]
            span.compiles += 1
            span.compile_s += duration


def _register_compile_listener() -> None:
    """Listen for program builds, once per process."""
    global _compile_listener_registered
    if _compile_listener_registered:
        return
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _compile_listener_registered = True


def notify_fault(error: BaseException) -> None:
    """Snapshot every live tracer's flight recorder for ``error``.

    Called (via a lazy import) from ``repro.runtime.faults`` when a fault
    error is constructed; a no-op with no tracers alive.
    """
    for tracer in list(_TRACERS):
        tracer.flight.snapshot(error, open_spans=tuple(tracer._stack))


class Tracer:
    """Nested-span tracer on an injectable monotonic clock.

    ``annotate`` (a name prefix such as ``"service."``) also makes every
    span a profiler annotation of that prefixed name.
    """

    def __init__(self, timer=time.monotonic, *,
                 recorder_capacity: int = 64, annotate: str | None = None):
        self.timer = timer
        self.annotate = annotate
        self.spans: list[Span] = []         # completed, in completion order
        self._stack: list[Span] = []
        self.flight = FlightRecorder(capacity=recorder_capacity)
        _register_compile_listener()
        _TRACERS.add(self)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Open a span; children inherit attrs (own keys win)."""
        if self.annotate is None:
            note = contextlib.nullcontext()
        else:
            import jax
            note = jax.profiler.TraceAnnotation(self.annotate + name)
        parent = self._stack[-1] if self._stack else None
        merged = dict(parent.attrs) if parent is not None else {}
        merged.update(attrs)
        with note:
            s = Span(name=name, t_start=self.timer(),
                     depth=len(self._stack),
                     parent=parent.name if parent is not None else None,
                     attrs=merged)
            self._stack.append(s)
            try:
                yield s
            finally:
                self._stack.pop()
                s.duration = self.timer() - s.t_start
                self.spans.append(s)
                self.flight.push(s)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def to_jsonl(spans: list[Span]) -> str:
    """One canonical JSON object per line (sorted keys, no whitespace)."""
    return "\n".join(json.dumps(s.to_dict(), sort_keys=True,
                                separators=(",", ":")) for s in spans)


def digest(spans: list[Span]) -> str:
    """blake2b over the canonical JSONL — identical spans, identical hex."""
    return hashlib.blake2b(to_jsonl(spans).encode(),
                           digest_size=16).hexdigest()
