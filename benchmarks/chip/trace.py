"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData``:

* device planes (``/device:TPU:<i>``): the events of their ``XLA Ops``
  line are the operations that ran on the device.  An event's name is
  the HLO instruction's text; ``op_name`` shortens it to the instruction
  (``fft_pallas``, ``custom-call:X64SplitHigh``);
* host planes (``/host:CPU``): the benchmark's and the service's
  ``TraceAnnotation`` spans, named ``bench.*`` and ``service.*``.

All times are on the profiler's one clock, in nanoseconds.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("bench.", "service.")
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Reduced:
    """Device operations per device, and the host spans, of one trace."""

    ops: dict[str, list[tuple[str, int, int]]]   # device -> (name, t0, t1)
    host: list[tuple[str, int, int]]             # (name, t0, t1)

    @property
    def n_devices(self) -> int:
        return len(self.ops)


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(hlo: str) -> str:
    """``%fft_pallas.1 = (...) custom-call(...)`` -> ``fft_pallas``; a
    custom call other than a kernel is named by its target."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    name = re.sub(r"\.\d+$", "", name)
    target = _TARGET.search(hlo)
    if target and target.group(1) != "tpu_custom_call":
        name = f"{name}:{target.group(1)}"
    return name


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: dict[str, list[tuple[str, int, int]]] = {}
    host: list[tuple[str, int, int]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    t0 = int(e.start_ns)
                    evs.append((op_name(e.name), t0,
                                t0 + int(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        t0 = int(e.start_ns)
                        host.append((e.name, t0, t0 + int(e.duration_ns)))
    return Reduced(ops=ops, host=host)


def window(red: Reduced, name: str = WINDOW_SPAN) -> tuple[int, int]:
    """Bounds of the (one) host span ``name``."""
    spans = [(a, b) for n, a, b in red.host if n == name]
    if len(spans) != 1:
        raise ValueError(f"expected one {name!r} span, found {len(spans)}")
    return spans[0]


def _clip(evs, lo: int, hi: int):
    for name, a, b in evs:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield name, a, b


def merged(evs, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of the events' intervals inside [lo, hi], sorted."""
    out: list[list[int]] = []
    for _, a, b in sorted(_clip(evs, lo, hi), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(red: Reduced, lo: int, hi: int) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not red.ops:
        return 0.0
    total = sum(sum(b - a for a, b in merged(evs, lo, hi))
                for evs in red.ops.values())
    return total / red.n_devices / 1e9


def op_seconds(red: Reduced, lo: int, hi: int,
               match=lambda name: True) -> float:
    """Summed device time of the operations whose name ``match`` accepts,
    over all devices."""
    return sum(b - a for evs in red.ops.values()
               for name, a, b in _clip(evs, lo, hi) if match(name)) / 1e9


def top_ops(red: Reduced, lo: int, hi: int, k: int = 10) -> list:
    """[[name, seconds], ...]: the ``k`` operations with most device time."""
    acc: collections.Counter = collections.Counter()
    for evs in red.ops.values():
        for name, a, b in _clip(evs, lo, hi):
            acc[name] += (b - a) / 1e9
    return [[n, s] for n, s in acc.most_common(k)]


def idle_gaps(red: Reduced, lo: int, hi: int, k: int = 10) -> list:
    """[[host span, seconds], ...]: device idle time inside [lo, hi],
    summed by the innermost benchmark or service span that was open on
    the host at each gap's middle (first device only)."""
    acc: collections.Counter = collections.Counter()
    evs = next(iter(red.ops.values()), [])
    busy = merged(evs, lo, hi)
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        inner = [(s, n) for n, s, e in red.host
                 if s <= mid < e and n != WINDOW_SPAN]
        label = max(inner)[1] if inner else "no span"
        acc[label] += (b - a) / 1e9
    return [[n, s] for n, s in acc.most_common(k)]
