"""Percent of the traced window in which no operation runs on the device
while the host is inside one of the service's copy steps (``submit``,
``stack``, ``pad``, ``h2d``): the copy steps' intervals, less their
overlap with device operations, on the profiler's clock, averaged over
the devices.  At most ``idle_share``."""

COPY_SPANS = ("service.submit", "service.stack", "service.pad",
              "service.h2d")


def _overlap_ns(a, b) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.bounds
    merged = run.tracemod.merged
    copying = merged([h for h in run.trace.host if h[0] in COPY_SPANS],
                     lo, hi)
    if not copying:
        return None
    copy_ns = sum(b - a for a, b in copying)
    idle_ns = [copy_ns - _overlap_ns(copying, merged(evs, lo, hi))
               for evs in run.trace.ops.values()]
    return 100.0 * sum(idle_ns) / len(idle_ns) / (hi - lo)
