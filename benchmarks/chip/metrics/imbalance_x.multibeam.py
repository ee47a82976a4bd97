"""The busiest chip's busy time (its union of ``XLA Ops`` intervals in
the traced window) over the mean chip's.  1 when the chips share the
work evenly; 13 beams over four chips allow no less than 4 / 3.25."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.bounds
    busy = [sum(b - a for a, b in run.tracemod.merged(evs, lo, hi))
            for evs in run.trace.ops.values()]
    mean = sum(busy) / len(busy)
    return max(busy) / mean if mean > 0 else None
