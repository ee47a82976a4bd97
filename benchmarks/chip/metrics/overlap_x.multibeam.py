"""How many chips compute at once while any does: the chips' summed busy
time (each chip's union of ``XLA Ops`` intervals in the traced window)
over the union of busy time across the chips.  1 when one chip works at
a time, 4 when all four work whenever one does; 13 beams over four chips
allow at most 3.25."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.bounds
    merged = run.tracemod.merged
    summed = sum(b - a for evs in run.trace.ops.values()
                 for a, b in merged(evs, lo, hi))
    union = sum(b - a for a, b in merged(
        [e for evs in run.trace.ops.values() for e in evs], lo, hi))
    return summed / union if union > 0 else None
