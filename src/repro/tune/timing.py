"""Shared warm-up/repeat wall-clock timing — one methodology everywhere.

The autotuner times the heuristic and every candidate *identically*, or
"speedup vs heuristic" compares apples to oranges: each goes through
:func:`time_fn`, warm-up calls first (JIT compilation and cache priming
are not the steady state), then ``repeats`` timed calls reduced with
``reduce`` (default ``min`` — best-of-n is robust to
scheduler noise on shared CPUs; pass ``statistics.median``/``mean`` for
other conventions).

``timer`` is injectable so tests can prove determinism: two tuning runs
fed the same fake clock must choose the same config.
"""
from __future__ import annotations

import time
from typing import Callable, Sequence

import jax


def time_fn(fn: Callable, *args, repeats: int = 5, warmup: int = 2,
            reduce: Callable[[Sequence[float]], float] = min,
            timer: Callable[[], float] = time.perf_counter) -> float:
    """Wall seconds per call of ``fn(*args)`` after warm-up."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(max(repeats, 1)):
        t0 = timer()
        jax.block_until_ready(fn(*args))
        samples.append(timer() - t0)
    return reduce(samples)
