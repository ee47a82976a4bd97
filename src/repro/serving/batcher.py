"""Request coalescing: group compatible requests into memory-bounded batches.

The paper's Eq. (6), N_FFT = M_GB / (N * B), sizes a batch by how many
length-N transforms fit a memory budget.  The batcher applies exactly that
cap: pending requests are grouped by shape key (same kind, length,
precision — transforms of different lengths cannot share one plan) and by
home worker (the worker whose device ``submit`` put the payload on, where
it routed one), kept in FIFO arrival order, and split whenever the
accumulated transform count would exceed the Eq. 6 budget.

With ``pow2_rows`` (the service's row bucketing, which pads a batch's
rows to the next power of two) the cap is the largest power of two
within the Eq. 6 budget, so the pad never takes device memory the budget
does not have: three one-row requests under a cap of three become 2 + 1,
not one batch padded to four.  Where every padded batch already fits the
budget, the batches are the same as without ``pow2_rows``.

A single request larger than the budget is never split (a client's batch
is one array); it becomes an oversized batch of its own, which the
executor shards across devices instead.
"""
from __future__ import annotations

import dataclasses

from repro.core.energy import ffts_per_batch
from repro.serving.request import FFTRequest, ShapeKey


@dataclasses.dataclass
class Batch:
    """One executable unit: same-shape requests fused into a single call."""

    batch_id: int
    key: ShapeKey
    requests: list[FFTRequest]
    home: int | None = None     # the worker holding the payloads, if routed

    @property
    def n_transforms(self) -> int:
        return sum(r.batch for r in self.requests)

    @property
    def bytes(self) -> int:
        """Payload footprint at the batch's executed precision (real for
        pow2 r2c payloads, complex otherwise)."""
        return self.n_transforms * self.key.n * self.key.elem_bytes

    @property
    def latency_budget(self) -> float | None:
        """Strictest (smallest) per-request budget governs the whole batch."""
        budgets = [r.latency_budget for r in self.requests
                   if r.latency_budget is not None]
        return min(budgets) if budgets else None


def _capped_runs(reqs: list[FFTRequest], cap: int) -> list[list[FFTRequest]]:
    """FIFO runs of whole requests, each closed before it would pass
    ``cap`` transforms."""
    runs: list[list[FFTRequest]] = []
    current: list[FFTRequest] = []
    count = 0
    for req in reqs:
        if current and count + req.batch > cap:
            runs.append(current)
            current, count = [], 0
        current.append(req)
        count += req.batch
    if current:
        runs.append(current)
    return runs


def coalesce(
    pending: list[FFTRequest],
    *,
    device_name: str,
    batch_bytes: float,
    start_id: int = 0,
    pow2_rows: bool = False,
) -> list[Batch]:
    """Coalesce ``pending`` (arrival order) into memory-bounded batches."""
    groups: dict[tuple[ShapeKey, int | None], list[FFTRequest]] = {}
    for req in pending:
        groups.setdefault((req.shape_key(device_name), req.home),
                          []).append(req)

    batches: list[Batch] = []
    for (key, home), reqs in groups.items():
        # Eq. 6 cap at the bytes the batch will actually occupy: pow2 r2c
        # payloads execute as real arrays, so twice as many fit.
        cap = ffts_per_batch(batch_bytes, key.n, key.elem_bytes)
        if pow2_rows:
            # Rows pad to the next power of two, so a batch may hold no
            # more than the largest power of two within the cap.
            cap = 1 << (cap.bit_length() - 1)
        for run in _capped_runs(reqs, cap):
            batches.append(Batch(start_id + len(batches), key, run, home))
    return batches
