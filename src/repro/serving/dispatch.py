"""Batch dispatch across devices via a work-stealing queue.

A batch whose payloads ``submit`` routed to a worker's device is queued
on that worker; any other lands on the least-loaded queue.  During the
drain loop each device pops its own queue FIFO and, when empty, steals
the freshest batch from the longest queue (repro.runtime.workqueue).
This is the paper's "FFTs which fit into GPU memory can be easily
distributed amongst the GPUs" (Sec. 2.3) made operational: batch-parallel
work needs no collectives, only load balance.

The dispatcher is cooperative and deterministic: one host thread drives
the workers in rounds.  A round launches one batch per worker, in worker
order, and only then collects them, in the same order.  JAX dispatches
device work asynchronously, so the devices of one round compute at once
while the host waits on the first of them.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax

from repro.runtime.faults import DrainDeadlineError
from repro.runtime.workqueue import WorkStealingQueue
from repro.serving.batcher import Batch


class Dispatcher:
    """Work-stealing executor over the visible JAX devices."""

    def __init__(self, devices: Sequence[Any] | None = None):
        self.devices = list(devices) if devices is not None else jax.devices()
        self.queue = WorkStealingQueue(len(self.devices))

    @property
    def steals(self) -> int:
        return self.queue.steals

    def submit(self, batch: Batch) -> int:
        """Queue a batch on its home worker, else on the least-loaded one;
        returns the worker."""
        if batch.home is not None:
            self.queue.push(batch.home, batch)
            return batch.home
        return self.queue.push_least_loaded(batch)

    def clear(self) -> list[Batch]:
        """Remove and return every queued batch (failure recovery)."""
        return self.queue.clear()

    def fill_metrics(self, registry) -> None:
        """Publish dispatch counters into a repro.obs MetricsRegistry."""
        registry.gauge("repro_dispatch_workers",
                       "worker slots (devices)").set(
                           self.queue.n_workers)
        registry.gauge("repro_dispatch_steals",
                       "batches stolen by idle workers").set(self.steals)

    def drain(
        self,
        execute: Callable[[Batch, int, Any], None],
        collect: Callable[[], None],
        *,
        timer: Callable[[], float] | None = None,
        deadline_s: float | None = None,
    ) -> int:
        """Run every queued batch; returns the number executed.

        ``execute(batch, worker, device)`` launches one batch on the
        worker that actually runs it (owner or thief); ``collect()``, called
        once a round has launched a batch on every worker that had one
        (and before an error of a launch propagates), waits for what the
        round launched.  ``execute`` or ``collect`` may re-queue a batch
        instead of running it (fault redistribution), so with
        ``deadline_s`` set (seconds on ``timer``'s clock, measured from
        drain start) a wedged worker surfaces a
        :class:`~repro.runtime.faults.DrainDeadlineError` naming the
        stuck batches' shape keys instead of looping forever.
        """
        executed = 0
        t0 = timer() if timer is not None and deadline_s is not None else 0.0
        while self.queue.pending():
            if (deadline_s is not None and timer is not None
                    and timer() - t0 > deadline_s):
                raise DrainDeadlineError(
                    deadline_s, [b.key for b in self.queue.items()])
            try:
                for worker in range(self.queue.n_workers):
                    batch = self.queue.pop(worker)
                    if batch is None:
                        continue
                    execute(batch, worker, self.devices[worker])
                    executed += 1
            finally:
                collect()
        return executed
