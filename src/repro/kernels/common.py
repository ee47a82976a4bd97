"""Shared kernel plumbing: backend mode, TPU tiling constants, tile sizing."""
from __future__ import annotations

import jax

#: Vector register shape of a 32-bit value on the TPU: (sublanes, lanes).
SUBLANES = 8
LANES = 128

#: Mosaic's default scoped-VMEM limit per kernel (v5e has 128 MiB of VMEM
#: per core; a kernel that needs more must ask for it).
SCOPED_VMEM_BYTES = 16 * 2**20

#: Bytes of double-buffered in/out blocks a kernel's tile may fill; the
#: rest of the scoped limit holds tables, scratch and temporaries.
BLOCK_BUDGET_BYTES = 6 * 2**20


def use_interpret() -> bool:
    """Pallas mode for the attached backend.

    ``cpu`` (the test path) runs kernels in interpret mode; ``tpu``
    compiles them.  Any other backend has no Pallas TPU lowering and no
    reason to emulate one, so it is an error rather than a silent
    interpret-mode run.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or in interpret mode on "
        f"'cpu'; the default backend is {backend!r}")


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def vmem_compiler_params(block_bytes: int):
    """Raise the scoped-VMEM limit for one kernel when its blocks (double
    buffered) plus scratch and temporaries cannot fit the default.

    Only kernels whose smallest legal block outgrows the default get
    here (a transposed write needs 128 rows for a lane-dense output; a
    dedispersion block keeps every DM trial's whole time axis).  v5e has
    128 MiB of VMEM per core; the limit is sized from the blocks, not set
    blindly.  None keeps the default.
    """
    from jax.experimental.pallas import tpu as pltpu
    need = 3 * block_bytes // 2 + 4 * 2**20
    if need <= SCOPED_VMEM_BYTES:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(need, 100 * 2**20))


def batch_tile(n: int, elem_bytes: int, *,
               vmem_budget: int = BLOCK_BUDGET_BYTES, buffers: int = 4,
               align: int = SUBLANES, override: int | None = None) -> int:
    """Rows per grid step keeping ``buffers`` (tile, n) planes in VMEM.

    ``buffers`` counts every pipelined in/out plane of the kernel with
    its double buffer (a C2C kernel's re/im in and out are 8).  The tile
    is ``align`` times a power of two, so the service's power-of-two
    bucketed batches never pad, and at least one aligned block.

    That tile is also the largest the budget admits: ``override`` (the
    autotuner's tuned choice, ``repro.tune``) is rounded up to ``align``
    and clamped to it, so no tuning-cache entry can hand the compiler a
    tile that overflows VMEM.
    """
    per_row = max(n, 1) * elem_bytes * buffers
    blocks = max(vmem_budget // per_row // align, 1)
    budget_tile = (1 << (blocks.bit_length() - 1)) * align
    if override is None:
        return budget_tile
    if override < 1:
        raise ValueError(f"batch tile override must be >= 1, "
                         f"got {override}")
    return min(round_up(override, align), budget_tile)


def rows_tile(rows: int, tile: int, align: int = SUBLANES) -> tuple[int, int]:
    """(tile, padded rows) for a batch of ``rows``.

    A batch no larger than one aligned block is its own (full-extent)
    block and is never padded; otherwise the tile is an ``align``
    multiple no larger than the aligned batch, and the batch pads up to
    a tile multiple.
    """
    if rows <= align:
        return rows, rows
    tile = min(tile, round_up(rows, align))
    return tile, round_up(rows, tile)
