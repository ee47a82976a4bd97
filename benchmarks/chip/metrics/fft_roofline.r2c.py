"""The served R2C transforms' least time on the chip over the summed
device time of every operation in the window: the R2C route's share of
its roofline, charged with all the device work of the served path, so
that work moved from a kernel into XLA ops cannot raise it.

The transforms are counted from the service's ``batch`` spans whose
``transform`` is ``r2c``: ``rows`` transforms of length ``shape[-1]``
each.  None where no span says so (a service that does not tag its
batches).  Work of a length-n real transform, whatever implements it:

* 2.5 n log2 n flops, half a complex transform's 5 n log2 n;
* one read of n float32 and one write of n/2 + 1 complex64 bins:
  4 n + 8 (n/2 + 1) bytes.
"""
import math


def r2c_flops(n: int, transforms: int) -> float:
    return 2.5 * n * math.log2(n) * transforms


def r2c_bytes(n: int, transforms: int) -> float:
    return (4.0 * n + 8.0 * (n // 2 + 1)) * transforms


def read(run):
    batches = [(s.attrs["shape"][-1], s.attrs["rows"]) for s in run.spans
               if s.name == "batch" and s.attrs.get("transform") == "r2c"]
    device_s = run.op_seconds()
    if not batches or device_s <= 0 or run.peak is None:
        return None
    least = sum(run.work.least_time(r2c_flops(n, rows), r2c_bytes(n, rows),
                                    run.peak)[0] for n, rows in batches)
    return 100.0 * least / device_s
