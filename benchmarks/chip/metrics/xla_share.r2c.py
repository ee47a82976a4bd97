"""Percent of the window's device time in operations that are not Pallas
kernels (trace names that do not end in ``_pallas``): the XLA passes
around the kernels, such as the real row's packing, the Hermitian split,
complex joins and splits, and the batch's stack."""


def read(run):
    total = run.op_seconds()
    if total <= 0:
        return None
    return 100.0 * run.op_seconds(
        lambda name: not name.endswith("_pallas")) / total
