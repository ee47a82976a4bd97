"""The served transforms' least time on the chip (5 n log2 n flops, 16
bytes a point) over the summed device time of every operation in the
window: the FFT kernels' share of their roofline, charged with all the
device work of the served path."""


def read(run):
    device_s = run.op_seconds()
    if device_s <= 0 or run.peak is None:
        return None
    r, w = run.record, run.work
    least, _ = w.least_time(w.fft_flops(r["n"], r["transforms"]),
                            w.fft_bytes(r["n"], r["transforms"]), run.peak)
    return 100.0 * least / device_s
