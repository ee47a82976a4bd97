"""The dedispersion kernel's share of its roofline: the least time of
D C N adds over (C + D) N float32 of traffic, over the device time of the
trace events whose name the table below holds."""

#: Substrings of the dedispersion kernel's trace names.
NAMES = ("dedisp",)


def read(run):
    device_s = run.op_seconds(
        lambda name: any(k in name.lower() for k in NAMES))
    if device_s <= 0 or run.peak is None:
        return None
    c, w = run.cell.config, run.work
    args = (c["dm_trials"], c["nchan"], c["ntime"],
            run.record["filterbanks"])
    least, _ = w.least_time(w.dedisp_flops(*args), w.dedisp_bytes(*args),
                            run.peak)
    return 100.0 * least / device_s
