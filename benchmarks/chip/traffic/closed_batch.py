"""Closed-loop batches of 1-D transforms.

Each drain submits ``requests`` payloads of (``rows``, ``n``) in the
configuration's ``dtype``, as its ``transform`` at its ``precision``,
made on the host from the seed at set-up and reused (telescope data
arrives in host memory), and waits for every result.  The window ends
when the last drain started within ``--seconds`` completes; the rate is
all the samples of those drains over all of that time.

``correct``: after each drain, ``sample_rows`` rows of every request,
drawn from the seed, are gathered from the receipt's result on the
device; once the window has closed, each is held to the float64 numpy FFT
of its payload row (worst relative L2 error, limit ``limits.fft_rel_l2``).
"""
from __future__ import annotations

import time


def _complex_normal(np, rng, shape, dtype):
    dtype = np.dtype(dtype)
    flat = rng.standard_normal((*shape[:-1], 2 * shape[-1]),
                               dtype=np.finfo(dtype).dtype)
    return flat.view(dtype)


def _serve(ctx, payloads):
    """One drain: submit every payload, drain, and the receipts."""
    svc, c = ctx.svc, ctx.config
    with ctx.annotate("bench.submit"):
        reqs = [svc.submit(p, transform=c["transform"],
                           precision=c["precision"]) for p in payloads]
    with ctx.annotate("bench.drain"):
        svc.drain()
    return [svc.receipt(r) for r in reqs]


def _sample(ctx, recs, srng, rows, k):
    """Gather ``k`` rows of each request's result on the device."""
    np = ctx.np
    out = []
    for i, rec in enumerate(recs):
        idx = np.sort(srng.choice(rows, size=k, replace=False))
        out.append((i, idx, rec.result[idx]))
    return out


def prepare(ctx):
    np, t = ctx.np, ctx.traffic
    rng = np.random.default_rng(ctx.seed)
    payloads = [_complex_normal(np, rng, (t["rows"], t["n"]),
                                ctx.config["dtype"])
                for _ in range(t["requests"])]
    # Warm-up: one drain and one sample gather compile (or load) every
    # program the window runs.
    srng = np.random.default_rng([ctx.seed, 7])
    recs = _serve(ctx, payloads)
    ctx.jax.block_until_ready(
        [dev for _, _, dev in _sample(ctx, recs, srng, t["rows"],
                                      t["sample_rows"])]
        + [r.result for r in recs])
    return {"payloads": payloads}


def window(ctx, state):
    np, t, jax = ctx.np, ctx.traffic, ctx.jax
    payloads = state["payloads"]
    srng = np.random.default_rng([ctx.seed, 1])
    drains = failed = 0
    host_s = 0.0
    samples, drain_s = [], []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        h0 = time.perf_counter()
        recs = _serve(ctx, payloads)
        host_s += time.perf_counter() - h0
        failed += sum(1 for r in recs
                      if r is None or r.status != "served")
        samples.extend(_sample(ctx, recs, srng, t["rows"],
                               t["sample_rows"]))
        with ctx.annotate("bench.wait"):
            jax.block_until_ready([r.result for r in recs])
        drain_s.append(time.perf_counter() - h0)
        drains += 1
    elapsed = time.perf_counter() - t0
    points = drains * len(payloads) * t["rows"] * t["n"]
    return {"attempted": drains * len(payloads), "failed": failed,
            "e2e": {"msamples_per_s": points / elapsed / 1e6},
            "window_s": elapsed, "host_s": host_s, "drains": drains,
            "drain_s": drain_s,
            "n": t["n"], "transforms": drains * len(payloads) * t["rows"],
            "samples": samples}


def _rows(ctx, state, record, fft):
    np, ref = ctx.np, ctx.reference
    worst = 0.0
    for i, idx, dev in record["samples"]:
        x = state["payloads"][i][idx]
        err = ref.rel_l2_rows(fft(x) if fft is not None else np.asarray(dev),
                              ref.fft_f64(x))
        worst = max(worst, float(err.max()))
    return worst


def check(ctx, state, record):
    return [{"name": "fft_rel_l2", "value": _rows(ctx, state, record, None),
             "limit": ctx.traffic["limits"]["fft_rel_l2"]}]


def control(ctx, state, record):
    """The control's reading on the same rows: the FFT one matmul
    precision below the configuration's."""
    ref = ctx.reference
    ref.control_precision(ctx.config["matmul_precision"])
    return {"fft_rel_l2": _rows(ctx, state, record, ref.fft_high)}
