"""Closed-loop batches of real-to-complex transforms.

Each drain submits ``requests`` payloads of (``rows``, ``n``) real
float32 series as ``transform="r2c"`` at the configuration's
``precision``, made on the host from the seed at set-up and reused
(telescope data arrives in host memory), and waits for every result: the
unnormalised ``n // 2 + 1``-bin half spectrum of each row.  The window
ends when the last drain started within ``--seconds`` completes; the rate
is all the input real samples of those drains over all of that time.

``correct``: after each drain, ``sample_rows`` rows of every request,
drawn from the seed, are gathered from the receipt's result on the device
as float32 real and imaginary planes and copied to host memory inside the
window, so the device holds at most one drain's samples.  At n = 2^20 a
sampled row of spectrum is 4 MiB: a drain's 8 rows are 32 MiB over the
link, and a 30 s window's samples a few GB of host memory.  The copy
starts as soon as the gather is enqueued; the host waits for it after the
drain's results.  Planes, because a complex64 array comes off a v5e
about fifteen times slower than its float32 planes: 120 ms against 8 ms
for a drain's 32 MiB, where the drain takes about 0.45 s.  Once the
window has closed, each row is held to ``np.fft.rfft`` of its payload
row in float64 (worst relative L2 error, limit ``limits.fft_rel_l2``).
"""
from __future__ import annotations

import runpy
import time
from pathlib import Path

_serve = runpy.run_path(
    str(Path(__file__).with_name("closed_batch.py")))["_serve"]


def _gather_planes(ctx):
    jnp = ctx.jax.numpy
    return ctx.jax.jit(lambda y, idx: (jnp.real(y[idx]), jnp.imag(y[idx])))


def _sample(ctx, gather, recs, srng, rows, k):
    """Gather ``k`` rows of each request's result on the device, as
    planes, and start their copies to the host."""
    np = ctx.np
    out = []
    for i, rec in enumerate(recs):
        idx = np.sort(srng.choice(rows, size=k, replace=False))
        planes = gather(rec.result, idx)
        for p in planes:
            p.copy_to_host_async()
        out.append((i, idx, planes))
    return out


def _to_host(ctx, picked):
    np = ctx.np
    return [(i, idx, tuple(np.asarray(p) for p in planes))
            for i, idx, planes in picked]


def prepare(ctx):
    np, t = ctx.np, ctx.traffic
    rng = np.random.default_rng(ctx.seed)
    payloads = [rng.standard_normal((t["rows"], t["n"]),
                                    dtype=np.dtype(ctx.config["dtype"]))
                for _ in range(t["requests"])]
    # Warm-up: one drain, one sample gather and its copy compile (or
    # load) every program the window runs.
    gather = _gather_planes(ctx)
    srng = np.random.default_rng([ctx.seed, 7])
    recs = _serve(ctx, payloads)
    _to_host(ctx, _sample(ctx, gather, recs, srng, t["rows"],
                          t["sample_rows"]))
    ctx.jax.block_until_ready([r.result for r in recs])
    return {"payloads": payloads, "gather": gather}


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
        picked = _sample(ctx, state["gather"], recs, srng, t["rows"],
                         t["sample_rows"])
        with ctx.annotate("bench.wait"):
            jax.block_until_ready([r.result for r in recs])
        with ctx.annotate("bench.sample"):
            samples.extend(_to_host(ctx, picked))
        drain_s.append(time.perf_counter() - h0)
        drains += 1
    elapsed = time.perf_counter() - t0
    points = drains * len(payloads) * t["rows"] * t["n"]
    return {"attempted": drains * len(payloads), "failed": failed,
            "e2e": {"msamples_per_s": points / elapsed / 1e6},
            "window_s": elapsed, "host_s": host_s, "drains": drains,
            "drain_s": drain_s, "samples": samples}


def _rows(ctx, state, record, fft):
    """Worst relative L2 error of the sampled rows (``fft`` of each
    payload row in the program's place, if given) against the reference,
    the float64 half spectrum."""
    np, ref = ctx.np, ctx.reference
    worst = 0.0
    for i, idx, (re, im) in record["samples"]:
        x = state["payloads"][i][idx]
        want = np.fft.rfft(x.astype(np.float64), axis=-1)
        err = ref.rel_l2_rows(fft(x) if fft is not None else re + 1j * im,
                              want)
        worst = max(worst, float(err.max()))
    return worst


def check(ctx, state, record):
    return [{"name": "fft_rel_l2", "value": _rows(ctx, state, record, None),
             "limit": ctx.traffic["limits"]["fft_rel_l2"]}]


def control(ctx, state, record):
    """The control's reading on the same rows: the full FFT one matmul
    precision below the configuration's, of each row cast to complex,
    cut to the half spectrum."""
    ref, np = ctx.reference, ctx.np
    ref.control_precision(ctx.config["matmul_precision"])

    def half_high(x):
        return ref.fft_high(x.astype(np.complex64))[..., :x.shape[-1] // 2 + 1]

    return {"fft_rel_l2": _rows(ctx, state, record, half_high)}
