#!/usr/bin/env python3
"""Smoke test of the FFT service on a TPU, end to end, in one process.

Drives ``repro.serving.FFTService`` (submit -> drain -> receipt) through
every route the service has, at the paper's batch sizes (about 1 GiB of
complex64 per drained batch, paper Sec. 4), and checks each phase against
a plain reference that does not use the code under test:

  c2c        4 x (8192, 4096) complex64     single fused pass
  c2c_long   2 x (1024, 65536) complex64    four-step (column + row pass)
  r2c        2 x (128, 2^20) float32        packed R2C on the four-step
  bluestein  4 x (1024, 3000) complex64     non-pow2 route
  fft2       8 x (4096, 4096) complex64     2-D plan graph
  fdas       2 x 2^20-sample series         overlap-save + fused bank mul
  pulsar     2 x (1024 ch, 2^17) filterbanks  dedisp, R2C, FDAS, hsum, sift

Every receipt must be served at rung 0 (the Pallas path; the pure-JAX
rung is never acceptable here) and the service's launch ledger must show
the kernel families the phase routes through.  Each phase prints one
JSON line; the last line is ``{"ok": true, "device": {...}}`` and is
printed only when every check passed.  Timings in the phase lines are
smoke timings (first call includes compilation), not benchmark results.

Usage (from the repository root, no PYTHONPATH needed):

  python chip_smoke.py             # one chip, every phase above
  python chip_smoke.py --chips 4   # four chips: sharded service,
                                   # dispatcher and pencil FFT only
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearse-cpu [--chips 4]
                                   # small-size dry run on the CPU; never
                                   # prints "ok"

Without a TPU the script exits with status 2 before running anything.
The persistent compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Per-transform relative L2 error bound against float64 numpy.
BOUND = 1e-4
#: Sharded service vs the same requests served on one chip.
SHARDED_BOUND = 1e-6
#: Pencil FFT vs the single-device plan (different algorithms).
PENCIL_BOUND = 1e-5
#: Dedispersed series vs the reference (float32 sums, different order).
DEDISP_BOUND = 1e-5

# (requests, rows per request, length) at full size and for the CPU
# rehearsal; for pulsar, (filterbanks, channels, samples).  The fdas
# phase keeps its full length in the rehearsal, and the pulsar phase a
# size that still takes the channel-tiled dedispersion and the packed
# four-step R2C: their injected-signal recovery is what needs rehearsing.
FULL = {
    "c2c": (4, 8192, 4096),
    "c2c_long": (2, 1024, 65536),
    "r2c": (2, 128, 2**20),
    "bluestein": (4, 1024, 3000),
    "fft2": (8, 4096, 4096),
    "fdas": (2, 1, 2**20),
    "pulsar": (2, 1024, 2**17),
}
REHEARSAL = {
    "c2c": (4, 16, 4096),
    "c2c_long": (2, 4, 65536),
    "r2c": (2, 1, 2**20),
    "bluestein": (4, 8, 3000),
    "fft2": (2, 256, 256),
    "fdas": (2, 1, 2**20),
    "pulsar": (2, 128, 2**15),
}
FDAS_TEMPLATES = 16
# The injected amplitude is 0.03 at 64 channels x 2^14 samples and scales
# as 1/sqrt(channels x samples), which holds each pulsar's expected
# normalised power (and so the recovery margin) fixed at every size.
PULSAR = {"dm_trials": 16, "templates": 8, "n_harmonics": 8}
PULSAR_AMP_AT_64x2_14 = 0.03
# Why the full-size pulsar phase stops where it does (tests/
# test_tpu_compile.py compiles both sides of each limit).
PULSAR_SIZE_NOTE = (
    "2^17 samples: the harmonic-sum plane kernel keeps whole padded "
    "spectrum rows in VMEM and exceeds the 16 MiB scoped limit at 2^18 "
    "(24.09 MiB); 1024 channels: a common survey channel count, not a "
    "kernel limit (the channel-tiled dedispersion kernel and the whole "
    "search also compile at 4096 channels), kept so the host-side "
    "reference check stays about a minute")
# (DM trial, template, bin at 2^14 samples) injected per filterbank.  The
# bin scales with the block length: adjacent DM trials differ by ~4
# samples of delay across the band, a phase spread of 2 pi bin 4 / ntime
# at the pulsar's frequency, so a fixed bin would let neighbouring trials
# stay coherent (and noise pick between them) at longer blocks.
PULSAR_INJECTED = [[(4, 6, 1500), (11, 1, 3900)], [(8, 4, 2400)]]
PULSAR_INJECTED_NTIME = 2**14
FDAS_INJECTED = [(11, 200_000), (3, 345_678)]          # (template, bin)


class SmokeError(RuntimeError):
    """A check failed."""


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _complex(rng, shape) -> np.ndarray:
    flat = rng.standard_normal((*shape[:-1], 2 * shape[-1]),
                               dtype=np.float32)
    return flat.view(np.complex64)


def _rel_l2(got, want, axes) -> np.ndarray:
    return (np.linalg.norm(got - want, axis=axes)
            / np.linalg.norm(want, axis=axes))


def _worst_rel_err(got: np.ndarray, x: np.ndarray, ref, axes=-1) -> float:
    """Largest per-transform relative L2 error over every transform
    (leading axis), checked in chunks of about 2^26 points against
    float64 numpy on the host."""
    worst = 0.0
    chunk = max(1, 2**26 // (x[0].size or 1))
    for i in range(0, x.shape[0], chunk):
        want = ref(x[i:i + chunk].astype(np.complex128
                                         if np.iscomplexobj(x)
                                         else np.float64))
        err = _rel_l2(got[i:i + chunk].astype(want.dtype), want, axes)
        worst = max(worst, float(np.max(err)))
    return worst


def _serve(jax, svc, payloads, **submit_kw):
    """Submit every payload, drain once; (receipts, seconds)."""
    t0 = time.perf_counter()
    reqs = [svc.submit(p, **submit_kw) for p in payloads]
    svc.drain()
    recs = [svc.receipt(r) for r in reqs]
    jax.block_until_ready([r.result for r in recs if r is not None])
    return recs, time.perf_counter() - t0


def _launches(recs) -> dict:
    """Kernel launches per family, once per executed batch."""
    counts: collections.Counter = collections.Counter()
    seen = set()
    for r in recs:
        if r.batch_id in seen:
            continue
        seen.add(r.batch_id)
        counts.update(rec.kernel for rec in r.launches)
    return dict(counts)


def _check_receipts(name: str, recs, families: set[str]) -> dict:
    for r in recs:
        if r is None or r.status != "served" or r.rung != 0:
            raise SmokeError(
                f"{name}: receipt not served at rung 0: "
                f"{None if r is None else (r.status, r.rung, r.reason)}")
    launches = _launches(recs)
    missing = families - set(launches)
    if missing:
        raise SmokeError(f"{name}: no launches of {sorted(missing)} in the "
                         f"ledger (saw {launches})")
    return launches


def _run_phase(jax, svc, name, payloads, ref, *, families, axes=-1,
               extra=None, **submit_kw) -> dict:
    """Serve twice (first call, then steady), check the first results."""
    recs, first_s = _serve(jax, svc, payloads, **submit_kw)
    launches = _check_receipts(name, recs, families)
    err = 0.0
    for p, r in zip(payloads, recs):
        got, x = np.asarray(r.result), p
        if axes != -1:                  # one 2-D transform per request
            got, x = got.reshape(-1, *p.shape), p[None]
        err = max(err, _worst_rel_err(got, x, ref, axes))
    del recs
    recs, steady_s = _serve(jax, svc, payloads, **submit_kw)
    _check_receipts(name, recs, families)
    del recs
    line = {
        "phase": name,
        "requests": len(payloads),
        "request_shape": list(payloads[0].shape),
        "dtype": str(payloads[0].dtype),
        "batch_bytes": int(sum(p.nbytes for p in payloads)),
        "rows_checked": "all",
        "rel_l2_err": err,
        "bound": BOUND,
        "launches": launches,
        "rungs": [0],
        "first_call_s_informational": first_s,
        "steady_s_informational": steady_s,
    }
    line.update(extra or {})
    if not err <= BOUND:
        _emit(line)
        raise SmokeError(f"{name}: relative L2 error {err:.3e} > {BOUND}")
    return line


def phase_fft(jax, svc, sizes, rng) -> list[dict]:
    lines = []
    k, rows, n = sizes["c2c"]
    xs = [_complex(rng, (rows, n)) for _ in range(k)]
    lines.append(_run_phase(jax, svc, "c2c", xs, np.fft.fft,
                            families={"fft-c2c"}))
    del xs
    k, rows, n = sizes["c2c_long"]
    xs = [_complex(rng, (rows, n)) for _ in range(k)]
    lines.append(_run_phase(jax, svc, "c2c_long", xs, np.fft.fft,
                            families={"fft-c2c-axis1", "fft-c2c-t"}))
    del xs
    k, rows, n = sizes["r2c"]
    xs = [rng.standard_normal((rows, n), dtype=np.float32)
          for _ in range(k)]
    lines.append(_run_phase(jax, svc, "r2c", xs, np.fft.rfft,
                            families={"fft-c2c-axis1", "fft-c2c-t"},
                            transform="r2c"))
    del xs
    k, rows, n = sizes["bluestein"]
    xs = [_complex(rng, (rows, n)) for _ in range(k)]
    lines.append(_run_phase(jax, svc, "bluestein", xs, np.fft.fft,
                            families={"fft-c2c"}))
    del xs
    k, rows, n = sizes["fft2"]
    xs = [_complex(rng, (rows, n)) for _ in range(k)]
    lines.append(_run_phase(jax, svc, "fft2", xs, np.fft.fft2,
                            families={"fft-c2c-t"}, axes=(-2, -1), ndim=2))
    return lines


def phase_fdas(jax, svc, sizes, rng) -> dict:
    import jax.numpy as jnp
    from repro.search.fdas import fdas_search, serving_candidates
    from repro.search.templates import TemplateBank

    k, _, n = sizes["fdas"]
    bank = TemplateBank.linear(zmax=(FDAS_TEMPLATES - 1) / 2.0,
                               n_templates=FDAS_TEMPLATES)
    s = np.arange(n) / n
    xs = []
    for t, k0 in FDAS_INJECTED[:k]:
        z = bank.drifts[t]
        x = (0.05 * np.cos(2 * np.pi * (k0 * s + 0.5 * z * s * s))
             + rng.standard_normal(n))
        xs.append(x.astype(np.float32)[None])
    recs, first_s = _serve(jax, svc, xs, kind="fdas",
                           templates=FDAS_TEMPLATES)
    launches = _check_receipts("fdas", recs, {"fft-c2c-mul", "fft-c2c"})
    found = []
    for (t, k0), r in zip(FDAS_INJECTED, recs):
        top = np.asarray(r.result)[0, 0]           # (template, bin, power)
        found.append([int(top[0]), int(top[1])])
        if int(top[0]) != t or abs(int(top[1]) - k0) > 1:
            raise SmokeError(f"fdas: top candidate {top[:2]} is not the "
                             f"injected (template {t}, bin {k0})")
    recs2, steady_s = _serve(jax, svc, xs, kind="fdas",
                             templates=FDAS_TEMPLATES)
    _check_receipts("fdas", recs2, {"fft-c2c-mul", "fft-c2c"})
    del recs2

    # The power plane behind those candidates, against a numpy matched
    # filter (float64 FFT convolution) over the same bank.  The receipts
    # carry candidates only, so the plane comes from a direct call of
    # the function the service compiled, on the batch it drained; its
    # candidates must equal the served ones.
    x = np.concatenate(xs)
    direct = fdas_search(jnp.asarray(x), bank)
    plane = np.asarray(direct.power)
    served = np.concatenate([np.asarray(r.result) for r in recs])
    if not np.array_equal(np.asarray(serving_candidates(direct))[..., :2],
                          served[..., :2]):
        raise SmokeError("fdas: the checked plane's candidates differ from "
                         "the served candidates")
    del recs
    xc = x.astype(np.float64) - x.mean(axis=-1, keepdims=True)
    spec = np.fft.rfft(xc, axis=-1)
    nbins = spec.shape[-1]
    sigma2 = np.mean(np.abs(spec) ** 2, axis=-1)[:, None, None]
    taps = bank.time_domain()
    m = 1 << (nbins + bank.taps - 2).bit_length()
    full = np.fft.ifft(np.fft.fft(spec, m)[:, None, :]
                       * np.fft.fft(taps, m)[None], axis=-1)
    want = np.abs(full[..., bank.offset:bank.offset + nbins]) ** 2 / sigma2
    err = float(np.max(_rel_l2(plane.astype(np.float64), want, -1)))
    line = {
        "phase": "fdas", "requests": k, "series_samples": n,
        "templates": FDAS_TEMPLATES, "plane_shape": list(plane.shape),
        "rows_checked": "all", "rel_l2_err": err, "bound": BOUND,
        "plane_source": "direct fdas_search on the drained batch; its "
                        "candidates equal the served ones",
        "injected": [list(i) for i in FDAS_INJECTED[:k]],
        "recovered": found, "launches": launches, "rungs": [0],
        "first_call_s_informational": first_s,
        "steady_s_informational": steady_s,
    }
    if not err <= BOUND:
        _emit(line)
        raise SmokeError(f"fdas: plane relative L2 error {err:.3e} > {BOUND}")
    return line


def phase_pulsar(jax, svc, sizes, seed: int) -> dict:
    import jax.numpy as jnp
    from repro.data.synthetic import (FilterbankSpec, InjectedPulsar,
                                      synthetic_filterbank)
    from repro.kernels.dedisp.ops import dedisperse_kernel
    from repro.fft.plan import MAX_KERNEL_N
    from repro.kernels.dedisp.ref import dedisperse_ref
    from repro.search.pipeline import DispersionPlan
    from repro.search.templates import TemplateBank

    k, nchan, ntime = sizes["pulsar"]
    amp = PULSAR_AMP_AT_64x2_14 * np.sqrt(64 * 2**14 / (nchan * ntime))
    spec = FilterbankSpec(nchan=nchan, ntime=ntime)
    dplan = DispersionPlan.from_spec(spec, n_trials=PULSAR["dm_trials"])
    bank = TemplateBank.linear(zmax=(PULSAR["templates"] - 1) / 2.0,
                               n_templates=PULSAR["templates"])
    injected = [[(d, t, b * ntime // PULSAR_INJECTED_NTIME)
                 for d, t, b in inj] for inj in PULSAR_INJECTED[:k]]
    fbs = []
    for i, inj in enumerate(injected):
        pulsars = tuple(InjectedPulsar(dm=dplan.dms[d], k0=b,
                                       z=bank.drifts[t], amp=amp)
                        for d, t, b in inj)
        fbs.append(synthetic_filterbank(spec, pulsars, noise=1.0,
                                        seed=seed + 100 + i))
    kw = dict(kind="pulsar", dm_trials=PULSAR["dm_trials"],
              templates=PULSAR["templates"],
              n_harmonics=PULSAR["n_harmonics"])
    # The series' R2C is one fused kernel up to 2 * MAX_KERNEL_N samples,
    # a packed four-step (column + row pass) beyond.
    r2c = ({"fft-r2c"} if ntime // 2 <= MAX_KERNEL_N
           else {"fft-c2c-axis1", "fft-c2c-t"})
    families = {"dedisperse", "fft-c2c-mul", "fft-c2c",
                "harmonic-sum-plane"} | r2c
    recs, first_s = _serve(jax, svc, fbs, **kw)
    launches = _check_receipts("pulsar", recs, families)
    found = []
    for inj, r in zip(injected, recs):
        cands = np.asarray(r.result)[0]           # (k, 5): dm, t, bin, ...
        got = sorted((int(c[0]), int(c[1]), int(c[2]))
                     for c in cands if c[0] >= 0)
        found.append(got)
        if got != sorted(inj):
            raise SmokeError(f"pulsar: candidates {got} != injected "
                             f"{sorted(inj)} (misses or false positives)")
    del recs
    recs, steady_s = _serve(jax, svc, fbs, **kw)
    _check_receipts("pulsar", recs, families)
    del recs

    # The receipts carry candidates only: the series comes from a direct
    # call of the kernel the pipeline runs first, on the drained batch.
    fb = np.stack(fbs)
    del fbs
    series = np.asarray(dedisperse_kernel(jnp.asarray(fb), dplan.delays))
    # ref.py gathers a (D, C, N) block per filterbank; the sum over
    # channels is split into slabs of 64 to bound host memory.
    delays = np.asarray(dplan.delays)
    want = np.zeros(series.shape, np.float64)
    with jax.default_device(jax.devices("cpu")[0]):
        for c in range(0, nchan, 64):
            want += np.asarray(dedisperse_ref(jnp.asarray(fb[:, c:c + 64]),
                                              delays[:, c:c + 64]))
    err = float(np.max(_rel_l2(series.astype(np.float64), want, -1)))
    line = {
        "phase": "pulsar", "filterbanks": k, "nchan": nchan,
        "ntime": ntime, **{key: PULSAR[key] for key in
                           ("dm_trials", "templates", "n_harmonics")},
        "injected_amp": float(amp), "size_note": PULSAR_SIZE_NOTE,
        "series_source": "direct dedisperse_kernel on the drained batch",
        "injected": [sorted(i) for i in injected],
        "recovered": found, "false_positives": 0,
        "dedisp_rel_l2_err": err, "dedisp_bound": DEDISP_BOUND,
        "launches": launches, "rungs": [0],
        "first_call_s_informational": first_s,
        "steady_s_informational": steady_s,
    }
    if not err <= DEDISP_BOUND:
        _emit(line)
        raise SmokeError(f"pulsar: dedispersed series error {err:.3e}")
    return line


def phase_four_chips(jax, spec, sizes, rng) -> list[dict]:
    """Sharded service, work-stealing dispatcher and pencil FFT."""
    import jax.numpy as jnp
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec
    from repro.fft.distributed import pencil_fft, untranspose_ref
    from repro.fft.plan import plan_for_length
    from repro.serving import FFTService

    devs = jax.devices()
    if len(devs) != 4:
        raise SmokeError(f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    auto = (AxisType.Auto,)
    k, rows, n = sizes["c2c"]
    xs = [_complex(rng, (rows, n)) for _ in range(k)]

    one = FFTService(spec, devices=devs[:1], max_retained_receipts=8)
    recs, _ = _serve(jax, one, xs)
    _check_receipts("single", recs, {"fft-c2c"})
    single = [np.asarray(r.result) for r in recs]
    del recs, one
    sharded = FFTService(spec, mesh=Mesh(np.array(devs), ("data",),
                                         axis_types=auto),
                         max_retained_receipts=8)
    recs, first_s = _serve(jax, sharded, xs)
    launches = _check_receipts("sharded", recs, {"fft-c2c"})
    err_vs_single = max(
        float(np.max(_rel_l2(np.asarray(r.result), s, -1)))
        for r, s in zip(recs, single))
    err_ref = max(_worst_rel_err(np.asarray(r.result), x, np.fft.fft)
                  for r, x in zip(recs, xs))
    del recs, single
    # Two rows do not divide over four devices: batch_parallel_fft pads
    # them to four, transforms, replicates the result and cuts it back.
    odd = [_complex(rng, (2, n))]
    recs, _ = _serve(jax, sharded, odd)
    _check_receipts("sharded_padded", recs, {"fft-c2c"})
    err_pad = _worst_rel_err(np.asarray(recs[0].result), odd[0], np.fft.fft)
    del recs, sharded
    lines = [{
        "phase": "sharded_service", "devices": 4, "mesh": {"data": 4},
        "requests": k, "request_shape": [rows, n],
        "rel_l2_err_vs_single_chip": err_vs_single,
        "bound_vs_single_chip": SHARDED_BOUND,
        "rel_l2_err_vs_numpy": err_ref, "bound": BOUND,
        "padded_batch_rows": 2, "padded_rel_l2_err_vs_numpy": err_pad,
        "launches": launches, "rungs": [0],
        "first_call_s_informational": first_s}]
    if not (err_vs_single <= SHARDED_BOUND and err_ref <= BOUND
            and err_pad <= BOUND):
        _emit(lines[-1])
        raise SmokeError("sharded service disagrees with one chip")

    # Dispatcher: one request per batch, eight batches over four devices.
    xs = xs + [_complex(rng, (rows, n)) for _ in range(8 - k)]
    disp = FFTService(spec, devices=devs, batch_bytes=xs[0].nbytes,
                      max_retained_receipts=8)
    recs, first_s = _serve(jax, disp, xs)
    launches = _check_receipts("dispatcher", recs, {"fft-c2c"})
    workers = sorted({r.worker for r in recs})
    err = max(_worst_rel_err(np.asarray(r.result), x, np.fft.fft)
              for r, x in zip(recs, xs))
    del recs, disp, xs
    lines.append({
        "phase": "dispatcher", "devices": 4, "requests": 8,
        "request_shape": [rows, n], "workers_used": workers,
        "rel_l2_err": err, "bound": BOUND, "launches": launches,
        "rungs": [0], "first_call_s_informational": first_s})
    if workers != [0, 1, 2, 3] or not err <= BOUND:
        _emit(lines[-1])
        raise SmokeError(f"dispatcher: workers {workers}, error {err:.3e}")

    # Pencil FFT of length 2^24 over the ("model",) axis vs one device.
    n1 = n2 = sizes["pencil"]
    batch = 4
    x = _complex(rng, (batch, n1 * n2))
    mesh = Mesh(np.array(devs), ("model",), axis_types=auto)
    t0 = time.perf_counter()
    xs_dev = jax.device_put(x.reshape(batch, n1, n2), NamedSharding(
        mesh, PartitionSpec(None, "model", None)))
    y = pencil_fft(xs_dev, mesh, n1=n1, n2=n2)
    got = np.asarray(untranspose_ref(jax.device_get(y), n1, n2))
    pencil_s = time.perf_counter() - t0
    with jax.default_device(devs[0]):
        plan = plan_for_length(n1 * n2)
        ref1 = np.asarray(jax.jit(plan.fn)(jnp.asarray(x)))
    err_vs_single = float(np.max(_rel_l2(got, ref1, -1)))
    err_np = _worst_rel_err(got, x, np.fft.fft)
    lines.append({
        "phase": "pencil", "devices": 4, "n": n1 * n2, "n1": n1, "n2": n2,
        "batch": batch, "rel_l2_err_vs_single_device_plan": err_vs_single,
        "bound_vs_single_device_plan": PENCIL_BOUND,
        "rel_l2_err_vs_numpy": err_np, "bound": BOUND,
        "first_call_s_informational": pencil_s})
    if not (err_vs_single <= PENCIL_BOUND and err_np <= BOUND):
        _emit(lines[-1])
        raise SmokeError("pencil FFT disagrees with the single-device plan")
    return lines


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run small sizes on the CPU backend (Pallas in "
                         "interpret mode); never prints an ok line")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    from repro.core.hardware import TPU_V5E, spec_for_device_kind
    from repro.serving import FFTService

    cache_dir = enable_compile_cache()
    compile_s: collections.Counter = collections.Counter()

    def on_duration(event: str, duration: float, **_):
        if "compil" in event:
            compile_s[event] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    spec = (spec_for_device_kind(dev.device_kind) if dev.platform == "tpu"
            else TPU_V5E)
    sizes = dict(REHEARSAL if args.rehearse_cpu else FULL)
    sizes["pencil"] = 2**8 if args.rehearse_cpu else 2**12
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()

    if args.chips == 4:
        lines = phase_four_chips(jax, spec, sizes, rng)
    else:
        svc = FFTService(spec, max_retained_receipts=8)
        lines = phase_fft(jax, svc, sizes, rng)
        for line in lines:
            _emit(line)
        lines = [phase_fdas(jax, svc, sizes, rng),
                 phase_pulsar(jax, svc, sizes, args.seed)]
    for line in lines:
        _emit(line)
    _emit({"phase": "summary", "device_kind": dev.device_kind,
           "spec": spec.name, "compile_cache_dir": cache_dir,
           "compile_events_s": dict(compile_s),
           "wall_s_informational": time.perf_counter() - t0})
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if args.rehearse_cpu:
        _emit({"rehearsal": "passed", "device": device})
    else:
        _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
