"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call is wall time of
the measured JAX call where applicable, else 0; ``derived`` carries the
figure's headline quantity).

  fig4_exec_time        t_fix staircase vs FFT length (measured, CPU)
  fig6_time_vs_freq     t_f/t_d regimes a/b/c (DVFS model, V100+Nano)
  fig7_energy_u_shape   E(f) per batch, N=16k (model)
  fig8_power_vs_freq    average power vs clock (model)
  fig9_optimal_freq     optimal f as % of boost (model)
  table3_mean_optimal   mean optimal clock per device x precision
  fig10_gflops_per_watt efficiency at the optimal clock
  fig11_exec_increase   slowdown at the optimal clock
  fig13_16_ief          efficiency increase vs boost & base clocks
  table4_pipeline       pulsar pipeline w/ per-stage clock locking
  kernels               Pallas kernels vs jnp oracle wall time (compiled on
                        TPU, interpret mode on CPU: an emulator, not a clock)
  fft                   mixed-radix engine: stages, R2C vs C2C wall time,
                        J/transform model -> persists BENCH_fft.json
  fft2                  N-D plan graph: HBM passes vs the per-axis chain,
                        fused four-step parity -> persists BENCH_fft2.json
  fdas                  acceleration search on the overlap-save conv
                        engine: fused-epilogue pass counts, traffic
                        ratio, parity, pulsar recovery
                        -> persists BENCH_fdas.json
  tune                  autotuner smoke: cost-model-pruned search on two
                        lengths, speedup vs heuristic, zero-measurement
                        cache replay -> persists BENCH_autotune.json
  pipeline              end-to-end pulsar search (dedispersion -> FDAS ->
                        fused harmonic sum -> sift): injected-pulsar
                        recovery, no-signal control, per-stage DVFS
                        clocks + J/stage, real-time margin
                        -> persists BENCH_pipeline.json
  roofline              the dry-run roofline table (artifacts)
  dvfs_cells            the paper's technique applied to every dry-run cell
  serving               the energy-aware FFT service on a synthetic stream
  chaos                 deterministic chaos/load harness: a mixed
                        fft/fft2/fdas/pulsar stream under an injected
                        fault schedule (device kills, clock-lock
                        failures, stalls) with SLO admission control —
                        gates the every-request-gets-a-receipt invariant,
                        availability and bit-reproducibility
                        -> persists BENCH_chaos.json
  power                 closed-loop power governance: governed 8-device
                        site convergence under a power cap, watchdog +
                        static-sweep fallback under injected sensor
                        faults, the emergency shed rung, telemetered
                        serving receipts -> persists BENCH_power.json
  obs                   unified observability plane: tracing overhead
                        gate (< 5% on a warm drain), ledger-audited
                        fft2/fdas pass counts, bit-reproducible span +
                        ledger digests across two runs, drift detection
                        under a miscalibrated sensor model
                        -> persists BENCH_obs.json

Usage: ``python benchmarks/run.py [target ...]`` — no arguments runs all.
"""
from __future__ import annotations

import glob
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def _time_fn(fn, *args, **kwargs):
    """The shared warm-up/repeat timing helper (``repro.tune.timing``).

    One implementation serves the fft/fft2/fdas targets AND the autotuner,
    so benchmark and tuner wall-clock figures are methodologically
    identical (same warm-up discipline, same reduction).
    """
    from repro.tune.timing import time_fn
    return time_fn(fn, *args, **kwargs)


def _timeit(fn, *args, n=5, warmup=2, reduce=None):
    """Wall time per call [us]: mean of n by default, or e.g. ``min`` —
    best-of-n is robust to scheduler noise on shared CPUs."""
    mean = (lambda s: sum(s) / len(s))
    return _time_fn(fn, *args, repeats=n, warmup=warmup,
                    reduce=mean if reduce is None else reduce) * 1e6


#: Common envelope version for every persisted BENCH_*.json.  Bump when
#: any emitter's layout changes shape (v2 added the shared
#: schema_version/device stamp and the power target; v3 the journal
#: incarnation id).
BENCH_SCHEMA_VERSION = 3


def _persist(name, out, *, device, incarnation=None):
    """Write ``BENCH_<name>.json`` with the common metadata stamp.

    Every persisted benchmark carries the same envelope — a
    ``schema_version``, the ``device`` whose DeviceSpec the modelled
    numbers are for, and the ``incarnation`` that produced the artifact
    (the journal incarnation for journal-attached runs, the process
    incarnation otherwise) — so downstream tooling parses all of them
    the same way and can tell two generations of the same artifact
    apart.  Keys already present in ``out`` win over the stamp.
    """
    from repro.runtime.journal import process_incarnation
    out = {"schema_version": BENCH_SCHEMA_VERSION, "device": device,
           "backend": jax.default_backend(),
           "incarnation": (incarnation if incarnation is not None
                           else process_incarnation()), **out}
    path = os.path.join(os.path.dirname(__file__), "..",
                        f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    return os.path.abspath(path)


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


# ---------------------------------------------------------------------------

def fig4_exec_time():
    """t_fix staircase: fixed data volume, varying FFT length (measured)."""
    from repro.fft.plan import plan_for_length
    m_bytes = 2**22                                     # 4 MiB on CPU
    for logn in (5, 8, 11, 13, 14, 16):
        n = 2**logn
        batch = max(m_bytes // (n * 8), 1)
        x = (jax.random.normal(jax.random.PRNGKey(0), (batch, n))
             + 1j * jax.random.normal(jax.random.PRNGKey(1), (batch, n))
             ).astype(jnp.complex64)
        plan = plan_for_length(n)
        us = _timeit(jax.jit(plan.fn), x)
        _row(f"fig4_tfix_n{n}", us,
             f"passes={plan.passes};alg={plan.algorithm}")


def fig6_time_vs_freq():
    from repro.core import JETSON_NANO, TESLA_V100, FFTCase, fft_workload
    for dev in (TESLA_V100, JETSON_NANO):
        for n in (2**10, 2**13, 2**14):
            prof = fft_workload(FFTCase(n=n), dev)
            f = dev.frequencies()
            t = prof.time(f, dev)
            _row(f"fig6_{dev.name}_n{n}", 0.0,
                 f"regime={prof.regime(dev)};max_slowdown="
                 f"{t.max()/t[0]:.2f}")


def fig7_energy_u_shape():
    from repro.core import JETSON_NANO, TESLA_V100, FFTCase, fft_workload, \
        sweep
    for dev in (TESLA_V100, JETSON_NANO):
        res = sweep(fft_workload(FFTCase(n=2**14), dev), dev)
        _row(f"fig7_{dev.name}_n16384", 0.0,
             f"opt_mhz={res.optimal.f:.0f};E_opt/E_boost="
             f"{res.optimal.energy/res.boost.energy:.3f}")


def fig8_power_vs_freq():
    from repro.core import (JETSON_NANO, TESLA_V100, FFTCase, PowerModel,
                            evaluate, fft_workload)
    for dev in (TESLA_V100, JETSON_NANO):
        prof = fft_workload(FFTCase(n=2**14), dev)
        pm = PowerModel(dev)
        pts = evaluate(prof, dev, pm, dev.frequencies())
        _row(f"fig8_{dev.name}", 0.0,
             f"P_boost={pts[0].power:.1f}W;"
             f"P_min={min(p.power for p in pts):.1f}W")


def fig9_optimal_freq():
    from repro.core.calibration import calibrate
    from repro.core.hardware import JETSON_NANO, TESLA_V100
    for dev in (TESLA_V100, JETSON_NANO):
        s = calibrate(dev, "fp32")
        fracs = [x.optimal_frequency_frac for x in s.sweeps]
        _row(f"fig9_{dev.name}_fp32", 0.0,
             f"opt_frac_min={min(fracs):.2f};max={max(fracs):.2f}")


def table3_mean_optimal():
    from repro.core.calibration import calibrate, supported_precisions
    from repro.core.hardware import JETSON_NANO, TESLA_V100
    for dev in (TESLA_V100, JETSON_NANO):
        for prec in supported_precisions(dev):
            s = calibrate(dev, prec)
            _row(f"table3_{dev.name}_{prec}", 0.0,
                 f"mean_opt_mhz={s.mean_opt.f_mean:.1f}")


def fig10_gflops_per_watt():
    from repro.core.calibration import calibrate
    from repro.core.hardware import JETSON_NANO, TESLA_V100
    for dev in (TESLA_V100, JETSON_NANO):
        s = calibrate(dev, "fp32")
        effs = [x.optimal.gflops_per_watt for x in s.sweeps]
        _row(f"fig10_{dev.name}_fp32", 0.0,
             f"gflops_per_w_median={np.median(effs):.1f}")


def fig11_exec_increase():
    from repro.core.calibration import calibrate
    from repro.core.hardware import JETSON_NANO, TESLA_V100
    for dev in (TESLA_V100, JETSON_NANO):
        s = calibrate(dev, "fp32")
        _row(f"fig11_{dev.name}_fp32", 0.0,
             f"median_slowdown_pct={100*s.median_slowdown:.2f}")


def fig13_16_ief():
    from repro.core.calibration import calibrate
    from repro.core.hardware import JETSON_NANO, TESLA_V100
    for dev in (TESLA_V100, JETSON_NANO):
        s = calibrate(dev, "fp32")
        base = s.mean_i_ef_base
        _row(f"fig13_{dev.name}_ief_boost", 0.0,
             f"I_ef={s.mean_i_ef_boost:.3f}")
        if base is not None:
            _row(f"fig14_{dev.name}_ief_base", 0.0, f"I_ef={base:.3f}")
        _row(f"fig15_{dev.name}_ief_meanopt_boost", 0.0,
             f"I_ef={s.mean_opt.i_ef_mean:.3f};loss_pp="
             f"{s.mean_opt.loss_pp:.1f}")


def table4_pipeline():
    """Pulsar pipeline with the FFT stage clock-locked (Sec. 5.3)."""
    from repro.core.hardware import TESLA_V100
    from repro.core.scheduler import DVFSScheduler, predicted_pipeline_i_ef
    from repro.core.dvfs import sweep
    from repro.fft.pipeline import (PipelineShape, fft_time_share,
                                    stage_profiles)
    dev = TESLA_V100
    sched = DVFSScheduler(dev)
    for harmonics in (2, 4, 8, 16, 32):
        shape = PipelineShape(batch=32, n=2**20, n_harmonics=harmonics)
        profs = stage_profiles(shape, dev)
        share = fft_time_share(shape, dev)
        fft_res = sweep(profs[0], dev)
        stages = sched.plan(profs,
                            locked={profs[0].name: fft_res.optimal.f})
        rep = sched.evaluate_pipeline(stages)
        pred = predicted_pipeline_i_ef(share, fft_res.i_ef_boost)
        _row(f"table4_h{harmonics}", 0.0,
             f"fft_share={100*share:.1f}%;I_ef={rep.i_ef:.3f};"
             f"share_arith_pred={pred:.3f};slowdown={100*rep.slowdown:.2f}%")


def kernels():
    from repro.kernels.fft.ops import fft_kernel_c2c
    from repro.kernels.harmonic_sum.ops import harmonic_sum_kernel
    from repro.kernels.spectrum.ops import power_spectrum_stats_kernel
    x = (jax.random.normal(jax.random.PRNGKey(0), (16, 2048))
         + 1j * jax.random.normal(jax.random.PRNGKey(1), (16, 2048))
         ).astype(jnp.complex64)
    mode = f"backend={jax.default_backend()}"
    us = _timeit(fft_kernel_c2c, x, n=3)
    ref = _timeit(jax.jit(jnp.fft.fft), x, n=3)
    _row("kernel_fft_2048x16", us, f"jnp_ref_us={ref:.1f} {mode}")
    p = jnp.abs(x) ** 2
    us = _timeit(lambda v: harmonic_sum_kernel(v, 32), p, n=3)
    _row("kernel_harmonic_sum_32", us, f"levels=6 {mode}")
    us = _timeit(power_spectrum_stats_kernel, x, n=3)
    _row("kernel_spectrum_stats", us, f"fused=power+mean+var {mode}")


def roofline():
    """The dry-run roofline table (reads artifacts/dryrun/*.json)."""
    from repro.analysis.roofline import roofline_from_artifact
    paths = sorted(glob.glob(os.path.join(ART, "*.json")))
    if not paths:
        _row("roofline", 0.0, "no-artifacts-run-dryrun-first")
        return
    from repro.configs import ARCHS
    for p in paths:
        if os.path.basename(p).split("__")[0] not in ARCHS:
            continue                      # fft-pencil handled separately
        t = roofline_from_artifact(p)
        r = t.row()
        _row(f"roofline_{t.arch}_{t.shape}_{t.mesh}", 0.0,
             f"bound={r['bound']};compute_ms={r['compute_ms']};"
             f"memory_ms={r['memory_ms']};coll_ms={r['collective_ms']};"
             f"useful={r['useful_ratio']};mfu={r['mfu_roofline']}")


def dvfs_cells():
    """The paper's technique applied to every lowered cell: optimal clock,
    predicted energy saving and slowdown — the headline integration."""
    from repro.analysis.roofline import roofline_from_artifact
    from repro.core.dvfs import sweep
    from repro.core.hardware import TPU_V5E
    from repro.core.workloads import roofline_workload
    paths = sorted(glob.glob(os.path.join(ART, "*__16x16.json")))
    from repro.configs import ARCHS
    for p in paths:
        if os.path.basename(p).split("__")[0] not in ARCHS:
            continue
        t = roofline_from_artifact(p)
        prof = roofline_workload(
            f"{t.arch}-{t.shape}", TPU_V5E, hlo_flops=t.hlo_flops,
            hbm_bytes=t.hbm_bytes, collective_bytes=t.collective_bytes,
            useful_flops=t.model_flops / t.chips, issue_efficiency=0.75)
        res = sweep(prof, TPU_V5E, time_budget=0.10)    # real-time margin
        _row(f"dvfs_{t.arch}_{t.shape}", 0.0,
             f"opt_mhz={res.optimal.f:.0f};power_cut="
             f"{100*res.power_reduction:.0f}%;slowdown="
             f"{100*res.slowdown:.1f}%;I_ef={res.i_ef_boost:.2f}")


def conclusions_cost_co2():
    """Paper Conclusions: recurrent cost + CO2 saving over years of
    operation.  Scenario: one 256-chip v5e pod serving decode traffic
    24/7 at the DVFS plan vs boost clocks (0.25 $/kWh, 0.4 kgCO2/kWh)."""
    from repro.analysis.roofline import roofline_from_artifact
    from repro.core.dvfs import sweep
    from repro.core.hardware import TPU_V5E
    from repro.core.realtime import CostModel
    from repro.core.workloads import roofline_workload
    path = os.path.join(ART, "codeqwen1.5-7b__decode_32k__16x16.json")
    if not os.path.exists(path):
        _row("cost_co2", 0.0, "no-artifacts")
        return
    t = roofline_from_artifact(path)
    prof = roofline_workload("decode", TPU_V5E, hlo_flops=t.hlo_flops,
                             hbm_bytes=t.hbm_bytes,
                             collective_bytes=t.collective_bytes,
                             issue_efficiency=0.75)
    res = sweep(prof, TPU_V5E, time_budget=0.10)
    cm = CostModel(device_cost=0.0, energy_cost=0.25, years=5.0)
    chips = 256
    kwh_saved = ((res.boost.power - res.optimal.power) / 1000.0
                 * 24 * 365 * 5 * chips)
    _row("conclusions_cost_co2", 0.0,
         f"pod_power_boost={res.boost.power*chips/1000:.1f}kW;"
         f"pod_power_opt={res.optimal.power*chips/1000:.1f}kW;"
         f"5yr_saving_usd={kwh_saved*0.25:,.0f};"
         f"5yr_co2_tonnes={kwh_saved*0.4/1000:,.0f}")


def fft_pencil_roofline():
    """The paper's own workload on the production mesh (fft_dryrun)."""
    for mesh in ("16x16", "2x16x16"):
        p = os.path.join(ART, f"fft-pencil__c2c_4096x8192_b64__{mesh}.json")
        if not os.path.exists(p):
            continue
        a = json.load(open(p))
        _row(f"fft_pencil_{mesh}", 0.0,
             f"coll_dev={a['collective_bytes_per_device']:.3e};"
             f"flops_dev={a['flops_per_device']:.3e};"
             f"fits={a['memory']['fits_16gb']}")


def fft():
    """FFT plan microbench — persists BENCH_fft.json.

    Per length 2^10..2^22: plan route, HBM passes, the kernels' DFT-matrix
    products, the pure-JAX engine's butterfly stage count (radix-2 vs
    mixed-radix), modelled J/transform at the optimal clock
    (C2C vs R2C), and measured wall time (C2C vs R2C) through the routed
    plans (Pallas kernel in interpret mode off-TPU).  Long lengths are
    wall-timed only up to REPRO_FFT_BENCH_MAX_LOG2_WALL (default 13) —
    interpret mode is an emulator, not a clock; the analytic rows still
    cover the full range.
    """
    from repro.core.dvfs import energy_per_transform, sweep
    from repro.core.hardware import TESLA_V100
    from repro.core.workloads import FFTCase, fft_workload
    from repro.fft.plan import _four_step_split, plan_for_length
    from repro.fft.radix import DEFAULT_RADICES, stage_count

    wall_max = int(os.environ.get("REPRO_FFT_BENCH_MAX_LOG2_WALL", "13"))
    dev = TESLA_V100
    rows = []
    for logn in range(10, 23):
        n = 2**logn
        plan_c = plan_for_length(n)
        plan_r = plan_for_length(n, "r2c")
        # Butterfly stages of the pure-JAX Stockham engine (the bottom
        # degradation rung; the Pallas kernels run DFT-matrix products,
        # ``plan.stages``), summed over the plan's pow2 passes for both
        # radix sets (a radix-2 four-step runs log2(n1)+log2(n2) stages).
        parts = (_four_step_split(n) if plan_c.algorithm == "four-step"
                 else (n,))
        stages_r2 = sum(stage_count(p, (2,)) for p in parts)
        stages_mixed = sum(stage_count(p, DEFAULT_RADICES) for p in parts)
        row = {
            "n": n,
            "algorithm": plan_c.algorithm,
            "passes_c2c": plan_c.passes,
            "passes_r2c": plan_r.passes,
            "dft_products": plan_c.stages,
            "stages_radix2": stages_r2,
            "stages_mixed": stages_mixed,
            "stage_ratio": stages_r2 / max(stages_mixed, 1),
        }
        for transform, plan in (("c2c", plan_c), ("r2c", plan_r)):
            case = FFTCase(n=n, transform=transform, radices=(4, 2))
            res = sweep(fft_workload(case, dev), dev)
            per = energy_per_transform(res, case.n_fft)
            row[f"model_j_per_fft_{transform}"] = per["optimal_j"]
            row[f"model_j_per_fft_{transform}_boost"] = per["boost_j"]
        if logn <= wall_max:
            batch = max(2**19 // n, 16)
            key = jax.random.PRNGKey(0)
            xr = jax.random.normal(key, (batch, n), jnp.float32)
            xc = (xr + 1j * jax.random.normal(key, (batch, n))
                  ).astype(jnp.complex64)
            row["batch"] = batch
            row["wall_us_c2c"] = _timeit(jax.jit(plan_c.fn), xc,
                                         n=7, warmup=3, reduce=min)
            row["wall_us_r2c"] = _timeit(jax.jit(plan_r.fn), xr,
                                         n=7, warmup=3, reduce=min)
            row["r2c_over_c2c"] = row["wall_us_r2c"] / row["wall_us_c2c"]
        rows.append(row)
        _row(f"fft_n{n}", row.get("wall_us_c2c", 0.0),
             f"alg={row['algorithm']};stages={row['stages_mixed']}v"
             f"{row['stages_radix2']};"
             f"r2c_ratio={row.get('r2c_over_c2c', float('nan')):.2f}")

    by_n = {r["n"]: r for r in rows}
    head = by_n[4096]
    out = {
        "device_model": dev.name,
        "radices": [4, 2],
        "backend": jax.default_backend(),
        # Headline acceptance figures at N = 2^12 (single fused pass).
        "criteria": {
            "stage_ratio_n4096": head["stage_ratio"],
            "r2c_over_c2c_wall_n4096": head.get("r2c_over_c2c"),
        },
        "lengths": rows,
    }
    path = _persist("fft", out, device=dev.name)
    _row("fft_bench_json", 0.0,
         f"written={path};"
         f"stage_ratio_n4096={head['stage_ratio']:.2f};"
         f"r2c_over_c2c_n4096={head.get('r2c_over_c2c', float('nan')):.2f}")


def fft2():
    """N-D plan-graph microbench — persists BENCH_fft2.json.

    Per 2-D shape: HBM passes of the plan graph vs the per-axis moveaxis
    chain (the acceptance >= 2x reduction for pow2 shapes), modelled
    J/transform at the boost vs the optimal clock (C2C and R2C), and
    measured wall time through the fused kernels (interpret mode
    off-TPU).  Also records the four-step headline: the long-1-D plan is
    two fused kernel passes with parity vs jnp.fft.fft at 1e-4 rtol.
    """
    from repro.core.dvfs import energy_per_transform, sweep
    from repro.core.hardware import TESLA_V100
    from repro.core.workloads import FFTCase, fft_workload
    from repro.fft.multidim import fft2 as fft2d, rfft2
    from repro.fft.plan import plan_for_length
    from repro.fft.plan_nd import plan_nd

    wall_max = int(os.environ.get("REPRO_FFT_BENCH_MAX_LOG2_WALL", "13"))
    dev = TESLA_V100
    shapes = [(64, 64), (128, 128), (256, 256), (512, 512),
              (1024, 1024), (2048, 2048), (100, 128), (12, 1024)]
    rows = []
    for shape in shapes:
        plan_c = plan_nd(shape)
        plan_r = plan_nd(shape, "r2c")
        row = {
            "shape": list(shape),
            "n": plan_c.n,
            "nodes": [n.op for n in plan_c.nodes],
            "passes_plan": plan_c.passes,
            "passes_chain": plan_c.chain_passes,
            "pass_reduction": plan_c.chain_passes / plan_c.passes,
            "passes_plan_r2c": plan_r.passes,
        }
        for transform, plan in (("c2c", plan_c), ("r2c", plan_r)):
            case = FFTCase(shape=shape, transform=transform, radices=(4, 2))
            res = sweep(fft_workload(case, dev), dev)
            per = energy_per_transform(res, case.n_fft)
            row[f"model_j_per_fft_{transform}"] = per["optimal_j"]
            row[f"model_j_per_fft_{transform}_boost"] = per["boost_j"]
        if math.log2(plan_c.n) <= wall_max:
            batch = max(2**18 // plan_c.n, 2)
            key = jax.random.PRNGKey(0)
            xr = jax.random.normal(key, (batch, *shape), jnp.float32)
            xc = (xr + 1j * jax.random.normal(key, (batch, *shape))
                  ).astype(jnp.complex64)
            row["batch"] = batch
            row["wall_us_c2c"] = _timeit(jax.jit(plan_c.fn), xc,
                                         n=5, warmup=2, reduce=min)
            row["wall_us_r2c"] = _timeit(jax.jit(plan_r.fn), xr,
                                         n=5, warmup=2, reduce=min)
            row["r2c_over_c2c"] = row["wall_us_r2c"] / row["wall_us_c2c"]
        rows.append(row)
        _row(f"fft2_{shape[0]}x{shape[1]}", row.get("wall_us_c2c", 0.0),
             f"passes={row['passes_plan']}v{row['passes_chain']};"
             f"nodes={'+'.join(row['nodes'])}")

    # Four-step headline: two fused passes + tight parity.  The pass
    # count is no longer taken from the plan's own claim: an eager run
    # inside a launch-ledger capture records the actual Pallas launches,
    # and the criteria report what the ledger saw.
    from repro.obs.ledger import LaunchLedger
    n4 = 2**14
    plan4 = plan_for_length(n4)
    x = (jax.random.normal(jax.random.PRNGKey(1), (2, n4)) +
         1j * jax.random.normal(jax.random.PRNGKey(2), (2, n4))
         ).astype(jnp.complex64)
    led4 = LaunchLedger()
    with led4.capture():
        got = np.asarray(plan4(x))
    four_step_counts = led4.counts()
    four_step_launches = sum(n for k, n in four_step_counts.items()
                             if k.startswith("fft-"))
    want = np.fft.fft(np.asarray(x), axis=-1)
    four_step_rel = float(np.abs(got - want).max() / np.abs(want).max())
    _row("fft2_four_step", 0.0,
         f"passes={four_step_launches};rel_err={four_step_rel:.2e};"
         f"ledger={'+'.join(f'{k}:{v}' for k, v in four_step_counts.items())}")

    # Ledger audit of the pow2 2-D claim on the smallest measured shape.
    x64 = (jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64))
           ).astype(jnp.complex64)
    led2 = LaunchLedger()
    with led2.capture():
        jax.block_until_ready(plan_nd((64, 64)).fn(x64))
    pow2_2d_ledger = led2.counts().get("fft-c2c-t", 0)

    pow2_rows = [r for r in rows if all(
        d & (d - 1) == 0 for d in r["shape"])]
    out = {
        "device_model": dev.name,
        "backend": jax.default_backend(),
        "criteria": {
            # Acceptance: >= 2x HBM-pass reduction for pow2 2-D shapes.
            "min_pass_reduction_pow2_2d": min(
                r["pass_reduction"] for r in pow2_rows),
            "pow2_2d_passes": max(r["passes_plan"] for r in pow2_rows),
            # Ledger audit: launches actually recorded by an eager run of
            # the 64x64 plan must equal the plan's claimed pass count.
            "pow2_2d_passes_ledger": pow2_2d_ledger,
            "pow2_2d_ledger_ok": pow2_2d_ledger == plan_nd((64, 64)).passes,
            # Acceptance: four-step = 2 fused passes, 1e-4 parity.  The
            # pass count is read from the launch ledger, not asserted.
            "four_step_passes": four_step_launches,
            "four_step_ledger_kernels": four_step_counts,
            "four_step_ledger_ok": four_step_launches == plan4.passes,
            "four_step_rel_err": four_step_rel,
            "four_step_parity_1e4": four_step_rel < 1e-4,
        },
        "shapes": rows,
    }
    path = _persist("fft2", out, device=dev.name)
    _row("fft2_bench_json", 0.0,
         f"written={path};"
         f"min_pass_reduction={out['criteria']['min_pass_reduction_pow2_2d']:.2f};"
         f"four_step_rel={four_step_rel:.2e}")


def fdas():
    """FDAS + overlap-save convolution engine — persists BENCH_fdas.json.

    Records the engine's pass accounting (one fused forward pass feeding
    the whole bank, T inverse passes, zero standalone multiply passes),
    the overlap-save vs direct pad-to-full-length traffic ratio, parity
    of the matched-filter plane against a direct ``jnp.fft``-based
    convolution oracle, recovery of an injected accelerated pulsar at
    its (template, bin) cell, and the per-stage DVFS play on the search
    pipeline (where the FFT-class share is far higher than the
    harmonic-sum demo's).
    """
    from repro.core.dvfs import sweep
    from repro.core.hardware import TESLA_V100
    from repro.core.scheduler import DVFSScheduler
    from repro.core.workloads import ConvCase, fdas_workload
    from repro.search import (TemplateBank, fdas_conv_plan, fdas_search,
                              matched_filter_plane)

    n = 2**13                                   # series length (CI-sized)
    bank = TemplateBank.linear(zmax=8, n_templates=9)
    t = bank.n_templates
    nbins = n // 2 + 1
    plan = fdas_conv_plan(n, bank)

    # --- parity: overlap-save plane vs direct pad-to-full-length oracle --
    rng = np.random.default_rng(0)
    spec = (rng.standard_normal((2, nbins))
            + 1j * rng.standard_normal((2, nbins))).astype(np.complex64)
    # The eager plane run is captured by a launch ledger, so the pass
    # claims below are audited against recorded Pallas launches rather
    # than restated from the plan's own accounting.
    from repro.obs.ledger import LaunchLedger
    ledger = LaunchLedger()
    with ledger.capture():
        got = np.asarray(matched_filter_plane(jnp.asarray(spec), bank))
    lcounts = ledger.counts()
    inv_records = [r for r in ledger.records if r.kernel == "fft-c2c"]
    # One batched inverse launch covers every (row, segment, template)
    # plane; T falls out of its recorded shape.
    inv_planes = (inv_records[0].shape[0]
                  // (spec.shape[0] * plan.n_segments)
                  if inv_records else 0)
    taps = bank.time_domain()
    m = 1 << (nbins + bank.taps - 2).bit_length()
    xs = np.fft.fft(spec, m, axis=-1)
    hs = np.fft.fft(taps, m, axis=-1)
    full = np.fft.ifft(xs[:, None, :] * hs[None], axis=-1)
    want = full[..., bank.offset:bank.offset + nbins]
    rel = float(np.abs(got - want).max() / np.abs(want).max())

    # --- injected accelerated pulsar ------------------------------------
    k0, z = 1200, 6.0
    s = np.arange(n) / n
    x = (0.25 * np.cos(2 * np.pi * (k0 * s + 0.5 * z * s * s))
         + 0.5 * rng.standard_normal(n)).astype(np.float32)[None]
    us = _timeit(lambda v: fdas_search(v, bank).power, jnp.asarray(x),
                 n=3, warmup=1)
    res = fdas_search(jnp.asarray(x), bank)
    power = np.asarray(res.power)[0]
    t_hit, b_hit = np.unravel_index(int(power.argmax()), power.shape)
    t_want = int(np.argmin(np.abs(np.array(bank.drifts) - z)))
    recovered = bool(t_hit == t_want and abs(b_hit - k0) <= 1)

    # --- DVFS: clock-lock the FFT-class stages --------------------------
    dev = TESLA_V100
    case = ConvCase(n=nbins, templates=t, taps=bank.taps)
    profs = fdas_workload(case, dev, series_n=n)
    sched = DVFSScheduler(dev)
    locked = {}
    for p in profs[:2]:                         # R2C + convolution stages
        locked[p.name] = sweep(p, dev).optimal.f
    rep = sched.evaluate_pipeline(sched.plan(profs, locked))
    times = [sweep(p, dev).boost.time for p in profs]
    fft_share = sum(times[:2]) / sum(times)

    _row("fdas_plane", us,
         f"nfft={plan.nfft};segments={plan.n_segments};"
         f"fwd_passes={plan.forward_passes};inv_passes={plan.inverse_passes};"
         f"traffic_ratio={plan.traffic_ratio:.2f};rel_err={rel:.2e}")
    _row("fdas_recovery", 0.0,
         f"template={t_hit}(want {t_want});bin={b_hit}(want {k0});"
         f"ok={recovered}")
    _row("fdas_dvfs", 0.0,
         f"fft_class_share={100*fft_share:.1f}%;I_ef={rep.i_ef:.3f};"
         f"slowdown={100*rep.slowdown:.2f}%")

    out = {
        "device_model": dev.name,
        "backend": jax.default_backend(),
        "series_n": n,
        "templates": t,
        "taps": bank.taps,
        "criteria": {
            # Acceptance: fused epilogues — forward + T inverse passes,
            # no standalone multiply pass.  Audited from the launch
            # ledger: one fft-c2c-mul launch (fused forward + bank
            # multiply), one batched inverse launch whose recorded shape
            # covers the T template planes.
            "forward_passes": plan.forward_passes,
            "inverse_passes": plan.inverse_passes,
            "forward_launches_ledger": lcounts.get("fft-c2c-mul", 0),
            "inverse_launches_ledger": lcounts.get("fft-c2c", 0),
            "inverse_planes_ledger": inv_planes,
            "ledger_audit_ok": (
                lcounts.get("fft-c2c-mul", 0) == plan.forward_passes
                and lcounts.get("fft-c2c", 0) == 1
                and inv_planes == plan.inverse_passes == t),
            "passes_per_template": plan.passes_per_template,
            "traffic_ratio_os_vs_direct": plan.traffic_ratio,
            # Acceptance: plane parity vs the direct oracle at 1e-4.
            "plane_rel_err": rel,
            "plane_parity_1e4": rel < 1e-4,
            # Acceptance: injected pulsar at the right (template, bin).
            "recovered_template": int(t_hit),
            "expected_template": t_want,
            "recovered_bin": int(b_hit),
            "expected_bin": k0,
            "recovered_ok": recovered,
        },
        "plan": {
            "nfft": plan.nfft,
            "step": plan.step,
            "n_segments": plan.n_segments,
            "os_bytes_per_row": plan.os_bytes,
            "direct_bytes_per_row": plan.direct_bytes,
        },
        "dvfs": {
            "fft_class_share": fft_share,
            "i_ef": rep.i_ef,
            "slowdown": rep.slowdown,
            "locked_mhz": locked,
        },
    }
    path = _persist("fdas", out, device=dev.name)
    _row("fdas_bench_json", 0.0,
         f"written={path};"
         f"traffic_ratio={plan.traffic_ratio:.2f};"
         f"parity={rel:.2e};recovered={recovered}")


def tune():
    """Autotuner smoke — persists BENCH_autotune.json.

    Tunes two small lengths end to end in interpret mode (candidate
    generation -> cost-model pruning -> measured survivors -> persisted
    choice), then reloads the persisted cache and replays both keys to
    prove the second run re-measures NOTHING, and reports the paper's
    Sec. 4 "common configuration" result on the software axis.

    Acceptance: ``speedup_vs_heuristic >= 1.0`` for every tuned length
    (the tuner may return the heuristic but never regress it — the
    heuristic's latency is the real-time bound) and a recorded cache-hit
    replay with zero measurements.
    """
    import tempfile
    from repro.tune import TuningCache, common_config, tune_length

    lengths = (256, 512)
    cache_file = os.path.join(tempfile.mkdtemp(prefix="repro-tune-bench-"),
                              "tune_cache.json")
    cache = TuningCache.load(path=cache_file)
    rows = []
    for n in lengths:
        res = tune_length(n, cache=cache, objective="energy",
                          repeats=3, warmup=1, save=False)
        rows.append({
            "n": n,
            "objective": res.record.objective,
            "chosen_config": res.config.to_dict(),
            "heuristic_config": res.record.heuristic.to_dict(),
            "wall_us_chosen": res.record.measured_s * 1e6,
            "wall_us_heuristic": res.record.heuristic_s * 1e6,
            "speedup_vs_heuristic": res.speedup_vs_heuristic,
            "candidates_generated": res.record.candidates,
            "candidates_measured": res.record.measured,
            "measurements": res.measurements,
        })
        _row(f"tune_n{n}", res.record.measured_s * 1e6,
             f"source={res.config.source};"
             f"speedup={res.speedup_vs_heuristic:.3f};"
             f"pruned={res.record.candidates}->{res.record.measured}")
    cache.save(cache_file)

    # --- cache-hit replay: a fresh process-equivalent load re-measures
    # nothing and returns the identical choice ------------------------------
    cache2 = TuningCache.load(path=cache_file)
    replays = []
    for row in rows:
        rep = tune_length(row["n"], cache=cache2)
        replays.append({
            "n": row["n"],
            "replayed": rep.replayed,
            "measurements": rep.measurements,
            "config_matches": rep.config.to_dict() == row["chosen_config"],
        })
    common, regret = common_config(cache2)
    _row("tune_replay", 0.0,
         f"cache_hits={sum(r['replayed'] for r in replays)};"
         f"re_measurements={sum(r['measurements'] for r in replays)};"
         f"common_src={common.source};common_regret={regret:.4f}")

    out = {
        "backend": jax.default_backend(),
        "device": cache.device,
        "cache_file": cache_file,
        "criteria": {
            # Acceptance: never regress the heuristic, per tuned length.
            "min_speedup_vs_heuristic": min(
                r["speedup_vs_heuristic"] for r in rows),
            "speedup_ok": all(
                r["speedup_vs_heuristic"] >= 1.0 for r in rows),
            # Acceptance: second run replays from the persisted cache
            # with zero re-measurement.
            "cache_hit_replays": sum(r["replayed"] for r in replays),
            "replay_measurements": sum(r["measurements"] for r in replays),
            "replay_configs_match": all(
                r["config_matches"] for r in replays),
        },
        "lengths": rows,
        "replays": replays,
        "common_config": {
            "config": common.to_dict(),
            "mean_regret": regret,
        },
    }
    path = _persist("autotune", out, device=cache.device)
    _row("tune_bench_json", 0.0,
         f"written={path};"
         f"min_speedup={out['criteria']['min_speedup_vs_heuristic']:.3f};"
         f"replay_measurements="
         f"{out['criteria']['replay_measurements']}")


def pipeline():
    """End-to-end pulsar search with per-stage DVFS — BENCH_pipeline.json.

    Runs the jitted ``repro.search.pipeline.pulsar_search`` graph
    (dedispersion -> FDAS -> fused harmonic sum -> sift) on a synthetic
    filterbank with two injected binary pulsars plus a noise-only
    control, and prices the four-stage DVFS plan on the V100 model.

    Self-checked acceptance (CI gates on a non-zero exit):
      * every injected pulsar is recovered at its exact
        (DM trial, template, bin) cell — no extras, no misses;
      * the no-signal control yields zero candidates;
      * the per-stage-locked pipeline stays real time
        (S = t_acquire / t_process >= 1).
    """
    from repro.core.hardware import TESLA_V100
    from repro.data.synthetic import (FilterbankSpec, InjectedPulsar,
                                      synthetic_filterbank)
    from repro.search import (DispersionPlan, TemplateBank,
                              plan_pulsar_stages, pulsar_search)

    spec = FilterbankSpec(nchan=16, ntime=2048)
    plan = DispersionPlan.from_spec(spec, n_trials=8)
    bank = TemplateBank.linear(zmax=4.0, n_templates=5)
    n_harmonics = 8
    # (DM trial, template, bin, drift): drifts (-4,-2,0,2,4) -> z=2 is
    # template 3, z=-4 template 0
    injected = [(3, 3, 300, 2.0), (6, 0, 611, -4.0)]
    pulsars = tuple(InjectedPulsar(dm=plan.dms[d], k0=b, z=z, amp=0.12)
                    for d, _, b, z in injected)
    fb = jnp.asarray(synthetic_filterbank(spec, pulsars, noise=1.0, seed=2))

    def run(v):
        return pulsar_search(v, plan, bank, n_harmonics=n_harmonics)

    us = _timeit(lambda v: run(v).candidates.snr, fb, n=3, warmup=1)
    c = run(fb).candidates
    got = sorted((int(d), int(t), int(b))
                 for d, t, b in zip(c.dm[0], c.template[0], c.bin[0])
                 if int(d) >= 0)
    want = sorted((d, t, b) for d, t, b, _ in injected)
    recovered_ok = got == want

    quiet = jnp.asarray(synthetic_filterbank(spec, (), noise=1.0, seed=3))
    false_pos = int((np.asarray(run(quiet).candidates.dm) >= 0).sum())

    dev = TESLA_V100
    sp = plan_pulsar_stages(spec, plan, bank, n_harmonics, dev)
    margin = sp.realtime_margin
    realtime_ok = margin >= 1.0

    _row("pipeline_search", us,
         f"recovered={got};want={want};ok={recovered_ok};"
         f"false_positives={false_pos}")
    for s in sp.report.stages:
        _row(f"pipeline_stage_{s.name}", 0.0,
             f"clock={s.f:.0f}MHz;time={s.time:.3e}s;energy={s.energy:.3e}J")
    _row("pipeline_dvfs", 0.0,
         f"I_ef={sp.report.i_ef:.3f};slowdown={100*sp.report.slowdown:.2f}%;"
         f"realtime_margin={margin:.1f}")

    out = {
        "device_model": dev.name,
        "backend": jax.default_backend(),
        "filterbank": {"nchan": spec.nchan, "ntime": spec.ntime,
                       "tsamp": spec.tsamp, "t_acquire": spec.t_acquire},
        "search": {"dm_trials": plan.n_trials,
                   "templates": bank.n_templates,
                   "n_harmonics": n_harmonics},
        "criteria": {
            # Acceptance: exact-cell recovery, zero false positives,
            # real-time at the per-stage locks.
            "injected": want,
            "recovered": got,
            "recovered_ok": recovered_ok,
            "false_positives": false_pos,
            "realtime_margin": margin,
            "realtime_ok": realtime_ok,
        },
        "dvfs": {
            "locked_mhz": sp.locked,
            "stages": [{"name": s.name, "clock_mhz": s.f,
                        "time_s": s.time, "energy_j": s.energy}
                       for s in sp.report.stages],
            "i_ef": sp.report.i_ef,
            "slowdown": sp.report.slowdown,
            "rows_per_batch": sp.case.n_rows,
        },
    }
    path = _persist("pipeline", out, device=dev.name)
    _row("pipeline_bench_json", 0.0,
         f"written={path};recovered={recovered_ok};"
         f"false_positives={false_pos};realtime_margin={margin:.1f}")
    if not (recovered_ok and false_pos == 0 and realtime_ok):
        raise SystemExit(
            f"pipeline self-check failed: recovered={got} (want {want}), "
            f"false_positives={false_pos}, realtime_margin={margin:.2f}")


def _synthetic_stream(rng, lengths, n_requests):
    """A repeated-shape request stream: (payload, length) tuples."""
    stream = []
    for i in range(n_requests):
        n = lengths[i % len(lengths)]
        b = 1 + int(rng.integers(0, 4))
        x = (rng.standard_normal((b, n))
             + 1j * rng.standard_normal((b, n))).astype(np.complex64)
        stream.append(x)
    return stream


def serving():
    """Energy-aware FFT service vs naive per-request execution.

    Reports service-level joules-per-transform, p50/p99 latency, cache
    behaviour (a repeated-shape stream must sweep each shape exactly once),
    and batched vs per-request throughput.
    """
    from repro.core.hardware import TPU_V5E
    from repro.serving import FFTService

    rng = np.random.default_rng(0)
    lengths = [1024, 4096, 1024, 2048]            # repeated shapes on purpose
    stream = _synthetic_stream(rng, lengths, n_requests=64)

    def play(service, stream, wave):
        """Stream requests in waves; returns (wall time, pass receipts).

        Each drain is one serving cycle — every wave after the first hits
        the plan/sweep cache (no re-sweep).
        """
        receipts = []
        t0 = time.perf_counter()
        for start in range(0, len(stream), wave):
            for x in stream[start:start + wave]:
                service.submit(x)
            receipts.extend(service.drain())
        return time.perf_counter() - t0, receipts

    svc = FFTService(TPU_V5E, keep_results=False)
    naive = FFTService(TPU_V5E, keep_results=False, coalesce_requests=False)
    # Warm both services (JIT compilation is one-time in a long-running
    # server), then measure a steady-state pass.
    play(svc, stream, wave=8)
    play(naive, stream, wave=8)
    wall_batched, steady = play(svc, stream, wave=8)
    rep = svc.report()
    wall_naive, steady_naive = play(naive, stream, wave=8)
    nrep = naive.report()
    # Steady-state figures come from the timed pass only (the cumulative
    # report also covers the JIT-compiling warm-up pass).
    lat = np.array([r.latency for r in steady])
    p50, p99 = np.percentile(lat, 50), np.percentile(lat, 99)
    tps = sum(r.request.batch for r in steady) / wall_batched
    tps_naive = sum(r.request.batch for r in steady_naive) / wall_naive

    n_shapes = len(set(lengths))
    _row("serving_stream", wall_batched / max(len(steady), 1) * 1e6,
         f"J_per_fft={rep.joules_per_transform:.3e};"
         f"p50_ms={p50*1e3:.2f};p99_ms={p99*1e3:.2f};"
         f"I_ef={rep.i_ef:.2f};batches={rep.n_batches};"
         f"sweeps={rep.cache.sweeps};cache_hits={rep.cache.hits};"
         f"resweep_free={rep.cache.sweeps == n_shapes}")
    _row("serving_vs_naive", wall_naive / max(len(steady_naive), 1) * 1e6,
         f"batched_tput={tps:.0f}tps;naive_tput={tps_naive:.0f}tps;"
         f"speedup={wall_naive/wall_batched:.2f}x;"
         f"naive_batches={nrep.n_batches}")


def _chaos_pool(seed):
    """Deterministic payload pool, one array per distinct request shape.

    Payloads are built once and resubmitted (the service never mutates
    them), so 10^5 requests cost 10^5 receipt objects, not 10^5 arrays.
    """
    rng = np.random.default_rng(seed)

    def cplx(shape):
        return jnp.asarray((rng.standard_normal(shape)
                            + 1j * rng.standard_normal(shape)
                            ).astype(np.complex64))

    def real(shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    return {
        "fft": {(n, b): cplx((b, n))
                for n in (256, 512, 1024) for b in (1, 2, 3, 4)},
        "r2c": {b: real((b, 512)) for b in (1, 2)},
        "fft2": cplx((2, 64, 64)),
        "fdas": real((1, 1024)),
        "pulsar": real((4, 256)),
    }


def _chaos_payload(i, pool):
    """Payload + submit kwargs for request ``i`` of the mixed stream.

    Pure function of (i, pool): the recovery harness re-resolves crashed
    requests' payloads from their journaled ``payload_ref`` (= i) with
    exactly this mapping.
    """
    if i % 997 == 111:
        return pool["pulsar"], {"kind": "pulsar", "dm_trials": 4,
                                "templates": 3, "n_harmonics": 4}
    if i % 211 == 23:
        return pool["fdas"], {"kind": "fdas", "templates": 3}
    if i % 53 == 17:
        return pool["fft2"], {"ndim": 2}
    if i % 7 == 3:
        return pool["r2c"][1 + i % 2], {"transform": "r2c"}
    return pool["fft"][((256, 512, 1024)[i % 3], 1 + i % 4)], {}


def _chaos_submit(svc, i, pool, **extra):
    """Submit request ``i`` of the deterministic mixed stream."""
    x, kwargs = _chaos_payload(i, pool)
    return svc.submit(x, **kwargs, **extra)


def _run_chaos(n_requests, seed, *, wave=512, deadline_s=7e-6):
    """One open-loop chaos run; returns (service, submitted, stats)."""
    import hashlib
    from repro.core.hardware import TPU_V5E
    from repro.power import FleetTelemetry
    from repro.runtime.faults import (FAIL_CLOCK_LOCK, FAIL_PLAN_BUILD,
                                      KILL_DEVICE, SENSOR_KINDS,
                                      STALL_WORKER, FaultPlan)
    from repro.serving import SLO, FFTService, SLOPolicy, rung_name

    pool = _chaos_pool(seed)
    # ~7 distinct shapes coalesce to ~7 batches per wave; double it so the
    # generated schedule covers every batch id the run can reach.
    n_batches = max(2 * 8 * (n_requests // wave + 1), 16)
    plan = FaultPlan.generate(seed, n_batches=n_batches,
                              stall_duration_s=0.02)
    policy = SLOPolicy(default=SLO(deadline_s=deadline_s))
    # The telemetry plane shares the fault plan: scheduled SENSOR_* events
    # corrupt the per-batch power samples so the watchdog (not just the
    # execution path) is exercised by the same deterministic schedule.
    telemetry = FleetTelemetry.for_serving(TPU_V5E, seed=seed,
                                           fault_plan=plan)
    svc = FFTService(TPU_V5E, keep_results=False, slo=policy,
                     fault_plan=plan, drain_deadline_s=300.0,
                     telemetry=telemetry)
    submitted = []
    t0 = time.perf_counter()
    for start in range(0, n_requests, wave):
        for i in range(start, min(start + wave, n_requests)):
            submitted.append(_chaos_submit(svc, i, pool))
        svc.drain()
    wall = time.perf_counter() - t0

    receipts = [svc.receipt(r) for r in submitted]
    missing = sum(1 for r in receipts if r is None)
    # The reproducibility digest covers request-visible *outcomes* only:
    # worker placement and measured latencies are wall-clock-dependent,
    # the (outcome, rung, reason) trajectory must not be.
    h = hashlib.blake2b(digest_size=16)
    for req, r in zip(submitted, receipts):
        h.update(f"{req.kind}:{r.outcome}:{r.rung}:{r.reason}".encode()
                 if r is not None else b"MISSING")
    rep = svc.report()

    served = [r for r in receipts if r is not None and r.status == "served"]
    shed = [r for r in receipts if r is not None and r.status == "shed"]
    lat = np.array([r.latency for r in served]) if served else np.zeros(1)
    by_rung = {}
    for r in served:
        g = by_rung.setdefault(rung_name(r.rung),
                               {"n": 0, "transforms": 0, "energy_j": 0.0})
        g["n"] += 1
        g["transforms"] += r.request.batch
        g["energy_j"] += r.energy_j
    for g in by_rung.values():
        g["j_per_transform"] = g["energy_j"] / max(g["transforms"], 1)

    stats = {
        "n_requests": n_requests,
        "n_workers": svc.dispatcher.queue.n_workers,
        "wave": wave,
        "seed": seed,
        "wall_s": wall,
        "requests_per_s": n_requests / wall,
        "missing_receipts": missing,
        "outcomes": {
            "served": sum(1 for r in served if r.retries == 0),
            "retried": sum(1 for r in served if r.retries > 0),
            "shed": len(shed),
        },
        "shed_by_reason": {
            reason: sum(1 for r in shed if r.reason == reason)
            for reason in sorted({r.reason for r in shed})
        },
        "shed_rate": len(shed) / max(n_requests, 1),
        "availability": rep.availability,
        "p50_latency_s": float(np.percentile(lat, 50)),
        "p99_latency_s": float(np.percentile(lat, 99)),
        "j_per_transform_by_rung": by_rung,
        "faults_fired": {k: plan.fired_count(k)
                         for k in (KILL_DEVICE, FAIL_CLOCK_LOCK,
                                   FAIL_PLAN_BUILD, STALL_WORKER)},
        "sensor_faults_fired": {k: plan.fired_count(k)
                                for k in SENSOR_KINDS},
        "faults_pending": plan.pending(),
        "measured_energy_j": rep.measured_energy_j,
        "modelled_energy_j": rep.energy_j,
        "telemetry": rep.telemetry,
        "breaker_opens": rep.breaker_opens,
        "redistributions": rep.redistributions,
        "steals": rep.steals,
        "degraded": rep.degraded,
        "admission": {"admitted": svc.admission.admitted,
                      "degraded": svc.admission.degraded,
                      "shed": svc.admission.shed},
        "digest": h.hexdigest(),
    }
    return svc, stats


def chaos():
    """Deterministic chaos/load harness — persists BENCH_chaos.json.

    Drives REPRO_CHAOS_REQUESTS (default 100000) mixed
    fft/fft2/fdas/pulsar requests through the SLO-governed service under
    a seed-generated fault schedule (>= 1 device kill, >= 1 clock-lock
    failure, >= 1 stalled worker), then re-runs a smaller stream twice to
    prove outcome bit-reproducibility.  Run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a
    simulated 8-device fleet.

    Self-checked acceptance (CI gates on a non-zero exit):
      * every submitted request terminates in exactly one receipt;
      * the fault plan was non-trivial AND every pinned kind fired;
      * availability >= 0.99 excluding admission sheds;
      * the same seed reproduces the same outcome digest.
    """
    from repro.runtime.faults import (FAIL_CLOCK_LOCK, KILL_DEVICE,
                                      STALL_WORKER)

    n_requests = int(os.environ.get("REPRO_CHAOS_REQUESTS", "100000"))
    seed = int(os.environ.get("REPRO_CHAOS_SEED", "0"))
    # The SLO deadline is in *modelled* boost-clock seconds (the admission
    # controller never reads the wall clock); ~7us of modelled TPU work per
    # wave puts the mixed stream right at the degrade/shed knee.
    deadline_s = float(os.environ.get("REPRO_CHAOS_DEADLINE_S", "7e-6"))
    svc, stats = _run_chaos(n_requests, seed, deadline_s=deadline_s)
    _row("chaos_stream", stats["wall_s"] / max(n_requests, 1) * 1e6,
         f"workers={stats['n_workers']};rps={stats['requests_per_s']:.0f};"
         f"served={stats['outcomes']['served']};"
         f"retried={stats['outcomes']['retried']};"
         f"shed={stats['outcomes']['shed']};"
         f"availability={stats['availability']:.4f}")
    _row("chaos_faults", 0.0,
         f"fired={stats['faults_fired']};breaker_opens="
         f"{stats['breaker_opens']};redistributions="
         f"{stats['redistributions']}")

    # Bit-reproducibility: two fresh services, same seed, same (smaller)
    # stream — identical outcome digests.
    n_sub = min(n_requests, int(os.environ.get(
        "REPRO_CHAOS_REPRO_REQUESTS", "2000")))
    _, sub_a = _run_chaos(n_sub, seed, deadline_s=deadline_s)
    _, sub_b = _run_chaos(n_sub, seed, deadline_s=deadline_s)
    reproducible = sub_a["digest"] == sub_b["digest"]
    _row("chaos_repro", 0.0,
         f"n={n_sub};digest_a={sub_a['digest'][:16]};"
         f"digest_b={sub_b['digest'][:16]};match={reproducible}")

    fired = stats["faults_fired"]
    criteria = {
        # Acceptance: every request terminates in exactly one receipt.
        "missing_receipts": stats["missing_receipts"],
        "every_request_receipted": stats["missing_receipts"] == 0,
        # Acceptance: the schedule was non-trivial and actually fired.
        "nontrivial_fault_plan": (fired[KILL_DEVICE] >= 1
                                  and fired[FAIL_CLOCK_LOCK] >= 1
                                  and fired[STALL_WORKER] >= 1),
        # Acceptance: availability (excluding admission sheds) >= 99%.
        "availability": stats["availability"],
        "availability_ok": stats["availability"] >= 0.99,
        # Acceptance: same seed => same outcome trajectory.
        "reproducible": reproducible,
    }
    out = {
        "backend": jax.default_backend(),
        "criteria": criteria,
        "run": stats,
        "repro_runs": [sub_a, sub_b],
    }
    from repro.core.hardware import TPU_V5E
    path = _persist("chaos", out, device=TPU_V5E.name)
    _row("chaos_bench_json", 0.0,
         f"written={path};"
         f"availability={stats['availability']:.4f};"
         f"reproducible={reproducible}")
    if not (criteria["every_request_receipted"]
            and criteria["nontrivial_fault_plan"]
            and criteria["availability_ok"] and reproducible):
        raise SystemExit(f"chaos self-check failed: {criteria}")


def _run_recovery(n_requests, seed, *, crashes=2, process="poisson",
                  rate_hz=1e5, period_s=4e-2, deadline_s=6e-5,
                  journal_dir=None, snapshot_every=1,
                  segment_records=100_000):
    """One crash-and-recover run over a seeded arrival process.

    Drives the mixed chaos stream through a journal-attached service in
    Poisson/Gamma arrival waves (the service drains once per
    ``period_s`` of simulated arrival time, so wave sizes genuinely
    vary), simulating ``crashes`` process kills at evenly spaced
    admission ordinals via the fault plan's arrival seam.  Each crash
    abandons the service mid-wave (journal tail un-fsynced, in-memory
    state gone) and recovers from the journal: replayed receipts are
    verified bit-identical against the outcomes already collected,
    in-flight admits are re-enqueued, and the wave resumes.  Returns
    (stats, journal_audit) with a submission-order outcome digest that
    must not depend on the crash schedule.
    """
    import collections
    import hashlib
    import shutil
    import tempfile

    from repro.core.energy import guarded_ratio
    from repro.core.hardware import TPU_V5E
    from repro.data.arrivals import arrival_times, wave_slices
    from repro.power import FleetTelemetry
    from repro.runtime.faults import (CRASH_PROCESS, FAIL_CLOCK_LOCK,
                                      FAIL_PLAN_BUILD, KILL_DEVICE,
                                      KILL_HOST, SENSOR_KINDS, STALL_WORKER,
                                      FaultPlan, HostTopology)
    from repro.runtime.journal import RequestJournal, read_journal
    from repro.serving import SLO, FFTService, SLOPolicy
    from repro.serving.recovery import ReplayResult

    pool = _chaos_pool(seed)
    times = arrival_times(n_requests, seed=seed + 1, process=process,
                          rate_hz=rate_hz)
    waves = list(wave_slices(times, period_s))
    n_workers = len(jax.devices())
    # Host fault domains: group the fleet into ~4 simulated hosts.
    topology = HostTopology(n_workers,
                            devices_per_host=max(1, n_workers // 4))
    n_batches = max(16 * (len(waves) + 1), 64)
    crash_arrivals = tuple(sorted(
        {n_requests * (k + 1) // (crashes + 1)
         for k in range(crashes)})) if crashes else ()
    # Two host kills pinned to batch ids the run will certainly reach
    # (the 7-shape stream coalesces to >= ~6 batches per wave).
    est_batches = max(6 * len(waves), 12)
    host_kill_batches = (max(est_batches // 3, 2),
                         max(2 * est_batches // 3, 5))

    def make_plan():
        # Identical seeded draws regardless of crash_arrivals (harness-
        # only events append after the rng), so the crashed and uncrashed
        # runs see the same serving faults.
        return FaultPlan.generate(seed, n_batches=n_batches,
                                  stall_duration_s=0.02,
                                  crash_arrivals=crash_arrivals,
                                  host_kill_batches=host_kill_batches)

    def build(plan, *, recover_from=None):
        kwargs = dict(
            device_spec=TPU_V5E, keep_results=False,
            slo=SLOPolicy(default=SLO(deadline_s=deadline_s)),
            fault_plan=plan, drain_deadline_s=300.0,
            telemetry=FleetTelemetry.for_serving(TPU_V5E, seed=seed,
                                                 fault_plan=plan),
            max_retained_receipts=16384, topology=topology)
        if recover_from is not None:
            return FFTService.recover(
                recover_from,
                payload_fn=lambda ref, meta: _chaos_payload(ref, pool)[0],
                journal_kwargs={"segment_records": segment_records},
                **kwargs)
        journal = RequestJournal(journal_dir,
                                 segment_records=segment_records)
        return FFTService(journal=journal, **kwargs)

    owns_dir = journal_dir is None
    if owns_dir:
        journal_dir = tempfile.mkdtemp(prefix="repro-journal-")

    outcomes = {}
    counters = collections.Counter()
    fired = collections.Counter()
    fault_kinds = (KILL_DEVICE, KILL_HOST, FAIL_CLOCK_LOCK,
                   FAIL_PLAN_BUILD, STALL_WORKER, *SENSOR_KINDS)

    def collect(receipts):
        for r in receipts:
            ref = r.request.payload_ref
            if ref is None:
                continue
            t = (r.request.kind, r.outcome, r.rung, r.reason)
            prev = outcomes.get(ref)
            if prev is None:
                outcomes[ref] = t
                if r.recovered:
                    counters["recovered_only"] += 1
            elif r.recovered:
                # A replayed receipt for an outcome the harness already
                # saw live: the exactly-once contract says it must be
                # bit-identical (status/reason/rung).
                if prev == t:
                    counters["replays_verified"] += 1
                else:
                    counters["replay_mismatches"] += 1
            else:
                counters["reexecuted_duplicates"] += 1

    def absorb(svc, plan):
        for k in fault_kinds:
            fired[k] += plan.fired_count(k)
        counters["host_kills"] += svc.host_kills
        if svc.admission is not None:
            counters["admitted"] += svc.admission.admitted
            counters["degraded"] += svc.admission.degraded
            counters["adm_shed"] += svc.admission.shed

    plan = make_plan()
    svc = build(plan)
    crashes_done = 0
    t0 = time.perf_counter()
    for w, (start, stop) in enumerate(waves):
        for i in range(start, stop):
            if plan.take(CRASH_PROCESS, arrival=i) is not None:
                # Simulated kill -9 mid-wave: the journal tail is
                # abandoned without a durability barrier and every byte
                # of in-memory service state dies with the process.
                absorb(svc, plan)
                svc.journal.crash()
                crashes_done += 1
                plan = make_plan()
                svc = build(plan, recover_from=journal_dir)
                plan.drop_consumed(batch_before=svc._next_batch_id,
                                   arrival_before=i + 1)
                collect(svc.recovered_receipts)
                svc.recovered_receipts.clear()   # verified; free them
            _chaos_submit(svc, i, pool, payload_ref=i)
        collect(svc.drain())
        if snapshot_every and (w + 1) % snapshot_every == 0:
            svc.snapshot()
    collect(svc.drain())
    wall = time.perf_counter() - t0
    absorb(svc, plan)
    incarnation = svc.journal.incarnation
    svc.journal.close()

    # End-of-run audit straight off the durable log: every admit must
    # have exactly one terminal record, no more, no less.  Streamed
    # (retain=0 keeps counts, not payloads) so auditing a 10^6-request
    # journal costs seq-set memory, not record memory.
    audit = ReplayResult(retain=0)
    _, jstats = read_journal(journal_dir, sink=audit.feed)

    h = hashlib.blake2b(digest_size=16)
    for i in range(n_requests):
        t = outcomes.get(i)
        h.update(f"{t[0]}:{t[1]}:{t[2]}:{t[3]}".encode()
                 if t is not None else b"MISSING")
    served = sum(1 for t in outcomes.values()
                 if t[1] in ("served", "retried"))
    fault_shed = sum(1 for t in outcomes.values() if t[1] == "shed"
                     and str(t[3] or "").startswith("fault:"))
    stats = {
        "n_requests": n_requests,
        "n_workers": n_workers,
        "hosts": topology.n_hosts,
        "seed": seed,
        "process": process,
        "rate_hz": rate_hz,
        "period_s": period_s,
        "waves": len(waves),
        "mean_wave": n_requests / max(len(waves), 1),
        "wall_s": wall,
        "requests_per_s": n_requests / wall,
        "crashes": crashes_done,
        "crash_arrivals": list(crash_arrivals),
        "incarnation": incarnation,
        "lost_receipts": n_requests - len(outcomes),
        "duplicate_receipts": (counters["reexecuted_duplicates"]
                               + audit.duplicate_terminals),
        "replays_verified": counters["replays_verified"],
        "replay_mismatches": counters["replay_mismatches"],
        "recovered_only": counters["recovered_only"],
        "outcomes": {
            "served": sum(1 for t in outcomes.values()
                          if t[1] == "served"),
            "retried": sum(1 for t in outcomes.values()
                           if t[1] == "retried"),
            "shed": sum(1 for t in outcomes.values() if t[1] == "shed"),
        },
        "availability": guarded_ratio(served, served + fault_shed,
                                      on_zero=1.0),
        "admission": {"admitted": counters["admitted"],
                      "degraded": counters["degraded"],
                      "shed": counters["adm_shed"]},
        "faults_fired": {k: fired[k] for k in fault_kinds},
        "host_kills": counters["host_kills"],
        "journal": {
            "segments": jstats.segments,
            "records": jstats.records,
            "invalid": jstats.invalid,
            "admits": audit.admits_total,
            "terminals": audit.terminals_total,
            "open_admits": len(audit.open_admits),
            "duplicate_terminals": audit.duplicate_terminals,
            "incarnations": audit.incarnations,
            "availability": audit.availability,
            "duplicate_rate": audit.duplicate_rate,
        },
        "digest": h.hexdigest(),
    }
    if owns_dir:
        shutil.rmtree(journal_dir, ignore_errors=True)
    return stats


def recovery():
    """Crash-and-recover gate — persists BENCH_recovery.json.

    Drives REPRO_RECOVERY_REQUESTS (default 10^6) mixed requests through
    the journal-attached service in seeded Poisson arrival waves with
    REPRO_CHAOS_CRASHES (default 2, >= 2 enforced) simulated process
    kills mid-run, recovering from the write-ahead journal each time;
    then repeats a smaller Gamma-arrival pair for the bursty process.
    Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for
    a simulated 8-device / 4-host fleet.

    Self-checked acceptance (CI gates on a non-zero exit):
      * zero lost receipts and zero duplicated receipts — the journal
        audit proves exactly one terminal record per admit;
      * every replayed receipt bit-identical (status/reason/rung) to the
        live receipt the previous incarnation issued;
      * availability >= 0.99 excluding admission sheds;
      * the outcome digest is identical crashed-and-recovered vs
        uncrashed at the same seed (for Poisson AND Gamma arrivals);
      * >= 2 crashes and >= 1 host kill actually happened.
    """
    from repro.core.hardware import TPU_V5E
    from repro.runtime.faults import KILL_HOST

    n_requests = int(os.environ.get("REPRO_RECOVERY_REQUESTS", "1000000"))
    crashes = max(int(os.environ.get("REPRO_CHAOS_CRASHES", "2")), 2)
    seed = int(os.environ.get("REPRO_RECOVERY_SEED", "0"))
    deadline_s = float(os.environ.get("REPRO_RECOVERY_DEADLINE_S", "6e-5"))

    crashed = _run_recovery(n_requests, seed, crashes=crashes,
                            deadline_s=deadline_s)
    _row("recovery_stream",
         crashed["wall_s"] / max(n_requests, 1) * 1e6,
         f"rps={crashed['requests_per_s']:.0f};"
         f"crashes={crashed['crashes']};"
         f"lost={crashed['lost_receipts']};"
         f"dup={crashed['duplicate_receipts']};"
         f"replays_verified={crashed['replays_verified']};"
         f"availability={crashed['availability']:.4f}")
    uncrashed = _run_recovery(n_requests, seed, crashes=0,
                              deadline_s=deadline_s)
    digests_match = crashed["digest"] == uncrashed["digest"]
    _row("recovery_digest", 0.0,
         f"crashed={crashed['digest'][:16]};"
         f"uncrashed={uncrashed['digest'][:16]};match={digests_match}")

    # The bursty arrival process, smaller but with the same contract.
    n_gamma = min(n_requests,
                  int(os.environ.get("REPRO_RECOVERY_GAMMA_REQUESTS",
                                     "20000")))
    g_crashed = _run_recovery(n_gamma, seed, crashes=crashes,
                              process="gamma", deadline_s=deadline_s)
    g_uncrashed = _run_recovery(n_gamma, seed, crashes=0,
                                process="gamma", deadline_s=deadline_s)
    gamma_match = g_crashed["digest"] == g_uncrashed["digest"]
    _row("recovery_gamma", 0.0,
         f"n={n_gamma};crashes={g_crashed['crashes']};"
         f"lost={g_crashed['lost_receipts']};"
         f"dup={g_crashed['duplicate_receipts']};match={gamma_match}")

    criteria = {
        "crashes_injected": crashed["crashes"],
        "crashes_ok": crashed["crashes"] >= 2,
        "zero_lost": (crashed["lost_receipts"] == 0
                      and g_crashed["lost_receipts"] == 0),
        "zero_duplicated": (crashed["duplicate_receipts"] == 0
                            and g_crashed["duplicate_receipts"] == 0),
        "journal_exactly_once": (
            crashed["journal"]["admits"] == n_requests
            and crashed["journal"]["terminals"] == n_requests
            and crashed["journal"]["open_admits"] == 0),
        "replays_bit_identical": (crashed["replay_mismatches"] == 0
                                  and g_crashed["replay_mismatches"] == 0),
        "availability": crashed["availability"],
        "availability_ok": crashed["availability"] >= 0.99,
        "digest_crash_invariant": digests_match and gamma_match,
        "host_kill_fired": crashed["faults_fired"][KILL_HOST] >= 1,
    }
    out = {
        "criteria": criteria,
        "crashed": crashed,
        "uncrashed": uncrashed,
        "gamma": {"crashed": g_crashed, "uncrashed": g_uncrashed},
    }
    path = _persist("recovery", out, device=TPU_V5E.name,
                    incarnation=crashed["incarnation"])
    _row("recovery_bench_json", 0.0,
         f"written={path};zero_lost={criteria['zero_lost']};"
         f"zero_dup={criteria['zero_duplicated']};"
         f"digest_invariant={criteria['digest_crash_invariant']}")
    if not (criteria["crashes_ok"] and criteria["zero_lost"]
            and criteria["zero_duplicated"]
            and criteria["journal_exactly_once"]
            and criteria["replays_bit_identical"]
            and criteria["availability_ok"]
            and criteria["digest_crash_invariant"]
            and criteria["host_kill_fired"]):
        raise SystemExit(f"recovery self-check failed: {criteria}")


def _power_site(seed, *, fault_plan=None, site_cap_w=1400.0,
                hard_cap_w=1500.0, n_devices=8):
    """A governed 8-device TPU_V5E site with PR 5 sweep-optimum fallbacks."""
    from repro.core import FFTCase, fft_workload
    from repro.core.dvfs import sweep
    from repro.core.hardware import TPU_V5E
    from repro.power import SiteBudgetScheduler, SitePipeline

    fallback = sweep(fft_workload(FFTCase(n=4096), TPU_V5E),
                     TPU_V5E).optimal.f
    pipes = [SitePipeline(name=f"pipe{i}", device_index=i,
                          priority=(i % 4) + 1, fallback_mhz=fallback,
                          u_core=0.9, u_mem=0.8)
             for i in range(n_devices)]
    return SiteBudgetScheduler(TPU_V5E, pipes, site_cap_w=site_cap_w,
                               hard_cap_w=hard_cap_w, seed=seed,
                               fault_plan=fault_plan)


def power():
    """Closed-loop power governance harness — persists BENCH_power.json.

    Exercises the repro.power subsystem end to end on the simulated
    8-device fleet:

      converge     the governed site from a cold start: per-pipeline PI
                   governors steer measured power onto the
                   priority-weighted budget split
      faults       one run per sensor-fault kind (dropout / spike /
                   stale) injected as a 4-tick storm on device 0: the
                   watchdog must go unhealthy and the governor must pin
                   the static sweep-optimum fallback clock exactly
      emergency    the site cap drops mid-run below current draw: the
                   emergency rung floors clocks, sheds the
                   lowest-priority pipeline and restores headroom
      serving      a telemetered FFTService stream: receipts carry
                   measured_energy_j next to the modelled energy_j

    Self-checked acceptance (CI gates on a non-zero exit):
      * the governed fleet's true site power NEVER exceeds the cap;
      * the controller converges within REPRO_POWER_MAX_TICKS ticks;
      * under EACH injected sensor-fault kind the governor engages the
        bit-exact static-sweep fallback;
      * two fresh runs produce the identical site digest.
    """
    from repro.core.hardware import TPU_V5E
    from repro.power import FleetTelemetry
    from repro.runtime.faults import SENSOR_KINDS, FaultEvent, FaultPlan

    seed = int(os.environ.get("REPRO_POWER_SEED", "0"))
    n_ticks = int(os.environ.get("REPRO_POWER_TICKS", "80"))
    max_ticks = int(os.environ.get("REPRO_POWER_MAX_TICKS", "40"))
    dt = 0.1

    # --- phase A: cold-start convergence under the site cap ---------------
    site = _power_site(seed)
    ticks = site.run(n_ticks, dt=dt)
    peak_w = max(t.truth_w for t in ticks)
    converged_tick = site.first_converged_tick
    digest_a = site.digest()
    site_b = _power_site(seed)
    site_b.run(n_ticks, dt=dt)
    reproducible = digest_a == site_b.digest()
    _row("power_converge", 0.0,
         f"ticks={n_ticks};converged_tick={converged_tick};"
         f"peak_w={peak_w:.1f};cap_w={site.site_cap_w:.0f};"
         f"digest={digest_a[:16]};reproducible={reproducible}")

    # --- phase B: static-sweep fallback under each sensor-fault kind ------
    fallback_runs = {}
    for kind in SENSOR_KINDS:
        storm = FaultPlan(events=[FaultEvent(kind, batch_id=k, worker=0)
                                  for k in range(10, 14)])
        fsite = _power_site(seed, fault_plan=storm)
        fticks = fsite.run(30, dt=dt)
        gov = fsite.governors["pipe0"]
        fb_ticks = [k for k, t in enumerate(fticks)
                    if t.modes[0] == "fallback"]
        exact = all(fticks[k].clocks_mhz[0] == gov.fallback_mhz
                    for k in fb_ticks)
        fallback_runs[kind] = {
            "fired": storm.fired_count(kind),
            "fallback_engagements": gov.fallback_engagements,
            "fallback_ticks": fb_ticks,
            "fallback_clock_exact": exact,
            "fallback_mhz": gov.fallback_mhz,
            "engaged": gov.fallback_engagements >= 1 and bool(fb_ticks),
            "recovered": fticks[-1].health[0] == "healthy",
        }
        _row(f"power_fault_{kind.replace('sensor-', '')}", 0.0,
             f"fired={storm.fired_count(kind)};"
             f"fallback_ticks={len(fb_ticks)};exact={exact};"
             f"recovered={fallback_runs[kind]['recovered']}")

    # --- phase C: emergency rung on a mid-run hard-cap breach -------------
    esite = _power_site(seed)
    esite.run(20, dt=dt)
    pre_active = len(esite.active)
    esite.site_cap_w, esite.hard_cap_w = 850.0, 900.0
    eticks = esite.run(20, dt=dt)[20:]
    emergency_fired = esite.emergencies >= 1
    shed_count = pre_active - len(esite.active)
    cap_restored = eticks[-1].truth_w <= esite.hard_cap_w
    _row("power_emergency", 0.0,
         f"emergencies={esite.emergencies};shed={shed_count};"
         f"final_w={eticks[-1].truth_w:.1f};hard_cap_w="
         f"{esite.hard_cap_w:.0f};restored={cap_restored}")

    # --- serving integration: measured J on receipts (informational) -----
    from repro.serving import FFTService
    rng = np.random.default_rng(seed)
    tel = FleetTelemetry.for_serving(TPU_V5E, seed=seed)
    svc = FFTService(TPU_V5E, keep_results=False, telemetry=tel)
    for i in range(32):
        n = (256, 512, 1024)[i % 3]
        svc.submit((rng.standard_normal((2, n))
                    + 1j * rng.standard_normal((2, n))
                    ).astype(np.complex64))
    svc.drain()
    rep = svc.report()
    _row("power_serving", 0.0,
         f"measured_j={rep.measured_energy_j:.3e};"
         f"modelled_j={rep.energy_j:.3e};"
         f"reads={rep.telemetry['reads']}")

    criteria = {
        # Acceptance: the governed fleet never exceeds the site cap.
        "peak_site_w": peak_w,
        "site_cap_w": site.site_cap_w,
        "cap_never_exceeded": peak_w <= site.site_cap_w,
        # Acceptance: bounded-time convergence from a cold start.
        "converged_tick": converged_tick,
        "converged_in_bound": (converged_tick is not None
                               and converged_tick <= max_ticks),
        # Acceptance: the bit-exact static fallback engages under every
        # injected sensor-fault kind.
        "fallback_under_each_kind": all(
            r["engaged"] and r["fallback_clock_exact"]
            for r in fallback_runs.values()),
        # Acceptance: the emergency rung both fires and works.
        "emergency_engaged": emergency_fired,
        "emergency_shed": shed_count,
        "emergency_cap_restored": cap_restored,
        # Acceptance: same seed => identical site digest, fresh runs.
        "reproducible": reproducible,
    }
    out = {
        "criteria": criteria,
        "converge": {
            "n_ticks": n_ticks,
            "dt_s": dt,
            "n_devices": 8,
            "converged_tick": converged_tick,
            "peak_site_w": peak_w,
            "final_site_w": ticks[-1].truth_w,
            "targets_w": dict(site.targets),
            "final_clocks_mhz": list(ticks[-1].clocks_mhz),
            "digest": digest_a,
            "telemetry": site.telemetry.summary(),
        },
        "sensor_faults": fallback_runs,
        "emergency": {
            "emergencies": esite.emergencies,
            "shed": shed_count,
            "active_after": list(t for t in eticks[-1].active),
            "final_site_w": eticks[-1].truth_w,
            "hard_cap_w": esite.hard_cap_w,
        },
        "serving": {
            "measured_energy_j": rep.measured_energy_j,
            "modelled_energy_j": rep.energy_j,
            "n_requests": rep.n_requests,
        },
    }
    path = _persist("power", out, device=TPU_V5E.name)
    _row("power_bench_json", 0.0,
         f"written={path};cap_ok={criteria['cap_never_exceeded']};"
         f"converged_tick={converged_tick};"
         f"fallback_ok={criteria['fallback_under_each_kind']};"
         f"reproducible={reproducible}")
    if not (criteria["cap_never_exceeded"]
            and criteria["converged_in_bound"]
            and criteria["fallback_under_each_kind"]
            and criteria["emergency_engaged"] and cap_restored
            and reproducible):
        raise SystemExit(f"power self-check failed: {criteria}")


def obs():
    """Observability plane — persists BENCH_obs.json.

    Gates: (1) tracing overhead — a tracer-instrumented warm service
    drain within 5% wall time of an uninstrumented one (min-of-repeats;
    the ledger/metrics/drift plane is always on in both, so the delta
    prices exactly the opt-in span machinery); (2) ledger-audited pass
    claims — an eager pow2 2-D plan records exactly 2 fused launches and
    the fused FDAS convolution records 1 forward + one batched inverse
    launch covering all T template planes; (3) reproducibility — two
    fresh fake-timer serving runs produce identical blake2b span digests
    and identical ledger digests; (4) model-drift detection — the drift
    detector alerts under a deliberately miscalibrated sensor truth
    model and stays silent under the calibrated one.
    """
    import dataclasses as _dc
    import itertools

    from repro.core.hardware import TPU_V5E
    from repro.core.power_model import PowerModel
    from repro.fft.convolve import conv_plan, overlap_save_conv
    from repro.fft.plan_nd import plan_nd
    from repro.obs import LaunchLedger, Tracer, launches_digest
    from repro.obs import trace as trace_mod
    from repro.power.telemetry import FleetTelemetry
    from repro.serving import FFTService
    from repro.serving import request as request_mod

    class _FakeTimer:
        """Deterministic clock: advances dt per call."""

        def __init__(self, dt=1e-4):
            self.t, self.dt = 0.0, dt

        def __call__(self):
            self.t += self.dt
            return self.t

    key = jax.random.PRNGKey(0)
    payloads = []
    for i in range(6):
        kr, ki, key = jax.random.split(key, 3)
        payloads.append((jax.random.normal(kr, (16, 2048))
                         + 1j * jax.random.normal(ki, (16, 2048))
                         ).astype(jnp.complex64))

    # --- 1. tracing overhead on a warm drain -------------------------
    def build(instrumented):
        return FFTService(TPU_V5E, devices=[None, None],
                          keep_results=False,
                          tracer=Tracer() if instrumented else None)

    def drive(svc):
        for p in payloads:
            svc.submit(p)
        return svc.drain()

    # Interleaved best-of-n: alternating the two services inside one
    # repeat loop exposes both to the same machine-state drift, so the
    # min-of-n delta prices the tracer, not the scheduler.
    plain, traced = build(False), build(True)
    for svc in (plain, traced):
        for _ in range(2):
            drive(svc)                                   # warm jit caches
    plain_s, traced_s = [], []
    for _ in range(9):
        t0 = time.perf_counter()
        drive(plain)
        plain_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        drive(traced)
        traced_s.append(time.perf_counter() - t0)
    plain_us, traced_us = 1e6 * min(plain_s), 1e6 * min(traced_s)
    overhead = traced_us / plain_us - 1.0
    overhead_ok = overhead < 0.05
    _row("obs_overhead", plain_us,
         f"traced_us={traced_us:.1f};overhead={100*overhead:+.2f}%;"
         f"ok={overhead_ok}")

    # --- 2. ledger-audited pass claims --------------------------------
    plan2 = plan_nd((64, 64))
    led = LaunchLedger()
    with led.capture():
        jax.block_until_ready(plan2.fn(payloads[0].reshape(-1, 64, 64)))
    fft2_counts = led.counts()
    fft2_ok = (fft2_counts.get("fft-c2c-t", 0) == plan2.passes == 2
               and len(fft2_counts) == 1)

    n, taps, t, nfft = 1000, 17, 3, 256
    cplan = conv_plan(n, taps, t, nfft)
    led = LaunchLedger()
    with led.capture():
        jax.block_until_ready(overlap_save_conv(
            payloads[1].reshape(-1)[:n], np.ones((t, taps), np.float32),
            nfft=nfft))
    fdas_counts = led.counts()
    inv = [r for r in led.records if r.kernel == "fft-c2c"]
    inv_planes = (inv[0].shape[0] // cplan.n_segments) if inv else 0
    fdas_ok = (fdas_counts.get("fft-c2c-mul", 0) == cplan.forward_passes
               and fdas_counts.get("fft-c2c", 0) == 1
               and inv_planes == cplan.inverse_passes == t)
    _row("obs_ledger_audit", 0.0,
         f"fft2={'+'.join(f'{k}:{v}' for k, v in fft2_counts.items())};"
         f"fdas_fwd={fdas_counts.get('fft-c2c-mul', 0)};"
         f"fdas_inv_planes={inv_planes};ok={fft2_ok and fdas_ok}")

    # --- 3/4. reproducible traces + drift detection -------------------
    def traced_run(power_model=None):
        # Each run stands for a fresh process, in which request ids (a
        # submit span's request_id) restart from zero.
        request_mod._REQUEST_IDS = itertools.count()
        timer = _FakeTimer()
        tracer = Tracer(timer=timer)
        svc = FFTService(
            TPU_V5E, devices=[None, None], timer=timer, tracer=tracer,
            keep_results=False,
            telemetry=FleetTelemetry.for_serving(
                TPU_V5E, seed=11, noise_frac=0.0,
                power_model=power_model))
        for p in payloads[:4]:
            # one drain per submit: every batch is metered, so the drift
            # detector clears its min_samples gate on one key
            svc.submit(p)
            svc.drain()
        return svc, tracer

    svc1, tr1 = traced_run()
    svc2, tr2 = traced_run()
    d1, d2 = trace_mod.digest(tr1.spans), trace_mod.digest(tr2.spans)
    # Receipt-level launch digests: the second run serves warm jit
    # executables (its own ledger records nothing live), so compare what
    # the receipts carry, replayed from the process-wide signature store.
    ld1 = launches_digest(r.launches for r in svc1.receipts)
    ld2 = launches_digest(r.launches for r in svc2.receipts)
    reproducible = d1 == d2 and ld1 == ld2
    launches_backed = all(
        r.launches and all(l.bytes_moved > 0 for l in r.launches)
        for svc in (svc1, svc2) for r in svc.receipts)
    _row("obs_trace_digest", 0.0,
         f"span_digest={d1};ledger_digest={ld1};match={reproducible}")

    hot = PowerModel(_dc.replace(TPU_V5E, name="hot-v5e",
                                 tdp=2.0 * TPU_V5E.tdp))
    svc_hot, _ = traced_run(power_model=hot)
    drift_ok = (svc1.drift.drift_alerts == 0
                and svc_hot.drift.drift_alerts >= 1)
    _row("obs_drift", 0.0,
         f"calibrated_alerts={svc1.drift.drift_alerts};"
         f"miscalibrated_alerts={svc_hot.drift.drift_alerts};"
         f"worst_err={svc_hot.drift.summary()['worst_ewma_error']:+.3f};"
         f"ok={drift_ok}")

    criteria = {
        # Acceptance: < 5% wall-time overhead for full tracing.
        "tracing_overhead_frac": overhead,
        "tracing_overhead_lt_5pct": overhead_ok,
        # Acceptance: ledger-audited pass counts match PR 3/4 claims.
        "fft2_ledger_counts": fft2_counts,
        "fft2_ledger_ok": fft2_ok,
        "fdas_ledger_counts": fdas_counts,
        "fdas_inverse_planes_ledger": inv_planes,
        "fdas_ledger_ok": fdas_ok,
        # Acceptance: identical digests across two fresh runs.
        "span_digest_run1": d1,
        "span_digest_run2": d2,
        "ledger_digest_run1": ld1,
        "ledger_digest_run2": ld2,
        "digests_reproducible": reproducible,
        "receipts_ledger_backed": launches_backed,
        # Acceptance: drift alerts iff the model is miscalibrated.
        "calibrated_drift_alerts": svc1.drift.drift_alerts,
        "miscalibrated_drift_alerts": svc_hot.drift.drift_alerts,
        "drift_detection_ok": drift_ok,
    }
    out = {
        "criteria": criteria,
        "overhead": {"plain_us": plain_us, "traced_us": traced_us,
                     "requests_per_drain": len(payloads)},
        "drift_miscalibrated": svc_hot.drift.summary(),
        "metrics_series": sorted(
            line.split("{")[0].split(" ")[0]
            for line in svc1.metrics_text().splitlines()
            if line and not line.startswith("#")),
    }
    path = _persist("obs", out, device=TPU_V5E.name)
    _row("obs_bench_json", 0.0,
         f"written={path};overhead_ok={overhead_ok};"
         f"ledger_ok={fft2_ok and fdas_ok};reproducible={reproducible};"
         f"drift_ok={drift_ok}")
    if not (overhead_ok and fft2_ok and fdas_ok and reproducible
            and launches_backed and drift_ok):
        raise SystemExit(f"obs self-check failed: {criteria}")


BENCHES = [fig4_exec_time, fig6_time_vs_freq, fig7_energy_u_shape,
           fig8_power_vs_freq, fig9_optimal_freq, table3_mean_optimal,
           fig10_gflops_per_watt, fig11_exec_increase, fig13_16_ief,
           table4_pipeline, kernels, fft, fft2, fdas, tune, pipeline,
           roofline, dvfs_cells, fft_pencil_roofline, conclusions_cost_co2,
           serving, chaos, recovery, power, obs]


def main(argv: list[str] | None = None) -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = sys.argv[1:] if argv is None else argv
    by_name = {b.__name__: b for b in BENCHES}
    if args:
        unknown = [a for a in args if a not in by_name]
        if unknown:
            raise SystemExit(
                f"unknown target(s) {unknown}; have {sorted(by_name)}")
        selected = [by_name[a] for a in args]
    else:
        selected = BENCHES
    print("name,us_per_call,derived")
    for b in selected:
        b()


if __name__ == "__main__":
    main()
