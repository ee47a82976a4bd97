"""The paper's Sec. 5.3 demonstration, end to end — plus the FDAS stage.

Runs the pulsar-search pipeline (R2C FFT -> power spectrum -> stats ->
harmonic sum -> S/N) on synthetic data with an injected pulsar through
``repro.fft.pipeline.pulsar_pipeline(real_input=True)`` — telescope
voltages are real, so the FFT stage does half the work and every routed
pass lands on the fused Pallas kernels (interpret mode on CPU).  Then the
Fourier-Domain Acceleration Search (``repro.search``) recovers an
injected *accelerated* pulsar from the same voltages, and the per-stage
DVFS clock plan reports the composite energy saving (Table 4).

Run:  PYTHONPATH=src python examples/pulsar_pipeline.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.dvfs import sweep
from repro.core.hardware import TESLA_V100
from repro.core.scheduler import DVFSScheduler
from repro.fft.pipeline import (PipelineShape, fft_time_share,
                                pulsar_pipeline, stage_profiles)
from repro.search import TemplateBank, fdas_search


def main():
    enable_compile_cache()
    # --- run the pipeline on real voltages with an injected pulsar -------
    n, batch = 4096, 4
    t = jnp.arange(n, dtype=jnp.float32)
    f0 = 96 / n
    key = jax.random.PRNGKey(0)
    noise = jax.random.normal(key, (batch, n))
    pulse = (jnp.sin(2 * jnp.pi * f0 * t) > 0.97).astype(jnp.float32)
    x = noise + 3.0 * pulse[None, :]

    # R2C route: half the FFT work, n/2+1 bins downstream (Sec. 5.3).
    snr = pulsar_pipeline(x, n_harmonics=16, real_input=True)
    nbins = snr.shape[-1]
    best = np.asarray(snr[:, :, 1:nbins - 1].max(axis=(1, 2)))
    peak_bin = int(np.asarray(snr[0].max(axis=0)[1:nbins - 1]).argmax()) + 1
    print(f"pulsar injected at bin 96 -> strongest S/N at bin {peak_bin}; "
          f"per-series peak S/N: {np.round(best, 1)}")

    # --- FDAS: recover an injected *accelerated* pulsar ------------------
    s = np.arange(n) / n
    k0, z = 700, 4.0                       # start bin, drift in bins
    accel = (0.4 * np.cos(2 * np.pi * (k0 * s + 0.5 * z * s * s))
             ).astype(np.float32)
    xa = np.asarray(noise) + accel[None, :]
    bank = TemplateBank.linear(zmax=8, n_templates=9)
    res = fdas_search(jnp.asarray(xa), bank, threshold=8.0,
                      max_candidates=4)
    print(f"\nFDAS: injected drift z={z:+.0f} bins at bin {k0}; "
          f"bank drifts {bank.drifts}")
    c = res.candidates
    for b in range(batch):
        rows = [
            f"(z={bank.drifts[int(ti)]:+.0f}, bin={int(bi)}, "
            f"P={float(p):.0f})"
            for ti, bi, p in zip(np.asarray(c.template[b]),
                                 np.asarray(c.bin[b]),
                                 np.asarray(c.power[b])) if ti >= 0
        ]
        print(f"  series {b}: " + (", ".join(rows) if rows
                                   else "no candidates above threshold"))

    # --- the paper's energy play: lock the FFT stage's clock -------------
    dev = TESLA_V100
    shape = PipelineShape(batch=32, n=2**20, n_harmonics=16, real_input=True)
    profs = stage_profiles(shape, dev)
    share = fft_time_share(shape, dev)
    sched = DVFSScheduler(dev)
    fft_opt = sweep(profs[0], dev).optimal.f
    stages = sched.plan(profs, locked={profs[0].name: fft_opt})
    rep = sched.evaluate_pipeline(stages)
    print(f"\nDVFS plan (V100 model): FFT stage locked to {fft_opt:.0f} MHz")
    for st in rep.stages:
        print(f"  {st.name:<14} f={st.f:7.1f} MHz  t={st.time*1e3:7.2f} ms"
              f"  P={st.power:6.1f} W")
    print(f"FFT time share {100*share:.0f}%  ->  composite I_ef "
          f"{rep.i_ef:.3f} at {100*rep.slowdown:.2f}% slowdown "
          f"(paper Table 4: 1.24-1.29)")

    # the sampled power trace of Fig. 19
    ts, ps, fs = sched.power_trace(stages)
    print(f"power trace: {len(ts)} samples, "
          f"P range [{ps.min():.0f}, {ps.max():.0f}] W, "
          f"clock range [{fs.min():.0f}, {fs.max():.0f}] MHz")


if __name__ == "__main__":
    main()
