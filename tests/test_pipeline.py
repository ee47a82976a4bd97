"""End-to-end pulsar-search pipeline tests (repro.search.pipeline).

The acceptance contract: a jitted ``pulsar_search`` recovers every
injected pulsar at its exact (DM trial, template, bin) cell, the
no-signal control yields zero candidates, the graph launches each fused
kernel exactly once (routing counters, the test_plan_nd.py pattern),
per-stage DVFS plans cover all four stages, and the serving cache keys
pulsar entries on the full pipeline configuration + active tuned config.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hardware import TESLA_V100, TPU_V5E
from repro.data.synthetic import (FilterbankSpec, InjectedPulsar,
                                  synthetic_filterbank)
from repro.search.pipeline import (DispersionPlan, plan_pulsar_stages,
                                   pulsar_search, serving_sifted)
from repro.search.sift import sift_candidates
from repro.search.templates import TemplateBank

SPEC = FilterbankSpec(nchan=16, ntime=2048)
PLAN = DispersionPlan.from_spec(SPEC, n_trials=8)
BANK = TemplateBank.linear(zmax=4.0, n_templates=5)


def _search(fb, **kw):
    kw.setdefault("n_harmonics", 8)
    return pulsar_search(fb, PLAN, BANK, **kw)


class TestInjectedRecovery:
    """Satellite 1: exact-cell recovery + the false-positive guard."""

    def test_two_pulsars_recovered_at_exact_cells(self):
        # drifts are (-4, -2, 0, 2, 4): z=2 -> template 3, z=-4 -> 0
        pulsars = (InjectedPulsar(dm=PLAN.dms[3], k0=300, z=2.0, amp=0.12),
                   InjectedPulsar(dm=PLAN.dms[6], k0=611, z=-4.0, amp=0.12))
        fb = synthetic_filterbank(SPEC, pulsars, noise=1.0, seed=2)
        res = _search(fb)
        c = res.candidates
        got = {(int(d), int(t), int(b))
               for d, t, b in zip(c.dm[0], c.template[0], c.bin[0])
               if int(d) >= 0}
        assert got == {(3, 3, 300), (6, 0, 611)}
        # every candidate above threshold, padding zeroed
        kept = np.asarray(c.dm[0]) >= 0
        assert (np.asarray(c.snr[0])[kept] > 25.0).all()
        assert (np.asarray(c.snr[0])[~kept] == 0.0).all()

    def test_no_signal_control_zero_candidates(self):
        fb = synthetic_filterbank(SPEC, (), noise=1.0, seed=3)
        res = _search(fb)
        c = res.candidates
        assert (np.asarray(c.dm) == -1).all()
        assert (np.asarray(c.template) == -1).all()
        assert (np.asarray(c.bin) == -1).all()
        assert (np.asarray(c.snr) == 0.0).all()
        # the raw statistic maximum sits far below the threshold
        assert float(res.stat.max()) < 25.0

    def test_batched_filterbanks_search_independently(self):
        quiet = synthetic_filterbank(SPEC, (), noise=1.0, seed=4)
        loud = synthetic_filterbank(
            SPEC, (InjectedPulsar(dm=PLAN.dms[2], k0=150, amp=0.15),),
            noise=1.0, seed=5)
        res = _search(jnp.stack([quiet, loud]))
        c = res.candidates
        assert (np.asarray(c.dm[0]) == -1).all()
        assert (int(c.dm[1, 0]), int(c.template[1, 0]),
                int(c.bin[1, 0])) == (2, 2, 150)

    def test_rank2_filterbank_accepted(self):
        fb = synthetic_filterbank(SPEC, (), noise=1.0, seed=6)
        res = _search(fb[None])
        assert res.candidates.dm.shape[0] == 1

    def test_rejects_bad_rank(self):
        with pytest.raises(ValueError, match="nchan, ntime"):
            _search(jnp.ones((2, 2, 4, 64)))

    def test_serving_sifted_packing(self):
        fb = synthetic_filterbank(
            SPEC, (InjectedPulsar(dm=PLAN.dms[3], k0=300, amp=0.2),),
            noise=1.0, seed=2)
        res = _search(fb)
        packed = serving_sifted(res)
        assert packed.shape == (1, 16, 5)
        np.testing.assert_allclose(packed[0, 0, :3], [3.0, 2.0, 300.0])
        # padding rows are (-1, -1, -1, -1, 0)
        np.testing.assert_allclose(packed[0, -1], [-1, -1, -1, -1, 0.0])


class TestRoutingCounters:
    """Satellite 2: the jitted graph launches each fused kernel exactly
    once per compile — no hidden re-dedispersion or ladder round-trips."""

    def test_each_fused_kernel_launches_once(self, monkeypatch):
        import repro.search.pipeline as pl
        calls = {"dedisp": 0, "hsum": 0}
        real_d, real_h = pl._kernel_dedisp, pl._kernel_hsum

        def count_d(*a, **k):
            calls["dedisp"] += 1
            return real_d(*a, **k)

        def count_h(*a, **k):
            calls["hsum"] += 1
            return real_h(*a, **k)

        monkeypatch.setattr(pl, "_kernel_dedisp", count_d)
        monkeypatch.setattr(pl, "_kernel_hsum", count_h)
        # fresh static shapes: this exact configuration appears nowhere
        # else, so jit MUST re-trace through the counting wrappers
        spec = FilterbankSpec(nchan=3, ntime=256)
        plan = DispersionPlan.from_spec(spec, n_trials=3)
        bank = TemplateBank.linear(zmax=1.0, n_templates=3)
        fb = synthetic_filterbank(spec, (), noise=1.0, seed=7)
        res = pl.pulsar_search(fb, plan, bank, n_harmonics=2, pool=16)
        res.stat.block_until_ready()
        assert calls == {"dedisp": 1, "hsum": 1}
        # a second identical call reuses the compiled graph: no re-trace
        pl.pulsar_search(fb, plan, bank,
                         n_harmonics=2, pool=16).stat.block_until_ready()
        assert calls == {"dedisp": 1, "hsum": 1}


class TestSift:
    def _volume(self, cells, shape=(1, 4, 3, 512)):
        stat = np.zeros(shape, np.float32)
        for (d, t, b), v in cells:
            stat[0, d, t, b] = v
        return jnp.asarray(stat), jnp.zeros(shape, jnp.int32)

    def test_harmonic_alias_absorbed(self):
        """A cell at 2x the bin within dm_tol is the same pulsar's
        harmonic: only the stronger survives."""
        stat, lev = self._volume([((2, 1, 100), 50.0), ((2, 1, 200), 30.0)])
        c = sift_candidates(stat, lev)
        kept = [(int(d), int(b)) for d, b in zip(c.dm[0], c.bin[0])
                if int(d) >= 0]
        assert kept == [(2, 100)]

    def test_adjacent_dm_leak_absorbed(self):
        stat, lev = self._volume([((2, 1, 100), 50.0), ((3, 1, 101), 30.0)])
        c = sift_candidates(stat, lev)
        assert [(int(d), int(b)) for d, b in zip(c.dm[0], c.bin[0])
                if int(d) >= 0] == [(2, 100)]

    def test_distant_candidates_both_kept(self):
        """Far apart in DM and unrelated in bin: two real candidates."""
        stat, lev = self._volume([((0, 0, 100), 50.0), ((3, 2, 173), 40.0)])
        c = sift_candidates(stat, lev)
        got = {(int(d), int(t), int(b))
               for d, t, b in zip(c.dm[0], c.template[0], c.bin[0])
               if int(d) >= 0}
        assert got == {(0, 0, 100), (3, 2, 173)}

    def test_below_threshold_dropped(self):
        stat, lev = self._volume([((1, 0, 50), 10.0)])
        c = sift_candidates(stat, lev, threshold=25.0)
        assert (np.asarray(c.dm) == -1).all()

    def test_weak_cell_cannot_absorb(self):
        """A sub-threshold stronger cell must not erase a real detection."""
        stat, lev = self._volume([((2, 1, 100), 20.0), ((2, 1, 200), 30.0)])
        c = sift_candidates(stat, lev, threshold=25.0)
        assert [(int(d), int(b)) for d, b in zip(c.dm[0], c.bin[0])
                if int(d) >= 0] == [(2, 200)]

    def test_level_travels_with_candidate(self):
        stat = np.zeros((1, 2, 2, 64), np.float32)
        lev = np.zeros((1, 2, 2, 64), np.int32)
        stat[0, 1, 0, 30] = 40.0
        lev[0, 1, 0, 30] = 3
        c = sift_candidates(jnp.asarray(stat), jnp.asarray(lev))
        assert int(c.level[0, 0]) == 3

    def test_guards(self):
        with pytest.raises(ValueError, match="volume"):
            sift_candidates(jnp.ones((4, 8)), jnp.zeros((4, 8), jnp.int32))
        with pytest.raises(ValueError, match="shapes differ"):
            sift_candidates(jnp.ones((1, 2, 2, 8)),
                            jnp.zeros((1, 2, 2, 9), jnp.int32))


class TestDispersionPlan:
    def test_from_spec_grid(self):
        assert PLAN.n_trials == 8
        assert PLAN.nchan == SPEC.nchan
        assert PLAN.dms[0] == 0.0
        assert PLAN.delays[0] == (0,) * SPEC.nchan
        assert PLAN.max_delay == max(PLAN.delays[-1])
        assert PLAN.delay_array().shape == (8, 16)
        hash(PLAN)                       # static jit argument => hashable

    def test_injection_and_plan_share_delays(self):
        """The exact-recovery mechanism: both sides round identically."""
        np.testing.assert_array_equal(
            PLAN.delay_array()[3], SPEC.delay_samples(PLAN.dms[3]))

    def test_rejects_overflowing_grid(self):
        spec = FilterbankSpec(nchan=8, ntime=128)
        with pytest.raises(ValueError, match="exceed"):
            DispersionPlan.from_spec(spec, dms=(1e5,))

    def test_rejects_bad_trial_counts(self):
        with pytest.raises(ValueError, match="n_trials"):
            DispersionPlan.from_spec(SPEC, n_trials=0)
        with pytest.raises(ValueError, match=">= 1 DM trial"):
            DispersionPlan(dms=(), delays=(), tsamp=1e-4)
        with pytest.raises(ValueError, match="delay rows"):
            DispersionPlan(dms=(0.0, 1.0), delays=((0, 0),), tsamp=1e-4)


class TestStagePlanning:
    """Per-stage DVFS: four stage models, a clock lock per stage, and a
    positive end-to-end real-time margin."""

    def test_workload_has_four_stages(self):
        from repro.core.workloads import PulsarCase, pulsar_search_workload
        case = PulsarCase(nchan=16, ntime=2048, dm_trials=8, templates=5,
                          taps=BANK.taps)
        profs = pulsar_search_workload(case, TESLA_V100)
        assert [p.name for p in profs] == ["dedisp", "fdas",
                                           "harmonic-sum", "sift"]
        for p in profs:
            assert float(p.time(TESLA_V100.f_max, TESLA_V100)) > 0

    def test_plan_pulsar_stages(self):
        sp = plan_pulsar_stages(SPEC, PLAN, BANK, 8, TESLA_V100)
        assert set(sp.locked) == {"dedisp", "fdas", "harmonic-sum", "sift"}
        grid = set(TESLA_V100.frequencies().tolist())
        assert all(c in grid for c in sp.locked.values())
        assert len(sp.report.stages) == 4
        assert all(s.energy > 0 and s.time > 0 for s in sp.report.stages)
        assert sp.realtime_margin >= 1.0        # real time at the locks
        assert sp.t_acquire == pytest.approx(SPEC.t_acquire)

    def test_total_profile_covers_stage_sum(self):
        """The merged profile the service sweeps must price the same work
        as the per-stage models (same HBM bytes and flops)."""
        from repro.core.workloads import (PulsarCase,
                                          pulsar_search_total_profile,
                                          pulsar_search_workload)
        case = PulsarCase(nchan=16, ntime=2048, dm_trials=8, templates=5,
                          taps=BANK.taps)
        profs = pulsar_search_workload(case, TESLA_V100)
        total = pulsar_search_total_profile(case, TESLA_V100)
        assert total.flops == pytest.approx(sum(p.flops for p in profs))
        assert total.t_mem == pytest.approx(sum(p.t_mem for p in profs))
        assert total.t_cache == pytest.approx(
            sum(p.t_cache for p in profs))


class TestServingPulsarCacheKeys:
    """Satellite 3: one PlanSweepCache entry per (shape, DM count, bank,
    harmonics, active tuned config) — config changes never serve stale
    pipelines."""

    NCHAN, NTIME = 8, 512

    def _cache(self):
        from repro.serving.cache import PlanSweepCache
        return PlanSweepCache(TPU_V5E, batch_bytes=2 ** 24)

    def _key(self, dm_trials=4, templates=5, n_harmonics=4):
        from repro.serving.request import ShapeKey
        return ShapeKey(kind="pulsar", n=self.NCHAN * self.NTIME,
                        precision="fp32", n_harmonics=n_harmonics,
                        device=TPU_V5E.name, transform="r2c",
                        shape=(self.NCHAN, self.NTIME),
                        templates=templates, dm_trials=dm_trials)

    def test_distinct_pipeline_configs_get_distinct_entries(self):
        cache = self._cache()
        base = cache.entry(self._key())
        assert cache.entry(self._key()) is base             # hit
        assert cache.entry(self._key(dm_trials=8)) is not base
        assert cache.entry(self._key(templates=3)) is not base
        assert cache.entry(self._key(n_harmonics=8)) is not base
        assert cache.stats.misses == 4
        assert cache.stats.hits == 1

    def test_entry_carries_stage_plan(self):
        e = self._cache().entry(self._key())
        assert e.plan.n_trials == 4                 # the DispersionPlan
        assert set(e.locked) == {"dedisp", "fdas", "harmonic-sum", "sift"}
        assert len(e.stages.stages) == 4
        assert e.realtime_margin is not None and e.realtime_margin > 0

    def test_retune_rebuilds_pulsar_entry(self):
        """A re-tune of the pipeline's inner R2C must rebuild the entry —
        serving the stale plan would ignore the tuned config (the
        test_tune.py TestServingIntegration contract, pulsar kind)."""
        from repro.tune import (ConfigKey, KernelConfig, TuneRecord,
                                TuningCache, TuningContext, use_tuning)
        cache = self._cache()
        key = self._key()
        e1 = cache.entry(key)
        assert cache.entry(key) is e1
        tuned = TuningCache(device=TPU_V5E.name)
        tuned.put(ConfigKey(TPU_V5E.name, (self.NTIME,), "r2c"),
                  TuneRecord(config=KernelConfig(tile_b=8, source="tuned")))
        with use_tuning(TuningContext(tuned)):
            e2 = cache.entry(key)
            assert e2 is not e1                    # rebuilt, not served stale
            assert cache.entry(key) is e2          # ... and then cached
        assert cache.entry(key) is e1              # context gone -> heuristic
