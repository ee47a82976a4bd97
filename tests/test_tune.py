"""Autotuner tests: cache persistence, determinism, plan-routing consults.

Covers the PR's acceptance criteria:
  * round-trip persistence of the on-disk tuning cache; corrupted and
    version-mismatched files fall back to heuristics without crashing;
  * a monkeypatched timer proves identical measurements yield an
    identical chosen config (determinism);
  * plan construction consults the tuning cache exactly once per
    (device, shape, kind) no matter how often plans rebuild;
  * ``REPRO_FFT_DISABLE_TUNING=1`` restores the pre-PR heuristic path
    bit-for-bit (the very same memoised plan objects);
  * the serving cache keys entries on the tuned config, so tuned plans
    are served transparently and never go stale.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hardware import TESLA_V100, TPU_V5E
from repro.fft.convolve import conv_plan, select_nfft
from repro.fft.plan import plan_for_length, plan_with_config
from repro.fft.plan_nd import plan_nd
from repro.tune import (CACHE_VERSION, HEURISTIC, ConfigKey, KernelConfig,
                        TuneRecord, TuningCache, TuningContext, cache_path,
                        common_config, generate_candidates, plan_config,
                        prune_candidates, tune_length, tune_segment,
                        use_tuning)

KEY = jax.random.PRNGKey(0)


def _tuned_cache(device="testdev", entries=()):
    cache = TuningCache(device=device)
    for shape, kind, cfg in entries:
        cache.put(ConfigKey(device, shape, kind), TuneRecord(config=cfg))
    return cache


def _spy(monkeypatch, hook: str) -> list:
    """Record (args, kwargs) of every call through a ``repro.fft.plan``
    kernel hook."""
    import repro.fft.plan as plan_mod
    calls = []
    orig = getattr(plan_mod, hook)

    def spy(*args, **kw):
        calls.append((args, kw))
        return orig(*args, **kw)

    monkeypatch.setattr(plan_mod, hook, spy)
    return calls


def rand_c(shape):
    kr, ki = jax.random.split(KEY)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


# ---------------------------------------------------------------------------
# Config / key plumbing
# ---------------------------------------------------------------------------

class TestConfig:
    def test_json_round_trip(self):
        cfg = KernelConfig(tile_b=16, split=(64, 128), segment=1024,
                           source="tuned")
        assert KernelConfig.from_dict(cfg.to_dict()) == cfg
        assert KernelConfig.from_dict(HEURISTIC.to_dict()) == HEURISTIC

    def test_is_heuristic(self):
        assert HEURISTIC.is_heuristic
        assert not KernelConfig(tile_b=8).is_heuristic
        assert not KernelConfig(segment=512).is_heuristic

    def test_key_token_round_trip(self):
        key = ConfigKey("TPU-v5e", (4096, 33, 9), "conv", "fp16")
        assert ConfigKey.from_token(key.token()) == key


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------

class TestCachePersistence:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "dev.json")
        cache = _tuned_cache(entries=[
            ((256,), "c2c", KernelConfig(tile_b=16, source="tuned")),
            ((512,), "r2c", KernelConfig(tile_b=8, source="tuned")),
        ])
        rec = TuneRecord(config=KernelConfig(tile_b=16, source="tuned"),
                         objective="energy", score=1.5, heuristic_score=2.0,
                         measured_s=0.5, heuristic_s=0.7, candidates=12,
                         measured=5)
        cache.put(ConfigKey("testdev", (1024,), "c2c"), rec)
        cache.save(path)
        loaded = TuningCache.load("testdev", path=path)
        assert len(loaded) == 3
        got = loaded.get(ConfigKey("testdev", (1024,), "c2c"))
        assert got == rec
        assert got.speedup_vs_heuristic == pytest.approx(1.4)

    def test_corrupted_file_falls_back_empty(self, tmp_path):
        path = str(tmp_path / "dev.json")
        with open(path, "w") as f:
            f.write("{ not json !!")
        loaded = TuningCache.load("testdev", path=path)
        assert len(loaded) == 0
        # ... and plan construction on top of it stays heuristic, no crash
        with use_tuning(TuningContext(loaded)):
            plan = plan_for_length(256)
        assert plan is plan_with_config(256)

    def test_version_mismatch_falls_back_empty(self, tmp_path):
        path = str(tmp_path / "dev.json")
        with open(path, "w") as f:
            json.dump({"version": CACHE_VERSION + 1, "entries": {
                "testdev|256|c2c|fp32": {"config": {"tile_b": 4}}}}, f)
        assert len(TuningCache.load("testdev", path=path)) == 0

    def test_malformed_record_falls_back_empty(self, tmp_path):
        path = str(tmp_path / "dev.json")
        with open(path, "w") as f:
            json.dump({"version": CACHE_VERSION,
                       "entries": {"testdev|256|c2c|fp32": 42}}, f)
        assert len(TuningCache.load("testdev", path=path)) == 0

    def test_missing_file_is_empty(self, tmp_path):
        assert len(TuningCache.load("testdev",
                                    path=str(tmp_path / "nope.json"))) == 0

    def test_env_override_controls_path(self, monkeypatch, tmp_path):
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "x.json"))
        assert cache_path("anydev") == str(tmp_path / "x.json")
        monkeypatch.delenv("REPRO_TUNE_CACHE")
        assert cache_path("anydev").endswith(
            os.path.join("repro-tune", "anydev.json"))

    def test_atomic_save_creates_dirs(self, tmp_path):
        path = str(tmp_path / "deep" / "nested" / "dev.json")
        cache = _tuned_cache()
        assert cache.save(path) == path
        assert json.load(open(path))["version"] == CACHE_VERSION


# ---------------------------------------------------------------------------
# The tuner proper
# ---------------------------------------------------------------------------

class _FakeClock:
    """Deterministic pseudo-random clock: same call sequence, same times."""

    def __init__(self):
        self.t = 0.0
        self.calls = 0

    def __call__(self):
        self.calls += 1
        self.t += 1e-4 * ((self.calls * 7919) % 13 + 1)
        return self.t


class TestTuner:
    def test_candidates_include_heuristic_first(self):
        cands = generate_candidates(256, "c2c", batch=64)
        assert cands[0] is HEURISTIC
        assert len(cands) == len(set(cands))        # no duplicates
        # no candidate is a functional clone of the heuristic
        assert not any(c.is_heuristic for c in cands[1:])

    @pytest.mark.parametrize("n", [256, 2048, 2**13])
    def test_tile_candidates_fit_vmem_budget(self, n):
        """Every proposed tile keeps the kernel's eight pipelined planes
        inside the block budget the kernels size their own tiles by."""
        from repro.kernels.common import BLOCK_BUDGET_BYTES
        from repro.tune.tuner import _tile_candidates
        tiles = [t for t in _tile_candidates(n, batch=1024) if t]
        assert all(t * n * 4 * 8 <= BLOCK_BUDGET_BYTES for t in tiles)

    def test_prune_keeps_heuristic_and_respects_budget(self):
        cands = generate_candidates(256, "c2c", batch=64)
        kept = prune_candidates(cands, 256, "c2c", TESLA_V100, "energy", 4)
        assert kept[0].config is HEURISTIC
        assert len(kept) <= 4

    def test_monkeypatched_timer_determinism(self):
        """Identical measurements => identical chosen config, bit for bit."""
        results = []
        for _ in range(2):
            cache = TuningCache(device="det-test")
            res = tune_length(256, cache=cache, objective="time",
                              repeats=3, warmup=0, timer=_FakeClock(),
                              save=False)
            results.append(res)
        a, b = results
        assert a.config == b.config
        assert a.record == b.record
        assert a.measurements == b.measurements > 0

    def test_never_regresses_heuristic(self):
        """A timer rigged AGAINST every non-heuristic candidate must make
        the tuner return the heuristic (speedup exactly 1.0)."""
        class RiggedClock(_FakeClock):
            def __call__(self):
                self.calls += 1
                # first measured candidate (the heuristic) looks fast,
                # everything after looks monotonically slower
                self.t += 1e-4 * self.calls
                return self.t

        cache = TuningCache(device="rig-test")
        res = tune_length(128, cache=cache, objective="time", repeats=2,
                          warmup=0, timer=RiggedClock(), save=False)
        assert res.config == HEURISTIC
        assert res.speedup_vs_heuristic == 1.0

    def test_cache_replay_skips_measurement(self, tmp_path):
        path = str(tmp_path / "dev.json")
        cache = TuningCache(device="replay-test")
        first = tune_length(256, cache=cache, objective="time", repeats=2,
                            warmup=0, timer=_FakeClock(), save=False)
        cache.save(path)
        fresh = TuningCache.load("replay-test", path=path)
        again = tune_length(256, cache=fresh)
        assert again.replayed
        assert again.measurements == 0
        assert again.config == first.config

    def test_rejects_unknown_objective_and_kind(self):
        with pytest.raises(ValueError, match="objective"):
            tune_length(64, objective="joules", cache=TuningCache("x"))
        with pytest.raises(ValueError, match="kind"):
            tune_length(64, kind="dct", cache=TuningCache("x"))

    def test_tune_segment_filter_longer_than_kernel_limit(self):
        """Filters too long for any single-pass segment fall through to
        multi-pass segments (no empty candidate list / IndexError)."""
        res = tune_segment(2**15, 5000, 2, cache=TuningCache("long-test"),
                           save=False)
        assert res.config.segment >= 5000
        assert res.config.segment & (res.config.segment - 1) == 0

    def test_tune_segment_model_choice_persists(self, tmp_path):
        path = str(tmp_path / "dev.json")
        cache = TuningCache(device="seg-test")
        res = tune_segment(4096, 64, 8, cache=cache, save=False)
        assert res.config.segment >= 64
        assert res.config.segment & (res.config.segment - 1) == 0
        cache.save(path)
        fresh = TuningCache.load("seg-test", path=path)
        again = tune_segment(4096, 64, 8, cache=fresh)
        assert again.replayed and again.config == res.config


# ---------------------------------------------------------------------------
# Plan routing: consult-once + bit-for-bit disable
# ---------------------------------------------------------------------------

class TestPlanRouting:
    def test_plan_consults_cache_exactly_once_per_key(self):
        cache = _tuned_cache(entries=[
            ((256,), "c2c", KernelConfig(tile_b=16, source="tuned"))])
        ctx = TuningContext(cache)
        with use_tuning(ctx):
            for _ in range(7):
                plan_for_length(256)
            assert ctx.consults == 1
            assert cache.lookups == 1
            plan_for_length(256, "r2c")            # distinct (shape, kind)
            assert ctx.consults == 2
            plan_for_length(512)                   # distinct shape
            assert ctx.consults == 3
            for _ in range(5):
                plan_nd((64, 64))                  # N-D key, same context
            assert ctx.consults == 4

    def test_tuned_plan_applies_config(self, monkeypatch):
        calls = _spy(monkeypatch, "_kernel_fft")
        cfg = KernelConfig(tile_b=4, source="tuned")
        cache = _tuned_cache(entries=[((256,), "c2c", cfg)])
        with use_tuning(TuningContext(cache)):
            plan = plan_for_length(256)
        x = rand_c((5, 256))
        np.testing.assert_allclose(plan(x), jnp.fft.fft(x),
                                   rtol=3e-3, atol=3e-3)
        assert [kw["tile_b"] for _, kw in calls] == [4]   # tuned tile used

    def test_tuned_four_step_split_applies(self, monkeypatch):
        calls = _spy(monkeypatch, "_kernel_fft_axis1")
        n = 2**14
        cfg = KernelConfig(split=(2**5, 2**9), source="tuned")
        cache = _tuned_cache(entries=[((n,), "c2c", cfg)])
        with use_tuning(TuningContext(cache)):
            plan = plan_for_length(n)
        assert plan.algorithm == "four-step"
        x = rand_c((2, n))
        plan(x)
        # the tuned (32, 512) cut, not the balanced (128, 128): the column
        # pass transforms n1 = 32 rows of n2 = 512 columns
        assert [a[0].shape[-2:] for a, _ in calls] == [(32, 512)]
        np.testing.assert_allclose(plan(x), jnp.fft.fft(x),
                                   rtol=3e-3, atol=3e-3)

    def test_bluestein_plan_threads_config_into_inner_ffts(self, monkeypatch):
        """Non-pow2 (Bluestein) plans must actually execute their tuned
        config — otherwise the tuner times byte-identical executables."""
        calls = _spy(monkeypatch, "_kernel_fft")
        cfg = KernelConfig(tile_b=4, source="tuned")
        plan = plan_with_config(45, "c2c", cfg)
        assert plan.algorithm == "bluestein"
        x = rand_c((3, 45))
        np.testing.assert_allclose(plan(x), jnp.fft.fft(x),
                                   rtol=3e-3, atol=3e-3)
        assert any(kw.get("tile_b") == 4 for _, kw in calls)

    def test_no_heuristic_clone_candidates(self):
        """Explicit copies of the heuristic's resolved tile / balanced
        split are excluded — they could beat the heuristic on noise."""
        from repro.kernels.common import batch_tile
        from repro.tune.tuner import _split_candidates, _tile_candidates
        from repro.fft.plan import _four_step_split
        n, batch = 256, 64
        heuristic_tile = min(batch_tile(n, 4, buffers=8), batch)
        assert heuristic_tile not in [
            t for t in _tile_candidates(n, batch) if t is not None]
        n4 = 2**15
        assert _four_step_split(n4) not in _split_candidates(n4)[1:]

    def test_invalid_tuned_split_falls_back_to_balanced(self):
        n = 2**14
        cfg = KernelConfig(split=(3, n // 3), source="tuned")  # not pow2
        plan = plan_with_config(n, "c2c", cfg)
        ref = plan_with_config(n)
        assert plan.stages == ref.stages

    def test_disable_env_restores_heuristic_bit_for_bit(self, monkeypatch):
        """The escape hatch returns the SAME memoised heuristic plan object
        the pre-tuner path built — not an equivalent copy."""
        heuristic = plan_with_config(256)
        cache = _tuned_cache(entries=[
            ((256,), "c2c", KernelConfig(tile_b=4, source="tuned"))])
        ctx = TuningContext(cache)
        with use_tuning(ctx):
            tuned = plan_for_length(256)
            assert tuned is not heuristic
            monkeypatch.setenv("REPRO_FFT_DISABLE_TUNING", "1")
            assert plan_for_length(256) is heuristic
            assert plan_nd((256,)) .fn is not None  # no crash on N-D either
            monkeypatch.delenv("REPRO_FFT_DISABLE_TUNING")
            assert plan_for_length(256) is tuned

    def test_no_context_is_heuristic_path(self):
        assert plan_config((256,), "c2c") is None
        assert plan_for_length(256) is plan_with_config(256)

    def test_conv_plan_uses_tuned_segment(self):
        n, taps, t = 2048, 33, 4
        cache = _tuned_cache(entries=[
            ((n, taps, t), "conv", KernelConfig(segment=1024,
                                                source="tuned"))])
        with use_tuning(TuningContext(cache)):
            plan = conv_plan(n, taps, t)
        assert plan.nfft == 1024
        # untuned / disabled path keeps the cost-model selection
        assert conv_plan(n, taps, t).nfft == select_nfft(taps, n, t)

    def test_conv_plan_ignores_invalid_tuned_segment(self):
        n, taps, t = 2048, 33, 4
        cache = _tuned_cache(entries=[
            ((n, taps, t), "conv", KernelConfig(segment=16,  # < taps
                                                source="tuned"))])
        with use_tuning(TuningContext(cache)):
            assert conv_plan(n, taps, t).nfft == select_nfft(taps, n, t)

    def test_common_default_serves_untuned_keys(self, monkeypatch):
        calls = _spy(monkeypatch, "_kernel_fft")
        cache = _tuned_cache(entries=[
            ((256,), "c2c", KernelConfig(tile_b=16, source="tuned"))])
        ctx = TuningContext(cache)
        ctx.common = KernelConfig(tile_b=8, source="common")
        with use_tuning(ctx):
            tuned = plan_for_length(256)           # its own entry
            untuned = plan_for_length(1024)        # falls back to common
        tuned(rand_c((2, 256)))
        untuned(rand_c((2, 1024)))
        assert [kw["tile_b"] for _, kw in calls] == [16, 8]


# ---------------------------------------------------------------------------
# Common config (paper Sec. 4, software axis)
# ---------------------------------------------------------------------------

class TestCommonConfig:
    def test_empty_cache_raises(self):
        with pytest.raises(ValueError, match="no tuned"):
            common_config(TuningCache("empty"))

    def test_heuristic_only_cache_yields_heuristic(self):
        cache = _tuned_cache(entries=[((256,), "c2c", HEURISTIC),
                                      ((512,), "c2c", HEURISTIC)])
        cfg, regret = common_config(cache)
        assert cfg.is_heuristic
        assert regret == pytest.approx(0.0)

    def test_portable_axes_only(self):
        cache = _tuned_cache(entries=[
            ((2**14,), "c2c", KernelConfig(tile_b=16, split=(32, 512),
                                           source="tuned"))])
        cfg, regret = common_config(cache)
        assert cfg.split is None and cfg.segment == 0
        assert regret >= 0.0


# ---------------------------------------------------------------------------
# Serving integration: the plan/sweep cache keys on the tuned config
# ---------------------------------------------------------------------------

class TestServingIntegration:
    def _service_cache(self):
        from repro.serving.cache import PlanSweepCache
        return PlanSweepCache(TPU_V5E, batch_bytes=2**24)

    def _key(self, n=256):
        from repro.serving.request import ShapeKey
        return ShapeKey(kind="fft", n=n, precision="fp32",
                        device=TPU_V5E.name)

    def test_retune_invalidates_entries_transparently(self):
        cache = self._service_cache()
        key = self._key()
        e1 = cache.entry(key)
        assert cache.entry(key) is e1              # heuristic entry cached
        cfg = KernelConfig(tile_b=4, source="tuned")
        tcache = _tuned_cache(entries=[((256,), "c2c", cfg)])
        with use_tuning(TuningContext(tcache)):
            e2 = cache.entry(key)                  # tuned entry, new build
            assert e2 is not e1
            assert e2.plan is plan_with_config(256, "c2c", cfg)
            assert cache.entry(key) is e2          # ... and then cached
        assert cache.entry(key) is e1              # context gone -> heuristic

    def test_fdas_entries_key_on_tuned_conv_segment(self):
        """A conv-segment re-tune must rebuild FDAS entries, not serve the
        plan/sweep priced under the old segment."""
        from repro.search.templates import TemplateBank
        from repro.serving.request import ShapeKey
        n, templates = 2048, 5
        key = ShapeKey(kind="fdas", n=n, precision="fp32",
                       device=TPU_V5E.name, templates=templates)
        bank = TemplateBank.linear(zmax=(templates - 1) / 2.0,
                                   n_templates=templates)
        cache = self._service_cache()
        e1 = cache.entry(key)
        assert cache.entry(key) is e1
        tuned = _tuned_cache(entries=[
            ((n // 2 + 1, bank.taps, templates), "conv",
             KernelConfig(segment=512, source="tuned"))])
        with use_tuning(TuningContext(tuned)):
            e2 = cache.entry(key)
            assert e2 is not e1
            assert e2.plan.nfft == 512             # tuned segment applied
        assert cache.entry(key) is e1              # context gone -> heuristic

    def test_serving_consults_tuning_once_per_shape(self):
        tcache = _tuned_cache(entries=[
            ((256,), "c2c", KernelConfig(tile_b=8, source="tuned"))])
        ctx = TuningContext(tcache)
        cache = self._service_cache()
        with use_tuning(ctx):
            for _ in range(6):
                cache.entry(self._key())
        # one consult for the serving key + plan build combined: the
        # context memoises, however many layers ask
        assert ctx.consults == 1
        assert cache.stats.plan_builds == 1
        assert cache.stats.sweeps == 1