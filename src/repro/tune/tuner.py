"""Energy-aware autotuner: cost-model-pruned kernel-configuration search.

The paper finds each FFT length's best *clock* by measurement (sweep,
then argmin J/transform under a latency bound); this module applies the
same discipline to the *software* configuration axes the clock sweep
holds fixed: batch tile, the four-step ``(n1, n2)`` split, and the
overlap-save segment length.  (The MXU kernels have no radix schedule to
tune: each fused pass is one or two DFT-matrix products.)

The search is staged so measurement stays cheap:

  1. **Generate** every candidate :class:`KernelConfig` for the key
     (splits/segments x batch tiles).
  2. **Prune with the cost model** (``core.workloads`` pass/traffic
     accounting + ``core.dvfs.sweep``): candidates are ranked by modelled
     boost-clock time (objective ``"time"``) or modelled J/transform at
     the DVFS-optimal clock (objective ``"energy"``) and only the top
     few survive — nothing untimed is ever worse than unranked.  The
     model prices neither the tile nor the split, so today every FFT
     candidate ties and the sort keeps generation order.
  3. **Measure survivors** with the shared warm-up/repeat methodology
     (:func:`repro.tune.timing.time_fn`), always including the
     heuristic config.
  4. **Score**: ``time`` = measured wall; ``energy`` = model power at the
     workload's DVFS-optimal clock x measured wall (J/call).  Whatever
     the objective, a config that measures *slower* than the heuristic is
     rejected — the heuristic's latency is the real-time bound (Sec. 2.3),
     so the tuner may return the heuristic but can never regress it.

Results persist to the per-device :class:`~repro.tune.cache.TuningCache`;
a second run replays the cached choice with **zero** measurements.
:func:`common_config` is the paper's Sec. 4 result on the software axis:
the single configuration minimising average modelled regret across every
tuned length, installable as the global default for untuned shapes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import numpy as np

from repro.core import dvfs
from repro.core.hardware import TESLA_V100, DeviceSpec
from repro.core.workloads import ConvCase, FFTCase, conv_workload, \
    fft_workload
from repro.fft.radix import is_pow2, next_pow2
from repro.tune.cache import TuneRecord, TuningCache
from repro.tune.config import (HEURISTIC, SOURCE_COMMON, SOURCE_TUNED,
                               ConfigKey, KernelConfig)
from repro.tune.context import TuningContext, use_tuning
from repro.tune.timing import time_fn

#: Batch tiles worth trying (f32 sublane is 8 on TPU; heuristic rides too).
TILE_CANDIDATES = (8, 16, 32, 64)

#: Survivors the measurement stage accepts per key (heuristic always rides).
DEFAULT_MEASURE_BUDGET = 5

#: Transform kinds :func:`tune_length` understands; "conv" tunes the
#: overlap-save segment of ``repro.fft.convolve`` instead of an FFT plan.
FFT_KINDS = ("c2c", "r2c", "c2r")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One generated config plus its cost-model ranking scores."""

    config: KernelConfig
    model_time: float           # modelled boost-clock seconds per batch
    model_j: float              # modelled J/transform at the optimal clock
    opt_power_w: float          # model power at the DVFS-optimal clock


@dataclasses.dataclass
class TuneResult:
    """Outcome of one :func:`tune_length` call."""

    key: ConfigKey
    record: TuneRecord
    measurements: int           # timed executions THIS call (0 on replay)
    replayed: bool              # served from the persistent cache
    survivors: tuple[KernelConfig, ...] = ()

    @property
    def config(self) -> KernelConfig:
        return self.record.config

    @property
    def speedup_vs_heuristic(self) -> float:
        return self.record.speedup_vs_heuristic


# ---------------------------------------------------------------------------
# Candidate generation
# ---------------------------------------------------------------------------

def _split_candidates(n: int) -> list[tuple[int, int] | None]:
    """Four-step (n1, n2) factorisations to try for a long pow2 length.

    The balanced heuristic cut is represented by None only — an explicit
    duplicate of it would be a functional clone of the heuristic that
    could "win" on timing noise.
    """
    from repro.fft.plan import MAX_SINGLE_PASS, _four_step_split
    if not is_pow2(n) or n <= MAX_SINGLE_PASS:
        return [None]
    splits: list[tuple[int, int] | None] = [None]    # heuristic balanced cut
    balanced = _four_step_split(n)
    log = n.bit_length() - 1
    for k in range(max(log // 2 - 1, 1), min(log // 2 + 2, log)):
        n1 = 1 << k
        n2 = n // n1
        if (max(n1, n2) <= MAX_SINGLE_PASS and (n1, n2) != balanced
                and (n1, n2) not in splits):
            splits.append((n1, n2))
    return splits


def _tile_candidates(n: int, batch: int) -> list[int | None]:
    """Batch tiles to try: the heuristic (None) plus explicit sublane
    multiples that fit the measurement batch and the kernels' VMEM block
    budget.

    The budget is the one the kernels size their own tiles by
    (``kernels.common.batch_tile``: eight pipelined planes in
    ``BLOCK_BUDGET_BYTES``), and the heuristic tile is the largest it
    admits, so every candidate is smaller.  The tile the heuristic would
    resolve to is excluded — an explicit copy of it is functionally the
    heuristic and must never beat it on noise.
    """
    from repro.kernels.common import batch_tile
    budget_tile = batch_tile(n, 4, buffers=8)
    heuristic_tile = min(budget_tile, batch)
    tiles: list[int | None] = [None]
    for t in TILE_CANDIDATES:
        if t < heuristic_tile and t not in tiles:
            tiles.append(t)
    return tiles


def generate_candidates(n: int, kind: str, batch: int) -> list[KernelConfig]:
    """The full config space for one key (heuristic config first)."""
    configs: list[KernelConfig] = [HEURISTIC]
    for split in _split_candidates(n):
        for tile in _tile_candidates(n, batch):
            cfg = KernelConfig(tile_b=tile, split=split, source=SOURCE_TUNED)
            if cfg.is_heuristic or cfg in configs:
                continue
            configs.append(cfg)
    return configs


def _segment_candidates(n: int, taps: int) -> list[int]:
    """Pow2 overlap-save segment lengths bracketing the signal.

    Mirrors :func:`repro.fft.convolve.select_nfft`'s bounds: the kernel
    cap only applies when some single-pass segment can hold the filter at
    all — longer filters fall through to multi-pass segments instead of
    producing an empty candidate list.
    """
    from repro.fft.plan import MAX_KERNEL_N
    lo = next_pow2(max(2 * taps, 16))
    hi = max(lo, next_pow2(n + taps - 1))
    if lo <= MAX_KERNEL_N:
        hi = min(hi, MAX_KERNEL_N)
    out = []
    nfft = lo
    while nfft <= hi:
        out.append(nfft)
        nfft *= 2
    return out


# ---------------------------------------------------------------------------
# Cost-model pruning
# ---------------------------------------------------------------------------

def _model_candidate(cfg: KernelConfig, n: int, kind: str,
                     model_device: DeviceSpec) -> Candidate:
    """Rank one config with the analytic pass/traffic model + DVFS sweep."""
    case = FFTCase(n=n, transform=kind if kind in FFT_KINDS else "c2c")
    res = dvfs.sweep(fft_workload(case, model_device), model_device)
    per = dvfs.energy_per_transform(res, case.n_fft)
    return Candidate(config=cfg, model_time=res.boost.time,
                     model_j=per["optimal_j"], opt_power_w=res.optimal.power)


def prune_candidates(configs: Sequence[KernelConfig], n: int, kind: str,
                     model_device: DeviceSpec, objective: str,
                     budget: int) -> list[Candidate]:
    """Keep the ``budget`` model-best candidates; the heuristic always
    survives (it anchors the never-regress guarantee)."""
    ranked = [_model_candidate(c, n, kind, model_device) for c in configs]
    score = (lambda c: c.model_time) if objective == "time" \
        else (lambda c: c.model_j)
    head, tail = ranked[0], sorted(ranked[1:], key=score)
    return [head] + tail[:max(budget - 1, 1)]


# ---------------------------------------------------------------------------
# Measurement + choice
# ---------------------------------------------------------------------------

def _fft_executable(n: int, kind: str, cfg: KernelConfig) -> Callable:
    import jax
    from repro.fft.plan import plan_with_config
    return jax.jit(plan_with_config(n, kind, cfg).fn)


def _fft_operand(n: int, kind: str, batch: int):
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(0)
    if kind == "r2c":
        return jax.random.normal(key, (batch, n), jnp.float32)
    if kind == "c2r":
        half = jax.random.normal(key, (batch, n // 2 + 1))
        return (half + 0.5j * half).astype(jnp.complex64)
    x = jax.random.normal(key, (batch, n))
    return (x + 1j * jax.random.normal(jax.random.PRNGKey(1), (batch, n))
            ).astype(jnp.complex64)


def tune_length(
    n: int,
    kind: str = "c2c",
    *,
    objective: str = "energy",
    cache: TuningCache | None = None,
    model_device: DeviceSpec = TESLA_V100,
    batch: int | None = None,
    measure_budget: int = DEFAULT_MEASURE_BUDGET,
    repeats: int = 3,
    warmup: int = 1,
    timer: Callable[[], float] = time.perf_counter,
    force: bool = False,
    save: bool = True,
) -> TuneResult:
    """Tune one ``(device, (n,), kind, dtype)`` key end to end.

    Replays the persisted choice with zero measurements when the cache
    already holds the key (pass ``force=True`` to re-measure).  ``timer``
    is injectable (determinism tests feed a fake clock).
    """
    if objective not in ("time", "energy"):
        raise ValueError(f"unknown objective {objective!r}; "
                         "have ('time', 'energy')")
    if kind not in FFT_KINDS:
        raise ValueError(f"unknown transform kind {kind!r}; have {FFT_KINDS}")
    cache = cache if cache is not None else TuningCache.load()
    key = ConfigKey(device=cache.device, shape=(int(n),), kind=kind)
    if not force:
        hit = cache.get(key)
        if hit is not None:
            return TuneResult(key=key, record=hit, measurements=0,
                              replayed=True)

    batch = batch or max(2**14 // n, 8)
    candidates = generate_candidates(n, kind, batch)
    survivors = prune_candidates(candidates, n, kind, model_device,
                                 objective, measure_budget)

    # Measure every survivor under a *disabled* tuning context so the plan
    # builders resolve exactly the config under test, nothing else.
    walls: list[float] = []
    with use_tuning(None):
        operand = _fft_operand(n, kind, batch)
        for cand in survivors:
            fn = _fft_executable(n, kind, cand.config)
            walls.append(time_fn(fn, operand, repeats=repeats,
                                 warmup=warmup, timer=timer))

    def score(i: int) -> float:
        if objective == "time":
            return walls[i]
        return survivors[i].opt_power_w * walls[i]      # J/call at f_opt

    best = min(range(len(survivors)), key=score)
    # Never regress the heuristic's wall time: its latency is the bound.
    if walls[best] > walls[0]:
        best = 0
    chosen = survivors[best].config
    if best != 0:
        chosen = dataclasses.replace(chosen, source=SOURCE_TUNED)
    record = TuneRecord(
        config=chosen,
        heuristic=HEURISTIC,
        objective=objective,
        score=score(best),
        heuristic_score=score(0),
        measured_s=walls[best],
        heuristic_s=walls[0],
        candidates=len(candidates),
        measured=len(survivors),
    )
    cache.put(key, record)
    if save:
        cache.save()
    return TuneResult(key=key, record=record,
                      measurements=len(survivors) * (repeats + warmup),
                      replayed=False,
                      survivors=tuple(c.config for c in survivors))


def tune_segment(
    n: int,
    taps: int,
    templates: int = 1,
    *,
    cache: TuningCache | None = None,
    model_device: DeviceSpec = TESLA_V100,
    save: bool = True,
) -> TuneResult:
    """Pick the overlap-save ``nfft`` by full cost-model sweep (no wall
    measurement: ``conv_workload`` prices every candidate's actual pass
    structure, and segments only change modelled traffic/FLOPs).

    Persisted under kind ``"conv"`` with shape ``(n, taps, templates)``;
    ``repro.fft.convolve.conv_plan`` consults it before ``select_nfft``.
    """
    cache = cache if cache is not None else TuningCache.load()
    key = ConfigKey(device=cache.device, shape=(int(n), int(taps),
                                                int(templates)), kind="conv")
    if (hit := cache.get(key)) is not None:
        return TuneResult(key=key, record=hit, measurements=0, replayed=True)

    def seg_j(nfft: int) -> float:
        case = ConvCase(n=n, templates=templates, taps=taps, nfft=nfft)
        res = dvfs.sweep(conv_workload(case, model_device), model_device)
        return res.optimal.energy / case.n_rows

    segments = _segment_candidates(n, taps)
    scored = sorted(segments, key=seg_j)
    from repro.fft.convolve import select_nfft
    heuristic_seg = select_nfft(taps, n, templates)
    record = TuneRecord(
        config=KernelConfig(segment=scored[0], source=SOURCE_TUNED),
        heuristic=KernelConfig(segment=0),
        objective="energy",
        score=seg_j(scored[0]),
        heuristic_score=seg_j(heuristic_seg),
        candidates=len(segments),
        measured=0,
    )
    cache.put(key, record)
    if save:
        cache.save()
    return TuneResult(key=key, record=record, measurements=0, replayed=False)


# ---------------------------------------------------------------------------
# The paper's Sec. 4 "common configuration" result, on the software axis
# ---------------------------------------------------------------------------

def common_config(
    cache: TuningCache,
    *,
    model_device: DeviceSpec = TESLA_V100,
) -> tuple[KernelConfig, float]:
    """The single config minimising average modelled regret across every
    tuned FFT length — the software mirror of the paper's one-common-clock
    result (Sec. 4: one well-chosen setting recovers ~50% of the savings).

    Only the length-portable axis (``tile_b``) generalises; splits and
    segments stay per-length.  The cost model does not price the tile, so
    every portable config models alike and the heuristic (first in the
    pool) wins with zero regret until the model learns tiles.  Returns ``(config, regret)``
    where ``regret`` is the mean relative J/transform excess over each
    length's own tuned optimum (0.0 = no loss anywhere).
    """
    keys = [k for k in cache.keys() if k.kind in FFT_KINDS
            and len(k.shape) == 1]
    if not keys:
        raise ValueError("no tuned FFT lengths in the cache")
    pool: list[KernelConfig] = [HEURISTIC]
    for k in keys:
        rec = cache.get(k)
        portable = KernelConfig(tile_b=rec.config.tile_b,
                                source=SOURCE_COMMON)
        if portable not in pool:
            pool.append(portable)

    def model_j(cfg: KernelConfig, key: ConfigKey) -> float:
        case = FFTCase(n=key.shape[0], transform=key.kind)
        res = dvfs.sweep(fft_workload(case, model_device), model_device)
        return dvfs.energy_per_transform(res, case.n_fft)["optimal_j"]

    # One sweep per (config, key): the regret loop reuses these figures.
    j = {(c, k): model_j(c, k) for c in pool for k in keys}
    best_per_key = {k: min(j[(c, k)] for c in pool) for k in keys}
    regrets = []
    for cfg in pool:
        regrets.append(float(np.mean(
            [j[(cfg, k)] / best_per_key[k] - 1.0 for k in keys])))
    i = int(np.argmin(regrets))
    cfg = pool[i]
    if cfg is not HEURISTIC:
        cfg = dataclasses.replace(cfg, source=SOURCE_COMMON)
    return cfg, regrets[i]


def install_common_default(
    cache: TuningCache | None = None,
    *,
    model_device: DeviceSpec = TESLA_V100,
) -> TuningContext:
    """Build a context whose untuned keys fall back to the common config
    (instead of the heuristics) and install it process-wide."""
    from repro.tune.context import set_tuning_context
    cache = cache if cache is not None else TuningCache.load()
    ctx = TuningContext(cache)
    try:
        common, _ = common_config(cache, model_device=model_device)
    except ValueError:
        common = None
    ctx.common = common
    set_tuning_context(ctx)
    return ctx
