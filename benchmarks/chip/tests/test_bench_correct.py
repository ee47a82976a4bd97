"""``correct`` separates the program from its control and from faults.

Each test drives a whole run of a cell in this process at the tiny sizes
of its traffic file's ``rehearse`` block, skipping the harness's look for
a chip: set-up, window, and the check against the plain reference.

* the program passes every check, and the control (the reference in the
  program's place at ``high`` precision) fails one;
* with the timed path broken underneath, ``correct`` comes out false:
  an answer altered where it is produced, and (FFT cells) half of each
  batch left untransformed; (pulsar) a sift run at a lowered threshold,
  or with no clustering, serves what the reference's sift does not.
"""
import functools
import json

import jax

import jax.numpy as jnp
import pytest

from conftest import CHIP

BENCH = json.loads((CHIP.parent.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
PULSAR = [w["name"] for w in BENCH["workloads"]
          if w["config"] == "pulsar_htru"]
SEED = 2_900_000_017


def _run(run, cell, traffic=None, **kw):
    c = run.load_cell(cell, rehearse=True)
    c.traffic.update(traffic or {})
    return run.run_cell(c, seed=SEED, seconds=1.0, trace=False,
                        rehearse=True, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_program_passes_and_control_fails(run, cell):
    res = _run(run, cell, control=True)
    assert res["correct"], res["checks"]
    assert any(res["control"][name] > c["limit"]
               for name, c in res["checks"].items()
               if name in res["control"]), (res["control"], res["checks"])


def _zero_bin(fn):
    return lambda x: fn(x).at[..., 0].set(0)


def _half_untransformed(fn):
    def broken(x):
        y = fn(x)
        half = y.shape[0] // 2
        return jnp.concatenate([y[:half], jnp.asarray(x)[half:]])
    return broken


def _stat_altered(fn):
    return lambda x: fn(x).at[..., 4].multiply(1.001)


def _candidate_dropped(fn):
    return lambda x: fn(x).at[..., 0, :].set(-1.0)


FAULTS = [(c, f) for c in CELLS if c not in PULSAR
          for f in (_zero_bin, _half_untransformed)] + [
    (c, f) for c in PULSAR for f in (_stat_altered, _candidate_dropped)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f in FAULTS])
def test_fault_is_not_correct(run, monkeypatch, cell, fault):
    from repro.serving.cache import PlanSweepCache
    build = PlanSweepCache._build

    def broken_build(self, key, **kw):
        entry = build(self, key, **kw)
        entry.fn = fault(entry.fn)
        return entry

    monkeypatch.setattr(PlanSweepCache, "_build", broken_build)
    res = _run(run, cell)
    assert not res["correct"], res["checks"]


# A lowered threshold changes what is served only where the sift's pool
# holds cells under the threshold that no stronger cell absorbs: in blocks
# of noise alone, which the sift at its threshold leaves empty.
SIFT_FAULTS = {"threshold_lowered": (dict(threshold=2.0),
                                     {"injected": [[], []]}),
               "clusters_not_merged": (dict(dm_tol=-1), None)}


@pytest.mark.parametrize("cell", PULSAR)
@pytest.mark.parametrize("fault", sorted(SIFT_FAULTS))
def test_sift_fault_is_not_correct(run, monkeypatch, cell, fault):
    run._program()
    import repro.search.pipeline as pipeline
    forced, traffic = SIFT_FAULTS[fault]

    def broken(stat, level, **kw):
        return sift(stat, level, **{**kw, **forced})

    sift = pipeline.sift_candidates
    monkeypatch.setattr(pipeline, "sift_candidates",
                        functools.wraps(sift)(broken))
    jax.clear_caches()                 # trace the search again, broken
    try:
        res = _run(run, cell, traffic)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert res["checks"]["sift_false"]["value"] > 0, res["checks"]
    assert not res["correct"]


@pytest.mark.parametrize("cell", PULSAR)
def test_program_passes_on_noise_alone(run, cell):
    res = _run(run, cell, {"injected": [[], []]})
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_service_is_built_as_the_configuration_states(run, monkeypatch,
                                                      cell):
    run._program()
    import repro.serving as serving
    built = {}

    class Recording(serving.FFTService):
        def __init__(self, *args, **kw):
            built.update(kw)
            super().__init__(*args, **kw)

    monkeypatch.setattr(serving, "FFTService", Recording)
    res = _run(run, cell)
    cfg = run.load_cell(cell).config
    for key in ("batch_bytes", "coalesce_requests", "bucket_batches",
                "max_retained_receipts"):
        assert built[key] == cfg[key], key
    assert res["device"]["x64"] is (cfg["precision"] == "fp64")
