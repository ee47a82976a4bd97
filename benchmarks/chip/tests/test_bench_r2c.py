"""The R2C cell ``fft_r2c_1m_1gib``: its rehearsal against the float64
reference and the control, faults on its timed path, and its two
readers on hand-made spans and traces.

The rehearsal's length (2^15) is past twice the kernel's, so it runs the
packed four-step and the Hermitian split the cell runs at 2^20."""
from types import SimpleNamespace

import pytest

from conftest import CHIP

CELL = "fft_r2c_1m_1gib"
SEED = 3_100_000_019


def _run(run, **kw):
    c = run.load_cell(CELL, rehearse=True)
    return run.run_cell(c, seed=SEED, seconds=1.0, trace=False,
                        rehearse=True, **kw)


def test_rehearsal_is_correct_and_control_is_not(run):
    res = _run(run, control=True)
    limit = res["checks"]["fft_rel_l2"]["limit"]
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert res["checks"]["fft_rel_l2"]["value"] < limit
    assert res["control"]["fft_rel_l2"] > limit, res["control"]


def _zero_dc(fn):
    return lambda x: fn(x).at[..., 0].set(0)


def _nyquist_dropped(fn):
    return lambda x: fn(x).at[..., -1].set(0)


def _nyquist_shifted(fn):
    def broken(x):
        y = fn(x)
        return y.at[..., -1].set(y[..., -2])
    return broken


@pytest.mark.parametrize("fault", [_zero_dc, _nyquist_dropped,
                                   _nyquist_shifted],
                         ids=lambda f: f.__name__[1:])
def test_fault_on_the_timed_path_is_not_correct(run, monkeypatch, fault):
    run._program()
    from repro.serving.cache import PlanSweepCache
    build = PlanSweepCache._build

    def broken_build(self, key, **kw):
        entry = build(self, key, **kw)
        entry.fn = fault(entry.fn)
        return entry

    monkeypatch.setattr(PlanSweepCache, "_build", broken_build)
    res = _run(run)
    assert not res["correct"], res["checks"]


# --------------------------------------------------------------------------
# readers
# --------------------------------------------------------------------------

def _reader(run, name):
    return run.load_module(CHIP / "metrics" / f"{name}.py",
                           f"chipbench_metric_{name}")


def _span(name, **attrs):
    return SimpleNamespace(name=name, attrs=attrs)


PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _view(run, tracemod, work, *, spans=(), ops=()):
    red = tracemod.Reduced(ops={"/device:TPU:0": list(ops)},
                           host=[("bench.window", 0, 10**9)])
    return run.RunView(cell=None, record={}, spans=list(spans), trace=red,
                       bounds=(0, 10**9), peak=PEAK, work=work,
                       tracemod=tracemod)


#: 10 ms of device time: 6 in the kernels, 4 in XLA ops.
OPS = [("fft_axis1_twiddle_pallas", 0, 3 * 10**6),
       ("fft_t_pallas", 3 * 10**6, 6 * 10**6),
       ("fusion", 6 * 10**6, 8 * 10**6),
       ("custom-call:X64Combine", 8 * 10**6, 10**7)]


def test_fft_roofline_r2c_counts_the_r2c_batches(run, tracemod, work):
    read = _reader(run, "fft_roofline.r2c").read
    n = 2**20
    spans = [_span("batch", transform="r2c", plan="four-step", rows=256,
                   shape=(n,)),
             # a child inherits the batch's attributes: not counted again
             _span("compute", transform="r2c", rows=256, shape=(n,)),
             _span("collect", transform="r2c", rows=256, shape=(n,)),
             _span("batch", transform="c2c", rows=64, shape=(4096,))]
    bytes_ = 2 * 256 * (4 * n + 8 * (n // 2 + 1))       # two batches
    value = read(_view(run, tracemod, work, spans=spans * 2, ops=OPS))
    assert value == pytest.approx(100 * bytes_ / 819e9 / 1e-2)
    assert 0 < value <= 100


def test_fft_roofline_r2c_reads_nothing_without_tagged_batches(
        run, tracemod, work):
    read = _reader(run, "fft_roofline.r2c").read
    untagged = [_span("batch", rows=256, shape=(2**20,))]
    assert read(_view(run, tracemod, work, spans=untagged, ops=OPS)) is None
    c2c = [_span("batch", transform="c2c", rows=64, shape=(4096,))]
    assert read(_view(run, tracemod, work, spans=c2c, ops=OPS)) is None


def test_xla_share_r2c_is_the_non_kernel_device_time(run, tracemod, work):
    read = _reader(run, "xla_share.r2c").read
    assert read(_view(run, tracemod, work, ops=OPS)) == pytest.approx(40.0)
    assert read(_view(run, tracemod, work)) is None
